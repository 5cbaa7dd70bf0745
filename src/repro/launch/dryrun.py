"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and derive the roofline terms (EXPERIMENTS.md §Dry-run / §Roofline).

``main()`` asks for 512 fake CPU devices through ``XLA_FLAGS`` before JAX
initializes its backend; importing this module sets nothing, so the
``build_cell`` helpers can be used on any mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun
"""

import argparse
import json
import os
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import (SHAPES, all_cells, cell_supported, default_kv_dtype,
                           get_config, input_specs)
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.registry import get_model
from repro.roofline import analysis as roofline
from repro.serving.serve_step import make_decode_step, make_prefill
from repro.training.train_step import (TrainConfig, abstract_train_state,
                                       make_train_step)


def _abstract(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


#: train cells needing more microbatches to fit the 16 GiB v5e budget
#: (activation residency scales with per-microbatch tokens).
MB_OVERRIDES = {
    "qwen1.5-32b": 32,
    "qwen2.5-14b": 32,
    "gemma2-27b": 32,
    "internvl2-26b": 32,
    "llama4-scout-17b-a16e": 32,
}


def build_cell(arch: str, shape_name: str, mesh, *, microbatches: int = 16,
               tc_overrides: dict | None = None):
    """Returns (jit_fn, example_args (avals), donate_note) for the cell."""
    cfg = get_config(arch)
    model = get_model(cfg)
    shape = SHAPES[shape_name]
    from jax.sharding import NamedSharding

    from repro.distributed.act_sharding import set_mesh

    set_mesh(mesh)
    ns = lambda spec: NamedSharding(mesh, spec)

    if shape.kind == "train":
        mb = max(microbatches, MB_OVERRIDES.get(arch, 0))
        while shape.global_batch % mb:
            mb //= 2
        tc = TrainConfig(num_microbatches=mb, loss_mode="sharded",
                         **(tc_overrides or {}))
        opt_avals = abstract_train_state(model)
        batch_avals = input_specs(cfg, shape)
        pspecs = shd.param_pspecs(model.abstract_params(), mesh, fsdp=True)
        batch_ps = shd.batch_pspecs(batch_avals, mesh)
        from jax.sharding import PartitionSpec as P
        from repro.models.common import dp_axes, pick

        vshard = pick(mesh, cfg.vocab_size, "model")
        logits_ps = P(dp_axes(mesh) or None, None, vshard)
        step = make_train_step(model, tc, param_pspecs=pspecs,
                               batch_pspecs=batch_ps,
                               logits_pspec=logits_ps)
        opt_sh = jax.tree.map(
            ns, shd.param_pspecs(opt_avals, mesh, fsdp=True))
        batch_sh = jax.tree.map(ns, batch_ps)
        fn = jax.jit(step, in_shardings=(opt_sh, batch_sh),
                     out_shardings=(opt_sh, None), donate_argnums=(0,))
        return fn, (opt_avals, batch_avals)

    kv_dtype = default_kv_dtype(arch, shape_name)
    params_avals = model.abstract_params()
    # serve params are replicated over the DP axes unless they don't fit a
    # chip when only model-sharded (llama4-scout: ~200 GB bf16 / 16-way TP).
    serve_fsdp = arch in ("llama4-scout-17b-a16e",)
    params_sh = jax.tree.map(ns, shd.param_pspecs(params_avals, mesh,
                                                  fsdp=serve_fsdp))
    state_avals = jax.eval_shape(
        lambda: model.init_decode_state(shape.global_batch, shape.seq_len,
                                        kv_dtype=kv_dtype))
    state_sh = jax.tree.map(
        ns, shd.decode_state_pspecs(state_avals, mesh, cfg))

    if shape.kind == "prefill":
        batch_avals = input_specs(cfg, shape)
        batch_sh = jax.tree.map(ns, shd.batch_pspecs(batch_avals, mesh))
        pf = make_prefill(model)
        fn = jax.jit(pf, in_shardings=(params_sh, batch_sh, state_sh),
                     out_shardings=(None, state_sh), donate_argnums=(2,))
        return fn, (params_avals, batch_avals, state_avals)

    # decode
    tok_avals = input_specs(cfg, shape)["tokens"]
    tok_sh = ns(shd.tokens_pspec(shape.global_batch, mesh))
    rng_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    dec = make_decode_step(model)
    fn = jax.jit(dec, in_shardings=(params_sh, state_sh, tok_sh, None),
                 out_shardings=(tok_sh, state_sh), donate_argnums=(1,))
    return fn, (params_avals, state_avals, tok_avals, rng_aval)


def _cpu_upcast_artifact_bytes(compiled) -> float:
    """Sum of >256 MiB f32 buffers produced by converting bf16/s8 tensors —
    the XLA:CPU bf16-matmul upcast artifact (absent on TPU)."""
    import math
    import re

    txt = compiled.as_text()
    shapes = {}
    for m in re.finditer(r"%([\w\.\-]+) = (bf16|s8|f32)\[([\d,]+)\]", txt):
        shapes[m.group(1)] = (m.group(2),
                              math.prod(int(x) for x in m.group(3).split(",")))
    total = 0.0
    seen = set()
    for m in re.finditer(
            r"%[\w\.\-]+ = f32\[([\d,]+)\][^=]*?(?:convert|copy)\(%([\w\.\-]+)\)",
            txt):
        elems = math.prod(int(x) for x in m.group(1).split(","))
        src = m.group(2)
        if elems * 4 < 256 * 2 ** 20 or src in seen:
            continue
        sdt = shapes.get(src, ("", 0))[0]
        if sdt in ("bf16", "s8"):
            seen.add(src)
            total += elems * 4
    return total


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             microbatches: int = 16, verbose: bool = True) -> dict:
    ok, why = cell_supported(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            fn, avals = build_cell(arch, shape_name, mesh,
                                   microbatches=microbatches)
            lowered = fn.lower(*avals)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cfg = get_config(arch)
            model = get_model(cfg)
            params_avals = model.abstract_params()
            from repro.models.registry import param_count

            n_params = sum(l.size for l in jax.tree.leaves(params_avals))
            if cfg.num_experts:
                from repro.models.registry import active_param_count

                n_active = active_param_count(cfg, params_avals)
            else:
                n_active = n_params
            shape = SHAPES[shape_name]
            mf = roofline.model_flops_estimate(
                cfg, shape.kind, shape.seq_len, shape.global_batch,
                n_params, n_active)
            rl = roofline.analyze(compiled, arch=arch, shape=shape_name,
                                  mesh_name=mesh_name, chips=chips,
                                  model_flops=mf)
            upcast = _cpu_upcast_artifact_bytes(compiled)
            out = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "ok",
                "compile_s": round(time.time() - t0, 1),
                "n_params": int(n_params), "n_active": int(n_active),
                "memory": {
                    "argument_bytes": mem.argument_size_in_bytes,
                    "output_bytes": mem.output_size_in_bytes,
                    "temp_bytes": mem.temp_size_in_bytes,
                    "alias_bytes": mem.alias_size_in_bytes,
                    "peak_per_chip_gib": round(
                        (mem.argument_size_in_bytes + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
                        / 2 ** 30, 3),
                    # XLA:CPU upcasts bf16/int8 dot operands to f32 and
                    # hoists the converts (no native bf16 MXU); a TPU build
                    # never materializes these copies.  Adjusted peak is the
                    # TPU-native estimate.
                    "cpu_upcast_artifact_gib": round(upcast / 2 ** 30, 3),
                    # floor at argument+output residency: the artifact scan
                    # has no liveness info, so it can over-subtract temps
                    "peak_tpu_adjusted_gib": round(max(
                        (mem.argument_size_in_bytes + mem.output_size_in_bytes
                         + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                         - upcast),
                        (mem.argument_size_in_bytes + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes)) / 2 ** 30, 3),
                },
                "roofline": rl.to_dict(),
            }
            if verbose:
                print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                      f"compile={out['compile_s']}s "
                      f"peak={out['memory']['peak_per_chip_gib']}GiB/chip "
                      f"(tpu-adj {out['memory']['peak_tpu_adjusted_gib']}) "
                      f"dominant={rl.dominant} step={rl.step_s*1e3:.2f}ms "
                      f"mfu={rl.mfu:.3f}")
                print("  memory_analysis:", mem)
                print("  cost_analysis: flops/chip=%.3e bytes/chip=%.3e"
                      % (rl.flops, rl.bytes_accessed))
                print("  collectives:", json.dumps(rl.collective_ops))
            return out
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "compile_s": round(time.time() - t0, 1)}


def main():
    # before the first device query: JAX fixes the device count when its
    # backend initializes
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=16)
    ap.add_argument("--out", default=None, help="directory for JSON results")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for (a, s, _, _) in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        for m in meshes:
            r = run_cell(arch, shape, m, microbatches=args.microbatches)
            results.append(r)
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                fn = f"{arch}_{shape}_{m}.json".replace("/", "_")
                with open(os.path.join(args.out, fn), "w") as f:
                    json.dump(r, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} ==")
    for r in results:
        if r["status"] == "error":
            print(f"  ERROR {r['arch']} × {r['shape']} × {r['mesh']}: "
                  f"{r['error']}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
