"""Compile each cell's programs at full size for a described TPU, no chip.

    JAX_PLATFORMS=cpu python bench/compile_rehearsal.py [--workload CELL ...]

For every cell (or those named): the input generator and the program the
window drives, lowered with the cell's real shapes on the devices of a
described ``v5e:2x2`` topology and compiled by the TPU compiler installed
here.  Prints each program's ``memory_analysis()`` (argument, output and
temp bytes per chip): what the chip's compiler would refuse, and how much
of a chip's memory a cell takes, before chip time is spent.  Nothing runs.
The streaming ingest step is left out: its state is made on the host's
default device, which a described chip cannot take.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _fmt(ma) -> str:
    return (f"args {ma.argument_size_in_bytes:,} B, out "
            f"{ma.output_size_in_bytes:,} B, temp {ma.temp_size_in_bytes:,} B")


def rehearse(cell, topo) -> list[str]:
    import jax
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from bench import harness, registry
    from repro.core import ExecutionOptions, MapReduce, ShuffleOptions

    app_mod = registry.load_module(cell.app_path)
    cfg, tr = cell.config, cell.traffic
    if cell.chips == 1:
        sharding = key_sharding = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(topo.devices[:cell.chips], (tr["axis"],))
        sharding = NamedSharding(mesh, P(tr["axis"]))
        key_sharding = NamedSharding(mesh, P())
    spec = harness.map_item_shapes(
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=sharding),
        app_mod, cfg)
    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32, sharding=key_sharding)
    gen = jax.jit(lambda k: app_mod.generate(
        cfg, jax.random.wrap_key_data(k)), out_shardings=sharding)
    lines = [f"{cell.name} generator: {_fmt(gen.lower(key).compile().memory_analysis())}"]

    app = app_mod.make_app(cfg)
    driver = tr["driver"]
    if driver == "batch":
        mr = MapReduce(app, flow=tr["flow"])
        compiled = mr.lower(spec).compile()
        lines.append(f"{cell.name} job ({mr.plan.flow}): "
                     f"{_fmt(compiled.memory_analysis())}")
    elif driver == "distributed":
        cap = app_mod.pairs(cfg) // cell.chips
        opts = ExecutionOptions(mesh=mesh, data_axis=tr["axis"],
                                shuffle=ShuffleOptions(
                                    capacity=cap, strict=tr["strict"],
                                    wire=tr["wire"]))
        mr = MapReduce(app, flow=tr["flow"])
        jitted = mr.lower(spec, options=opts).compile()._entry.executable
        compiled = jitted.lower(spec).compile()
        text = compiled.as_text()
        colls = sorted({op for op in ("all-to-all", "all-reduce",
                                      "all-gather", "collective-permute")
                        if op in text})
        lines.append(f"{cell.name} job ({mr.plan.flow}, per chip): "
                     f"{_fmt(compiled.memory_analysis())}; collectives "
                     f"{colls}")
    else:
        lines.append(f"{cell.name} {driver}: not rehearsed here")
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append")
    args = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from jax.experimental import topologies

    from bench import registry

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in
                              registry.benchmark()["workloads"]]
    for name in names:
        for line in rehearse(registry.cell(name), topo):
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
