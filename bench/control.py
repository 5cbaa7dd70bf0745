"""The control of a cell's correctness check: it has to come out wrong.

    python bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

The control is the plain reference put in the program's place, computed a
step below the configuration's precision: its counts and every integer
value column held in the next narrower integer than the configuration
states (``control_dtype``: int16 for int32), every float column rounded
to bfloat16.  The answers a run would compare are made from the cell's own
input, at the cell's own size, and compared with the reference exactly as
a run compares the program's, under the configuration's tolerances.  For
each seed one JSON line gives the control's ``wrong_keys``, which must be
above the limit of 0.  The batch cells compare one answer per job, all
alike; the ingest cell compares one snapshot after every
``snapshot_every``-th micro-batch, here ``--snapshots`` of them.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_answer(expected: dict, dtype: str):
    """The reference table in the program's place, a step below the
    configuration's precision: counts and integer columns held in
    ``dtype`` (the next narrower integer than the configuration states),
    float columns rounded to bfloat16."""
    import ml_dtypes
    import numpy as np

    from bench import harness

    def lower(col):
        if np.issubdtype(col.dtype, np.integer):
            return col.astype(np.dtype(dtype)).astype(np.int64)
        return col.astype(ml_dtypes.bfloat16).astype(col.dtype)

    values = {name: lower(col) for name, col in
              harness.columns(expected["values"]).items()}
    return (np.arange(expected["counts"].shape[0]), values,
            lower(expected["counts"]))


def control_reading(cell, seed: int, snapshots: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench import harness, registry
    from bench.reference import windows as ref_windows

    app_mod = registry.load_module(cell.app_path)
    ref_mod = registry.load_module(cell.reference_path)
    cfg, tr = cell.config, cell.traffic
    tolerance = harness.tolerances(cfg, app_mod, ref_mod)
    gen = jax.jit(lambda k: app_mod.generate(cfg, k),
                  out_shardings=SingleDeviceSharding(jax.devices()[0]))
    items = jax.tree_util.tree_map(np.asarray, gen(harness.prng_key(seed)))
    low = cfg["control_dtype"]
    if tr["driver"] == "service":
        batch = int(tr["batch_items"])
        n_pool = items.shape[0] // batch
        per = [ref_mod.counts(items[i * batch:(i + 1) * batch], cfg)
               for i in range(n_pool)]
        every = int(tr["snapshot_every"])
        expected = []
        for k in range(1, snapshots + 1):
            cover = ref_windows.covered(k * every, int(tr["window_size"]),
                                        int(tr["window_slide"]))
            expected.append(harness.count_table(
                sum(per[b % n_pool] for b in cover)))
    else:
        expected = [harness.reference_table(ref_mod, items, cfg)]
    answers = [("control", *control_answer(e, low))
               for e in expected]
    checked = harness.check(answers, expected, tolerance)
    return {"workload": cell.name, "seed": seed, "control_dtype": low,
            "answers": checked["answers"],
            "wrong_keys": checked["wrong_keys"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append", required=True)
    p.add_argument("--seed", type=int, action="append", required=True)
    p.add_argument("--snapshots", type=int, default=80)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--set", action="append", default=[], metavar="KEY=N",
                   help="override a configuration size (tests)")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import registry

    for name in args.workload:
        cell = registry.cell(name, rehearse=args.rehearse)
        for kv in args.set:
            k, v = kv.split("=")
            cell.config[k] = int(v)
        for seed in args.seed:
            print(json.dumps(control_reading(cell, seed, args.snapshots)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
