"""The control of a cell's correctness check: it has to come out wrong.

    python bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

The control is the plain reference put in the program's place, its counts
held in the next narrower integer than the configuration states
(``control_dtype``: int16 for int32): the answers a run would compare are
made from the cell's own input, at the cell's own size, and compared with
the reference exactly as a run compares the program's.  For each seed one
JSON line gives the control's ``wrong_keys``, which must be above the
limit of 0.  The batch cells compare one answer per job, all alike; the
ingest cell compares one snapshot after every ``snapshot_every``-th
micro-batch, here ``--snapshots`` of them.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_answer(expected, dtype: str):
    """The reference in the program's place, its counts held in ``dtype``
    (the next narrower integer than the configuration states)."""
    import numpy as np

    low = expected.astype(np.dtype(dtype)).astype(np.int64)
    return (np.arange(expected.shape[0]), low, low)


def control_reading(cell, seed: int, snapshots: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench import harness, registry
    from bench.reference import windows as ref_windows

    app_mod = registry.load_module(cell.app_path)
    ref_mod = registry.load_module(cell.reference_path)
    cfg, tr = cell.config, cell.traffic
    gen = jax.jit(lambda k: app_mod.generate(cfg, k),
                  out_shardings=SingleDeviceSharding(jax.devices()[0]))
    items = np.asarray(gen(harness.prng_key(seed)))
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
            expected.append(sum(per[b % n_pool] for b in cover))
    else:
        expected = [ref_mod.counts(items, cfg)]
    answers = [("control", *control_answer(e, low))
               for e in expected]
    checked = harness.check(answers, expected)
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
