"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each run is a new process: it makes the cell's input on the chip from the
seed, warms up the program (``setup_s``), then either measures for
``--seconds`` (``--trace 0``: the cell's end-to-end metrics) or runs a
short stretch of the same traffic under the profiler (``--trace 1``: the
per-layer metrics, the device's busy and window seconds and a breakdown).
Either way it then checks every answer the timed path produced against
the plain reference, prints each compared number beside its limit as the
last lines of standard error, and prints one JSON line last on standard
output.  A run that finds no TPU, or fewer chips than the cell needs,
exits non-zero and prints no result.

``--rehearse`` runs the cell's small ``rehearse`` sizes on any backend
(the CPU rehearsal of the tests); it is never a measurement.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="small sizes on any backend; never a measurement")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, registry

    try:
        cell = registry.cell(args.workload, rehearse=args.rehearse)
        import repro  # noqa: F401  the system under test
    except (KeyError, OSError, ImportError) as e:
        harness.log(f"bench: cannot set up {args.workload!r}: {e!r}")
        return 2
    try:
        result = harness.execute(cell, seed=args.seed, seconds=args.seconds,
                                 trace=bool(args.trace),
                                 rehearse=args.rehearse, t0=T0)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    for line in harness.check_lines(result):
        harness.log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
