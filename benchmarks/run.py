"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping to the paper:
  bench_phoenix_suite     Figs 6/7  (the up-to-2.0x optimizer claim)
  bench_memory            Figs 8/9  (heap/GC pressure -> bytes pressure)
  bench_optimizer_overhead  §4.3    (81us detect / 7.6ms transform)
  bench_flow_sweep        Fig 10    (speedup vs (key,value) pressure)
  bench_scalability       Fig 5     (scaling -> collective-bytes scaling)
  bench_integrations      beyond paper (grad-accum / MoE / decode combiners)
  bench_streaming         beyond paper (continuous-ingestion service)
  bench_resilience        beyond paper (recovery time, failover latency)

A module that raises prints a ``*_FAILED`` row and the harness exits
non-zero at the end, so CI can gate on benchmark health.  ``--json PATH``
writes the parsed rows as a machine-readable artifact (the CI smoke job
uploads ``BENCH_ci.json`` to start the perf trajectory), and
``--preset ci`` selects a tiny workload scale via REPRO_BENCH_SCALE.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import traceback

# self-locating: `python benchmarks/run.py` puts benchmarks/ (not the repo
# root) on sys.path; make `benchmarks.*` and `repro.*` importable either way
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

MODULE_NAMES = (
    "bench_phoenix_suite",
    "bench_memory",
    "bench_optimizer_overhead",
    "bench_flow_sweep",
    "bench_scalability",
    "bench_integrations",
    "bench_streaming",
    "bench_resilience",
)

CI_SCALE = 0.05


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", choices=("full", "ci"), default="full",
                    help="ci = tiny workloads for the smoke job")
    ap.add_argument("--scale", type=float, default=None,
                    help="explicit workload scale (overrides --preset)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write parsed rows + failures as JSON")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark modules to run")
    args = ap.parse_args(argv)

    from benchmarks.common import bench_scale, parse_rows

    # precedence: --scale > --preset ci > pre-set REPRO_BENCH_SCALE > 1.0
    if args.scale is not None:
        scale = args.scale
    elif args.preset == "ci":
        scale = CI_SCALE
    else:
        scale = bench_scale()
    os.environ["REPRO_BENCH_SCALE"] = str(scale)

    import importlib

    from repro.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    names = args.only if args.only else MODULE_NAMES
    rows: list[dict] = []
    failures: list[dict] = []
    print("name,us_per_call,derived")
    for name in names:
        buf = io.StringIO()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            with contextlib.redirect_stdout(buf):
                mod.main()
        except Exception:
            err = traceback.format_exc()
            failures.append({"module": name, "traceback": err})
            sys.stdout.write(buf.getvalue())
            print(f"{name}_FAILED,0,")
            print(err, file=sys.stderr)
            continue
        text = buf.getvalue()
        sys.stdout.write(text)
        rows.extend(parse_rows(text))

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"scale": scale, "preset": args.preset, "rows": rows,
                       "failures": failures}, f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}")

    if failures:
        print(f"# {len(failures)} benchmark module(s) FAILED: "
              + ", ".join(f["module"] for f in failures), file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
