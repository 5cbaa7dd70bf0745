"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file
names its ``app``; the files are then found by name alone:

- ``bench/apps/<app>.py``: the job's map/reduce and the input generator
  (``make_app``, ``generate``, ``items_shape``, ``pairs``, ``key_space``);
  the input is one array or a pytree of columns;
- ``bench/reference/<app>.py``: the plain reference, NumPy only: either
  ``table(items, cfg)``, a dict ``{"values": {name: ndarray[K, ...]},
  "counts": ndarray[K]}``, or ``counts(items, cfg)`` for a job whose
  values are its counts (``bench/harness.py`` says how columns are named
  and compared);
- ``bench/traffic/<traffic>.json``: the traffic's parameters and its
  ``driver``;
- ``bench/drivers/<driver>.py``: the entry point the window drives;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

The configuration's file may state a tolerance for a float column of the
reference's table, ``"tolerance": {name: {"rtol": r, "atol": a, "why":
"<reason>"}}``; every other column is compared exactly.

So a new cell, configuration, traffic mix or metric is a new file and an
entry in ``BENCHMARK.json``, never an edit of these files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (metric files have dots in their
    names, so they are not importable as packages)."""
    path = Path(path).resolve()
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    app_path: Path
    reference_path: Path
    driver_path: Path
    bench_dir: Path = BENCH_DIR

    def metric_path(self, name: str) -> Path:
        return self.bench_dir / "metrics" / f"{name}.py"


def _overridden(entry: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in entry.items() if k != "rehearse"}
    if rehearse:
        out.update(entry.get("rehearse", {}))
    return out


def _applies(entry: dict, cell: str, e2e_names) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") is None or entry["moves"] in e2e_names


def cell(name: str, bench: dict | None = None, *, rehearse: bool = False,
         root: Path = ROOT) -> Cell:
    """Resolve cell ``name``; ``rehearse`` applies the files' small
    ``rehearse`` sizes (CPU rehearsals only, never a measurement)."""
    bench = bench if bench is not None else benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = _overridden(json.load(f), rehearse)
    bench_dir = root / "bench"
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = _overridden(json.load(f), rehearse)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer,
        app_path=bench_dir / "apps" / f"{config['app']}.py",
        reference_path=bench_dir / "reference" / f"{config['app']}.py",
        driver_path=bench_dir / "drivers" / f"{traffic['driver']}.py",
        bench_dir=bench_dir)
