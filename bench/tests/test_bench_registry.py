"""BENCHMARK.json resolves by name to its files, and keeps the contract's
shape: names, units, bounds, metrics per cell, chips and the run budget."""

from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import registry  # noqa: E402

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = registry.cell(cell)
    for path in (c.app_path, c.reference_path, c.driver_path):
        assert path.is_file(), path
    for m in c.per_layer:
        assert c.metric_path(m["name"]).is_file()
        assert hasattr(registry.load_module(c.metric_path(m["name"])),
                       "read")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
    assert c.chips in (1, 4)


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)


def test_configs_are_files_under_paths_and_used():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "apps" / f"{cfg['app']}.py").is_file()
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_share_and_run_budget():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    # a full check of 24 cells: 2 + 14 runs a cell, each run_seconds + 60,
    # 2 x 90 s of compiles a cell, 1200 s spare, within 43,200 s
    assert ((2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180
            + 1200) <= 43200


def test_a_dropped_in_cell_is_found_without_an_edit(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = dict(BENCH)
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "wc_large.burst", "config": "wordcount-phoenix-large",
         "traffic": "burst", "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    burst = {"driver": "service", "batch_items": 1024,
             "window_size": 4, "window_slide": 4, "snapshot_every": 2,
             "warmup_batches": 4, "trace_batches": 4}
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(burst))
    c = registry.cell("wc_large.burst", registry.benchmark(root), root=root)
    assert c.traffic["batch_items"] == 1024
    assert c.driver_path.name == "service.py"
    assert {m["name"] for m in c.end_to_end} == {"setup_s"}
