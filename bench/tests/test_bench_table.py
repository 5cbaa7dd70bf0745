"""The check compares a whole result table, column by column, with the
reference: a table-valued toy job (``bench/tests/toy/``) with columnar
items, an exact int32 sum column, a float32 mean column under a stated
tolerance, and counts.  Its configuration lives in a temporary root beside
a copy of ``bench/``, never in ``BENCHMARK.json``."""

from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import ROOT, bench, result_line  # noqa: E402,I001

sys.path.insert(0, str(ROOT))
from bench import harness, registry  # noqa: E402

TOY = Path(__file__).resolve().parent / "toy"
CELL = "toy.batch"
CONFIG = json.loads((TOY / "config.json").read_text())
RTOL = CONFIG["tolerance"]["1"]["rtol"]


def make_root(root: Path, config: dict) -> Path:
    """A checkout holding ``bench/``, the toy's files and a
    ``BENCHMARK.json`` whose one cell runs the toy through the batch
    driver; the program is found in this repository's ``src``."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(TOY / "app.py", root / "bench" / "apps" / "toy_table.py")
    shutil.copy(TOY / "reference.py",
                root / "bench" / "reference" / "toy_table.py")
    (root / "bench" / "configs" / "toy-table.json").write_text(
        json.dumps(config))
    real = registry.benchmark()
    bench_json = {k: real[k] for k in ("command", "paths", "run_seconds")}
    bench_json["configs"] = [{"name": "toy-table", "source": "a test",
                              "file": "bench/configs/toy-table.json",
                              "reduced": [], "why": "test"}]
    bench_json["workloads"] = [{"name": CELL, "config": "toy-table",
                                "traffic": "batch", "chips": 1,
                                "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        bench_json[group] = [
            dict(m, workloads=[CELL]) for m in real[group]
            if "wc_large.batch" in m.get("workloads", ["wc_large.batch"])]
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("toy"), CONFIG)


def toy_run(root):
    cell = registry.cell(CELL, registry.benchmark(root), root=root)
    return harness.Run(cell, seed=7, devices=None, rehearse=True)


def run_toy(toy_root, patch: str = ""):
    proc = bench(["--workload", CELL, "--seed", str(2**31 + 5),
                  "--seconds", "0.2", "--rehearse"], patch=patch,
                 root=toy_root, cwd=toy_root)
    return result_line(proc), proc.stderr


def alter(body: str) -> str:
    """A patch that changes every result where the program makes it:
    ``body`` edits ``keys``, ``values`` (a list of the columns) and
    ``counts``, all host copies."""
    return f"""
import numpy as np
from repro.core import api
_init = api.MapReduceResult.__init__
def _alter(self, keys, values, counts, *a, **kw):
    keys, counts = np.array(keys), np.array(counts)
    values = [np.array(v) for v in values]
{body}
    _init(self, keys, tuple(values), counts, *a, **kw)
api.MapReduceResult.__init__ = _alter
"""


def test_the_toy_is_correct_and_logs_its_float_error(toy_root):
    out, err = run_toy(toy_root)
    assert out["correct"] is True and out["attempted"] >= 1
    assert out["checks"]["wrong_keys"] == {"value": 0, "limit": 0}
    line = [ln for ln in err.splitlines()
            if ln.startswith("column 1: largest relative error")]
    assert len(line) == 1 and f"rtol {RTOL!r}" in line[0]
    assert not any(ln.startswith("column 0:") for ln in err.splitlines())


NOISE = alter(f"    values[1] = values[1] * np.float32(1 + {RTOL / 10})")

FAULTS = {
    "int_column_off_by_one": alter("    values[0][3] += 1"),
    "float_beyond_tolerance": alter(
        f"    values[1][2] *= np.float32(1 + {RTOL * 10})"),
    "count_altered": alter("    counts[5] += 1"),
    "column_missing": alter("    values = values[:1]"),
    "int_sum_in_int16": alter(
        "    values[0] = values[0].astype(np.int16).astype(np.int32)"),
}


def test_noise_inside_the_tolerance_is_correct(toy_root):
    out, _ = run_toy(toy_root, NOISE)
    assert out["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(toy_root, fault):
    out, _ = run_toy(toy_root, FAULTS[fault])
    assert out["correct"] is False
    assert out["checks"]["wrong_keys"]["value"] > 0 and out["failed"] >= 1


def test_the_control_is_not_correct(toy_root):
    proc = bench(["--workload", CELL, "--seed", "3", "--seed", str(2**31 + 3)],
                 script=str(toy_root / "bench" / "control.py"),
                 cwd=toy_root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    # int16 wraps every key's sum (about 400,000) and bfloat16's 8 bits of
    # mantissa move every mean by far more than the 1e-4 tolerance
    assert [x["wrong_keys"] for x in lines] == [CONFIG["keys"]] * 2


@pytest.mark.parametrize("column", ["0", "1"])
def test_the_control_fails_on_each_column_alone(column):
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 8, 65536).astype(np.int32)
    x = rng.random(65536, dtype=np.float32)
    from bench.control import control_answer

    table = registry.load_module(TOY / "reference.py").table(
        {"key": keys, "x": x}, CONFIG)
    ctl = control_answer(table, "int16")
    right = dict(table["values"])
    right[column] = ctl[1][column]
    answer = (ctl[0], right, table["counts"])
    tol = {"1": (RTOL, 0.0)}
    assert harness.wrong_keys(answer, table, tol) == 8


BAD_TOLERANCES = {
    "no_why": {"1": {"rtol": 1e-4, "atol": 0}},
    "empty_why": {"1": {"rtol": 1e-4, "why": " "}},
    "int_column": {"0": {"rtol": 1e-4, "why": "test"}},
    "not_a_column": {"2": {"rtol": 1e-4, "why": "test"}},
    "counts": {"counts": {"atol": 1, "why": "test"}},
    "unknown_key": {"1": {"rtol": 1e-4, "why": "test", "ulp": 2}},
    "negative": {"1": {"rtol": -1e-4, "why": "test"}},
}


@pytest.mark.parametrize("case", sorted(BAD_TOLERANCES))
def test_a_bad_tolerance_raises_at_load(tmp_path, case):
    config = copy.deepcopy(CONFIG)
    config["tolerance"] = BAD_TOLERANCES[case]
    with pytest.raises(ValueError, match="tolerance on"):
        toy_run(make_root(tmp_path, config))


def test_the_stated_tolerance_is_loaded(toy_root):
    assert toy_run(toy_root).tolerance == {"1": (RTOL, 0.0)}


def test_columnar_items_run_through_the_batch_driver(toy_root):
    import jax

    run = toy_run(toy_root)
    run.devices = jax.devices()[:1]
    driver = registry.load_module(run.cell.driver_path).Driver(run)
    assert set(driver.items) == {"key", "x"}
    window = driver.window(0.05)
    assert window["job_s"] > 0
    rows, keys = CONFIG["rows"], CONFIG["keys"]
    # keys int32, the int32 sum and float32 mean, counts int32
    row = 4 + 4 + 4 + 4
    assert driver.groupby_bytes() == rows * (4 + 4) + row * keys
    answers, expected = driver.answers()
    assert all(isinstance(e["values"]["1"], np.ndarray) for e in expected)
    checked = harness.check(answers, expected, run.tolerance)
    assert checked["answers"] == len(answers) >= 1
    assert checked["wrong_keys"] == 0


def test_columns_are_named_by_their_path():
    a = np.zeros(3)
    assert list(harness.columns(a)) == ["value"]
    assert list(harness.columns((a, a))) == ["0", "1"]
    assert list(harness.columns({"sum": a, "avg": a})) == ["avg", "sum"]
    assert list(harness.columns({"q": {"sum": a}})) == ["q.sum"]


def test_float_columns_compare_by_tolerance_and_nan_matches_nan():
    want = np.array([1.0, np.nan, 0.0, 2.0])
    got = np.array([1.0 + 5e-5, np.nan, 1e-9, 2.0], np.float32)
    tbl = {"values": {"m": want}, "counts": np.ones(4, np.int64)}
    answer = (np.arange(4), {"m": got}, np.ones(4, np.int32))
    # exact where no tolerance is stated: the first and third keys differ
    assert harness.wrong_keys(answer, tbl) == 2
    assert harness.wrong_keys(answer, tbl, {"m": (1e-4, 0.0)}) == 1
    assert harness.wrong_keys(answer, tbl, {"m": (1e-4, 1e-8)}) == 0
    errs = harness.largest_relative_errors([("a", *answer)], [tbl])
    assert errs["m"] == pytest.approx(5e-5, rel=0.2)


def test_a_column_of_another_shape_is_wrong_on_every_key():
    want = np.arange(4, dtype=np.int64)
    tbl = {"values": {"value": want}, "counts": want}
    answer = (np.arange(4), np.stack([want, want], 1), want)
    assert harness.wrong_keys(answer, tbl) == 4
    short = (np.arange(4), want[:3], want)
    assert harness.wrong_keys(short, tbl) == 1


def test_item_shapes_map_over_columns(toy_root):
    run = toy_run(toy_root)
    specs = harness.map_item_shapes(lambda shape, dtype: (shape, str(dtype)),
                                    run.app_mod, run.cfg)
    rows = CONFIG["rows"]
    assert specs == {"key": ((rows,), "int32"), "x": ((rows,), "float32")}
    wc = registry.cell("wc_large.batch")
    single = harness.map_item_shapes(lambda shape, dtype: shape,
                                     registry.load_module(wc.app_path),
                                     wc.config)
    assert single == (wc.config["windows"], wc.config["window_tokens"])
