"""The four-chip skew cell end to end at its rehearsal size on the CPU
(four virtual devices for the mesh); never a measurement."""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import ROOT, bench, check_line, result_line  # noqa: E402,I001

sys.path.insert(0, str(ROOT))
from bench import registry  # noqa: E402

CELL = "wc_large.shuffle_skew_x4"


@pytest.mark.parametrize("trace", [0, 1])
def test_skew_cell_rehearses(trace):
    proc = bench(["--workload", CELL, "--seed", str(2**31 + 7),
                  "--seconds", "0.5", "--trace", str(trace), "--rehearse"],
                 devices=4)
    out = result_line(proc)
    check_line(CELL, out, trace)
    if trace:
        assert out["metrics"]["collective_ms.batch"]["value"] > 0


def test_skew_driver_passes_the_planner_its_settings():
    """The driver's options take the traffic's skew fields and keep the
    distributed driver's capacity, strictness and wire."""
    from repro.core import ExecutionOptions, ShuffleOptions

    cell = registry.cell(CELL, rehearse=True)
    driver_cls = registry.load_module(cell.driver_path).Driver
    driver = object.__new__(driver_cls)  # the options setter alone, no mesh
    driver.run = types.SimpleNamespace(traffic=cell.traffic)
    driver.options = ExecutionOptions(shuffle=ShuffleOptions(
        capacity=64, strict=True, wire="raw"))
    sh = driver.options.shuffle
    assert (sh.skew, sh.sample_fraction, sh.hot_key_split_max) == (
        "auto", 0.25, 4)
    assert (sh.capacity, sh.strict, sh.wire) == (64, True, "raw")
