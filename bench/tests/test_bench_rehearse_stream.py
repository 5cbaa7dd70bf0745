"""The ingest and four-chip cells end to end at rehearsal sizes on the
CPU (four virtual devices for the mesh); never a measurement."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import bench, check_line, result_line  # noqa: E402


@pytest.mark.parametrize("trace", [0, 1])
def test_ingest_cell_rehearses(trace):
    proc = bench(["--workload", "wc_large.ingest", "--seed", "5",
                  "--seconds", "0.5", "--trace", str(trace), "--rehearse"])
    out = result_line(proc)
    check_line("wc_large.ingest", out, trace)
    assert "0 backend compiles" in proc.stdout or trace


@pytest.mark.parametrize("trace", [0, 1])
def test_four_chip_cell_rehearses(trace):
    proc = bench(["--workload", "wc_large.shuffle_x4", "--seed", "6",
                  "--seconds", "0.5", "--trace", str(trace), "--rehearse"],
                 devices=4)
    out = result_line(proc)
    check_line("wc_large.shuffle_x4", out, trace)
    if trace:
        assert out["metrics"]["collective_ms.batch"]["value"] > 0
