"""Each batch cell end to end at its small rehearsal sizes on the CPU,
through its own driver, measured and traced; never a measurement."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import ROOT, bench, check_line, result_line  # noqa: E402,I001

sys.path.insert(0, str(ROOT))
from bench import registry  # noqa: E402

ONE_CHIP = [w["name"] for w in registry.benchmark()["workloads"]
            if w["chips"] == 1 and registry.cell(w["name"]).traffic[
                "driver"] == "batch"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_batch_cell_rehearses(cell, trace):
    proc = bench(["--workload", cell, "--seed", str(2**31 + 11),
                  "--seconds", "0.5", "--trace", str(trace), "--rehearse"])
    check_line(cell, result_line(proc), trace)
    assert "check wrong_keys: 0 (limit 0)" in proc.stderr.splitlines()[-1]
