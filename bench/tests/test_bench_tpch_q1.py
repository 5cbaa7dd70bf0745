"""TPC-H Q1 (``q1_sf10.batch``) at its rehearsal size on the CPU: the job
through ``run`` under the stream and sort flows, compared with the plain
reference by the harness's own check; the plan it derives; and the
control, which holds the int64 sums in int32 and must come out wrong."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import ROOT, bench  # noqa: E402,I001

sys.path.insert(0, str(ROOT))
from bench import harness, registry  # noqa: E402

CELL = registry.cell("q1_sf10.batch", rehearse=True)
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def q1():
    import jax

    run = harness.Run(CELL, seed=SEED, devices=jax.devices()[:1],
                      rehearse=True)
    items = run.make_items()
    host = jax.tree_util.tree_map(np.asarray, items)
    return run, items, run.expected(host)


@pytest.mark.parametrize("flow", ["stream", "sort"])
def test_q1_matches_the_reference(q1, flow):
    from repro.core import MapReduce

    run, items, expected = q1
    mr = MapReduce(run.app_mod.make_app(run.cfg), flow=flow)
    answer = harness.fetch(mr.run(items))
    checked = harness.check([("job", *answer)], [expected], run.tolerance)
    assert checked == {"answers": 1, "failed": 0, "wrong_keys": 0}
    # 4 of the 6 groups are filled, and the filter drops some rows
    counts = expected["counts"]
    assert np.count_nonzero(counts) == 4
    assert 0 < counts.sum() < run.cfg["rows"]
    # the sums pass 2^32: int32 holders would wrap
    assert np.abs(expected["values"]["3"]).max() > 2**32


def test_q1_plan_derives_five_wide_holders(q1):
    from repro.core import MapReduce
    from repro.core import plan_cache as pc

    run, _, _ = q1
    mr = MapReduce(run.app_mod.make_app(run.cfg), flow="auto")
    assert mr.plan.flow == "stream"
    assert mr.plan.spec.describe.count("monoid<add>") == 5
    assert pc.STATS.holder_bytes[mr._plan_key] == 5 * 8 + 4


def test_q1_control_is_not_correct():
    proc = bench(["--workload", "q1_sf10.batch", "--seed", "3",
                  "--seed", str(2**31 + 3), "--rehearse"],
                 script=str(ROOT / "bench" / "control.py"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    # the four filled groups' sums wrap in int32, on every seed
    assert [x["wrong_keys"] for x in lines] == [4, 4]
