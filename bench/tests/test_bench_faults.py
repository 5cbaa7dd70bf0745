"""A run with the timed path broken underneath reports ``correct`` false.

Each test drives the whole of a rehearsal run (the look for a chip is
skipped by ``--rehearse``) with one fault planted in the program: half of
the input left out, an answer altered where it is produced, an ingest that
leaves the service state unchanged, and the exchange between chips left
out."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _bench_subproc import bench, result_line  # noqa: E402

HALF_THE_INPUT = """
from repro.core import api
_run = api.MapReduce.run
api.MapReduce.run = lambda self, items, **kw: _run(
    self, items[: items.shape[0] // 2], **kw)
"""

ANSWER_ALTERED = """
import numpy as np
from repro.core import api
_init = api.MapReduceResult.__init__
def _alter(self, keys, values, counts, *a, **kw):
    values = np.array(values)
    values[1] += 1
    _init(self, keys, values, counts, *a, **kw)
api.MapReduceResult.__init__ = _alter
"""

STATE_UNCHANGED = """
from repro.core import api
api.Compiled.ingest_state = lambda self, state, items, n_valid: state
"""

NO_EXCHANGE = """
import jax
jax.lax.all_to_all = lambda x, axis_name, split_axis, concat_axis, **kw: x
"""

CASES = [
    ("wc_large.batch", HALF_THE_INPUT, 1),
    ("hg_large.batch", HALF_THE_INPUT, 1),
    ("wc_large.batch", ANSWER_ALTERED, 1),
    ("wc_large.ingest", ANSWER_ALTERED, 1),
    ("wc_large.ingest", STATE_UNCHANGED, 1),
    ("wc_large.shuffle_x4", NO_EXCHANGE, 4),
    ("wc_large.shuffle_x4", ANSWER_ALTERED, 4),
]


@pytest.mark.parametrize("cell,patch,devices", CASES,
                         ids=[f"{c}-{i}" for i, (c, _, _) in
                              enumerate(CASES)])
def test_fault_is_not_correct(cell, patch, devices):
    proc = bench(["--workload", cell, "--seed", "17", "--seconds", "0.2",
                  "--rehearse"], devices=devices, patch=patch)
    out = result_line(proc)
    assert out["correct"] is False
    assert out["checks"]["wrong_keys"]["value"] > 0
    assert out["failed"] >= 1
