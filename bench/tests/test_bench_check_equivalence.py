"""On count-valued answers the table check gives the same ``wrong_keys``
as the check it replaced, which compared the first value column and the
counts with one vector of expected counts.  That check is kept here as the
oracle."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import harness  # noqa: E402


def oracle_wrong_keys(answer, expected: np.ndarray) -> int:
    """The count-only check as it stood before tables were compared."""
    keys, values, counts = answer
    K = expected.shape[0]
    values = np.asarray(values).reshape(values.shape[0], -1)[:, 0]
    bad = ((np.asarray(keys)[:K] != np.arange(K))
           | (values[:K].astype(np.int64) != expected)
           | (np.asarray(counts)[:K].astype(np.int64) != expected))
    return int(bad.sum()) + int(np.count_nonzero(np.asarray(counts)[K:]))


def key_id(keys, values, counts, rng):
    keys[rng.integers(len(keys))] += 1


def value(keys, values, counts, rng):
    values[rng.integers(len(values))] -= 1


def count(keys, values, counts, rng):
    counts[rng.integers(len(counts))] += 2


def past_k(keys, values, counts, rng):
    counts[-1] = 5


def narrowed(keys, values, counts, rng):
    values[:] = values.astype(np.int16)
    counts[:] = counts.astype(np.int16)


FAULTS = {"none": [], "key_id": [key_id], "value": [value], "count": [count],
          "past_k": [past_k], "narrowed": [narrowed],
          "value_and_count": [value, count],
          "all": [key_id, value, count, past_k]}


@pytest.mark.parametrize("padded", [0, 16], ids=["K", "K+16"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_new_check_equals_the_old_on_counts(fault, seed, padded):
    rng = np.random.default_rng(seed)
    K = 4096
    expected = np.minimum(rng.zipf(1.3, K), 10**6).astype(np.int64) * 7
    keys = np.arange(K + padded, dtype=np.int32)
    counts = np.zeros(K + padded, np.int32)
    counts[:K] = expected
    values = counts.copy()
    # with no rows past K there is no count past K to plant
    plants = [p for p in FAULTS[fault] if padded or p is not past_k]
    for plant in plants:
        for _ in range(3):
            plant(keys, values, counts, rng)
    answer = (keys, values, counts)
    old = oracle_wrong_keys(answer, expected)
    assert harness.wrong_keys(answer, harness.count_table(expected)) == old
    assert (old == 0) == (not plants)


def test_the_control_reads_as_before():
    """The control narrows values and counts alike: the old check counted
    such a key once, and so does the new one."""
    from bench.control import control_answer

    expected = np.array([1, 40000, 5, 70000, 2**15], np.int64)
    ctl = control_answer(harness.count_table(expected), "int16")
    old = oracle_wrong_keys((ctl[0], ctl[1]["value"], ctl[2]), expected)
    assert old == 3
    assert harness.check([("control", *ctl)],
                         [harness.count_table(expected)])["wrong_keys"] == old
