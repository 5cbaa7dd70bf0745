"""Plain reference of TPC-H Query 1 over lineitem's Q1 columns.

NumPy only; imports nothing of the engine.  The same filter (ship date at
most 1998-12-01 - DELTA days), the same keys (``2 f + s``, ``f`` the index
of the return flag in "ANR", ``s`` that of the line status in "FO") and
exact int64 sums; the averages are float64 divisions of the exact sums
rescaled to units, 0 for an empty group.  Rows are read in blocks, so the
full-size table needs no wide temporaries.

The program returns its reduce's tuple by position, so the columns are
named ``"0"`` to ``"6"`` in Q1's select order: sum_qty (hundredths),
sum_base_price (cents), sum_disc_price (1e-4 dollars), sum_charge (1e-6
dollars), avg_qty, avg_price, avg_disc.
"""

from __future__ import annotations

import datetime

import numpy as np

BLOCK_ROWS = 1 << 23
K = 6


def _cutoff(cfg) -> int:
    last = datetime.date(1998, 12, 1) - datetime.timedelta(
        days=cfg["delta_days"])
    return (last - datetime.date(1970, 1, 1)).days


def table(items, cfg) -> dict:
    last = _cutoff(cfg)
    n = np.asarray(items["l_shipdate"]).shape[0]
    sums = np.zeros((5, K), np.int64)  # qty, price, disc_price, charge, disc
    counts = np.zeros(K, np.int64)
    for lo in range(0, n, BLOCK_ROWS):
        col = {c: np.asarray(items[c][lo:lo + BLOCK_ROWS]) for c in items}
        keep = col["l_shipdate"] <= last
        rf = col["l_returnflag"][keep]
        f = np.where(rf == ord("A"), 0, np.where(rf == ord("N"), 1, 2))
        s = np.where(col["l_linestatus"][keep] == ord("F"), 0, 1)
        key = 2 * f + s
        qty, price, disc, tax = (col[c][keep].astype(np.int64) for c in (
            "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
        disc_price = price * (100 - disc)
        for i, v in enumerate((qty, price, disc_price,
                               disc_price * (100 + tax), disc)):
            for k in range(K):
                sums[i, k] += v[key == k].sum(dtype=np.int64)
        counts += np.bincount(key, minlength=K)
    with np.errstate(all="ignore"):
        avg = [np.where(counts > 0, sums[i] / counts / 100, 0.0)
               for i in (0, 1, 4)]
    return {"values": {"0": sums[0], "1": sums[1], "2": sums[2],
                       "3": sums[3], "4": avg[0], "5": avg[1], "6": avg[2]},
            "counts": counts}

