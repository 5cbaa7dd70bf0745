"""Plain reference of Phoenix histogram: pixels per (channel, intensity).

NumPy only; imports nothing of the engine.  Bin ``c * levels + v`` counts
the pixels whose channel ``c`` reads ``v``; every value is a sum of ones,
so a bin's value equals its count.  Channels are counted one at a time in
blocks of rows, so the full-size bitmap needs no wide temporaries.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1 << 24


def counts(items, cfg) -> np.ndarray:
    levels = cfg["levels"]
    items = np.asarray(items)
    out = np.zeros((items.shape[1], levels), np.int64)
    for lo in range(0, items.shape[0], BLOCK_ROWS):
        block = items[lo:lo + BLOCK_ROWS]
        for c in range(items.shape[1]):
            out[c] += np.bincount(block[:, c], minlength=levels)
    return out.reshape(-1)
