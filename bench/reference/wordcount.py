"""Plain reference of Phoenix word_count: how often each word id occurs.

NumPy only; imports nothing of the engine.  Every value is a sum of ones,
so a word's value equals its count.
"""

from __future__ import annotations

import numpy as np


def counts(items, cfg) -> np.ndarray:
    """int64 ``[vocab]`` occurrences of each word id in host ``items``."""
    return np.bincount(np.asarray(items).reshape(-1),
                       minlength=cfg["vocab"]).astype(np.int64)
