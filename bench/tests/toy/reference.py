"""Plain reference of the toy table job (``app.py`` beside it).

NumPy only.  The program returns its reduce's tuple by position, so the
columns are ``"0"`` (the int sum) and ``"1"`` (the mean).  ``floor(100 x)``
is taken in float32, as the job states it; the sums and the mean in
float64.
"""

from __future__ import annotations

import numpy as np


def table(items, cfg) -> dict:
    key = np.asarray(items["key"])
    x = np.asarray(items["x"], np.float32)
    counts = np.bincount(key, minlength=cfg["keys"]).astype(np.int64)
    cents = np.floor(x * np.float32(100)).astype(np.int64)
    sums = np.zeros(cfg["keys"], np.int64)
    np.add.at(sums, key, cents)
    mean = (np.bincount(key, weights=x.astype(np.float64),
                        minlength=cfg["keys"]) / counts)
    return {"values": {"0": sums, "1": mean}, "counts": counts}
