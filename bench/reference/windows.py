"""Plain reference of a count-based sliding window over micro-batches.

A window of ``size`` micro-batches advancing every ``slide`` splits the
stream into periods of ``slide`` batches; a snapshot taken after batch
``n`` (batches numbered from 0, ``n + 1`` ingested) covers the periods
from ``size // slide - 1`` periods before the current one up to the
current, which may be partly filled.
"""

from __future__ import annotations


def covered(n_ingested: int, size: int, slide: int) -> range:
    """0-based ids of the micro-batches a snapshot after ``n_ingested``
    batches covers."""
    if n_ingested <= 0:
        return range(0)
    period = (n_ingested - 1) // slide
    first = max(0, period - (size // slide - 1))
    return range(first * slide, n_ingested)
