"""Host ms from an ``ingest()`` call to its return, by the benchmark's
own span: the median over the traced stretch's calls."""

import statistics


def read(view):
    calls = view.info.get("dispatch_s") or []
    return 1e3 * statistics.median(calls) if calls else None
