"""Host ms of the ingest executable's call inside ``ingest()`` (its
dispatch; the fold itself runs on after it returns), by the program's
own spans: the median duration of the ``mr.dispatch`` spans whose parent
is an ``mr.ingest`` span.  Nothing to read where the program records no
spans."""

import statistics


def read(view):
    try:
        from repro.core import trace
    except ImportError:
        return None
    recs = trace.records()
    ingests = {r.index for r in recs if r.name == trace.INGEST}
    calls = [r.end_ns - r.start_ns for r in recs
             if r.name == trace.DISPATCH and r.parent in ingests]
    return statistics.median(calls) / 1e6 if calls else None
