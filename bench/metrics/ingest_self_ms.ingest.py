"""Host ms an ``ingest()`` call spends outside the ingest executable's
call, by the program's own spans: the median over the traced stretch's
``mr.ingest`` spans of their duration less their ``mr.dispatch`` child.
Nothing to read where the program records no spans."""

import statistics


def read(view):
    try:
        from repro.core import trace
    except ImportError:
        return None
    recs = trace.records()
    exec_ns = {r.parent: r.end_ns - r.start_ns for r in recs
               if r.name == trace.DISPATCH}
    own = [r.end_ns - r.start_ns - exec_ns.get(r.index, 0) for r in recs
           if r.name == trace.INGEST]
    return statistics.median(own) / 1e6 if own else None
