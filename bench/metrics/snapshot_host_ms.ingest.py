"""Host ms of a ``snapshot()`` call (merge of the live window slots and
finalize, dispatched; the copy to the host is not in it), by the
program's own spans: the median duration of the traced stretch's
``mr.snapshot`` spans.  Nothing to read where the program records no
spans."""

import statistics


def read(view):
    try:
        from repro.core import trace
    except ImportError:
        return None
    snaps = [r.end_ns - r.start_ns for r in trace.records()
             if r.name == trace.SNAPSHOT]
    return statistics.median(snaps) / 1e6 if snaps else None
