"""JAX traces per ``snapshot()`` call: the mean over the traced
stretch's ``mr.snapshot`` spans of the jaxpr traces the program counted
inside each, its children's included.  Nothing to read where the
program records no spans."""


def read(view):
    try:
        from repro.core import trace
    except ImportError:
        return None
    snaps = [r.traces for r in trace.records() if r.name == trace.SNAPSHOT]
    return sum(snaps) / len(snaps) if snaps else None
