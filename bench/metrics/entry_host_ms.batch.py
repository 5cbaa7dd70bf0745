"""Host ms per job in the entry point, by the program's own spans: the
``mr.run`` / ``mr.run_distributed`` spans of the traced stretch, less the
time their ``mr.sync`` children block on the device, summed and divided
by the jobs.  Nothing to read where the program records no spans."""


def read(view):
    try:
        from repro.core import trace
    except ImportError:
        return None
    recs = trace.records()
    runs = [r for r in recs if r.name in (trace.RUN, trace.RUN_DISTRIBUTED)]
    if not runs:
        return None
    ids = {r.index for r in runs}
    host_ns = sum(r.end_ns - r.start_ns for r in runs) - sum(
        r.end_ns - r.start_ns for r in recs
        if r.name == trace.SYNC and r.parent in ids)
    return host_ns / 1e6 / view.info["jobs"]
