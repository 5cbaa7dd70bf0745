"""Bytes of holder state the job folds per emitted pair, by the program's
own counter: ``plan_cache.STATS.holder_bytes``, per plan, every holder
column's itemsize x width plus the int32 count (8 for a one-column int32
sum).  A wide job pays 8 bytes only for a declared 64-bit column.  The
largest over the plans the run built; nothing to read where the program
keeps no such counter."""


def read(view):
    try:
        from repro.core import plan_cache
    except ImportError:
        return None
    per_plan = getattr(plan_cache.STATS, "holder_bytes", None)
    if not per_plan:
        return None
    return max(per_plan.values())
