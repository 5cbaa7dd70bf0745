"""Device ms per job in sorts: HLO sort ops, or fusions holding one, and
the Pallas radix-partition and segment-reduce kernels; on the chip with
the most."""


def read(view):
    return view.summary.class_ms_per_job("sort", view.info["jobs"])
