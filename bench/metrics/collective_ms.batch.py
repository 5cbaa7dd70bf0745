"""Device ms per job in collectives (the shuffle's all-to-all and any
all-reduce or all-gather), on the chip with the most."""


def read(view):
    return view.summary.class_ms_per_job("collective", view.info["jobs"])
