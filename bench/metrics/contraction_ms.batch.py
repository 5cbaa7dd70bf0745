"""Device ms per job in contractions: ops that are, or fuse, a dot or a
convolution, ops XLA rewrote from one (the one-hot fold, which XLA lowers
to a compare and a reduce), and Pallas fold kernels; on the chip with the
most."""


def read(view):
    return view.summary.class_ms_per_job("contraction", view.info["jobs"])
