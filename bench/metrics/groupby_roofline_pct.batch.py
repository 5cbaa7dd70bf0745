"""The whole job's share of the HBM roofline: the least time the chips
could take to move the group-by's own bytes (read the items once, write
the result table once) at their published HBM bandwidth, over the traced
job's time from the call until the result is on the host.  The bytes do
not depend on the flow or the kernels that do the work."""


def read(view):
    if view.peaks is None:
        return None  # a device with no published peaks: nothing to read
    chips = view.summary.n_devices
    least_s = view.info["bytes"] / (chips * view.peaks["hbm_bytes_per_s"])
    job_s = sum(view.info["job_s"]) / len(view.info["job_s"])
    return 100.0 * least_s / job_s
