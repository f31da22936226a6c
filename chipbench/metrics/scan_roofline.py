"""Share of the HBM roofline reached by the query-group scan programs in
the traced window: the sum over their runs of the least time their input
columns take to read at the chip's peak bandwidth, over the sum of their
device times."""

from chipbench.roofline import peaks, scan_bytes


def read(run):
    if run.trace is None:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    least = spent = 0.0
    for program, seconds in run.trace["programs"]:
        b = scan_bytes(program, run.n_rows)
        if b is not None:
            least += b / bw
            spent += seconds
    if not spent:
        return None
    return 100.0 * least / spent
