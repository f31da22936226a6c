"""Share of the HBM roofline reached by the island mesh's query-group scan
programs in the traced window: the sum over their runs of the least time
their input columns take to read at the chip's peak bandwidth, over the
sum of their device times.

A mesh program runs on every island's device, and the trace lists it once
per device with that device's time, so each run is charged one island's
rows: the table's rows over the configuration's island count. A row costs
what ``chipbench/roofline.py`` gives the one-chip scan of the same query
group."""

from chipbench.roofline import SCAN_PROGRAMS, peaks

# mesh program -> bytes read per row: (int32 code columns, bool masks)
MESH_SCANS = {
    "scan_exact_mesh": SCAN_PROGRAMS["scan_filter_agg_exact_kernel"],
    "scan_exact_join_mesh": SCAN_PROGRAMS["join_scan_pallas"],
}


def read(run):
    if run.trace is None:
        return None
    island_rows = run.n_rows // int(run.cell.config["system"]["n_shards"])
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    least = spent = 0.0
    for program, seconds in run.trace["programs"]:
        if program in MESH_SCANS:
            codes, masks = MESH_SCANS[program]
            least += island_rows * (4 * codes + masks) / bw
            spent += seconds
    if not spent:
        return None
    return 100.0 * least / spent
