"""Share of the HBM roofline a solve reaches: the least bytes any correct
solve moves (``bench.lib.work``), over the device's busy time per query
times the chips' peak bandwidth (``bench.lib.peaks``)."""

from bench.lib.peaks import peaks
from bench.lib.work import solve_min_bytes


def read(run):
    t = run.trace
    if t is None or run.answers == 0 or t.busy_mean_s <= 0:
        return None
    per_query_s = t.busy_mean_s / run.answers
    bandwidth = peaks(run.device_kind)["hbm_bytes_per_s"] * run.chips
    return 100.0 * solve_min_bytes(run.graph_n, run.graph_directed_edges) / (per_query_s * bandwidth)
