"""Algorithms and kernels: the share of its HBM roofline that a PageRank
superstep reaches (percent).

The least time of a superstep is its bytes over the chip's HBM bandwidth
(``refs/peaks.py``).  Its bytes come from the graph, not from the code:
``4 * E`` for one int32 source index per edge, and ``12 * V`` for reading the
rank and the out-degree and writing the new rank, 4 bytes each.  PageRank
does about 2 FLOP per edge, so bandwidth, not the MXU, bounds it.  The time
is the device's busy time in the traced window over the supersteps completed
in it.  Nothing to read (no trace, no busy time) gives no value.
"""

from refs.peaks import peaks


def read(obs: dict):
    t, g = obs.get("trace"), obs.get("graph")
    steps = obs.get("supersteps")
    if not t or not g or not steps or t["busy_s"] <= 0:
        return None
    least_s = (4 * g["edges"] + 12 * g["vertices"]) / peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / steps)
