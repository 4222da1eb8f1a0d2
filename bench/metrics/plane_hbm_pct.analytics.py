"""Share of the HBM roofline: the minimum bytes the jobs' message plane
must move (the reference's synchronous rounds, bench/reference/rounds.py)
over peak HBM bandwidth times the device busy seconds of the jobs. A
bandwidth roofline: no arithmetic bound applies to a min/add plane.
Moves `evps`."""


def read(run):
    t = run.trace_summary
    moved = run.counters.get("plane_bytes")
    if t is None or not moved or not t["busy_s"] or run.peaks is None:
        return None
    return 100.0 * moved / (run.peaks.hbm_bw * t["busy_s"])
