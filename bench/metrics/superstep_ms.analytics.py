"""Device busy milliseconds per superstep of the window's jobs: the busy
union of the traced window over the supersteps the jobs reported
(`info["iterations"]`). Moves `evps`."""


def read(run):
    t = run.trace_summary
    steps = run.counters.get("supersteps")
    if t is None or not steps or not t["busy_s"]:
        return None
    return t["busy_s"] * 1000.0 / steps
