"""Share of device busy time spent in the `gather_emit*` Pallas kernels
(kernels/fused_gather_emit.py) during the jobs. Moves `evps`."""
from bench import trace


def read(run):
    return trace.busy_share(run.trace_summary, "gather_emit")
