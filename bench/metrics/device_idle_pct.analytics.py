"""Share of the traced window in which no operation ran on the device,
during the jobs. Moves `evps`."""
from bench import trace


def read(run):
    return trace.idle_pct(run.trace_summary)
