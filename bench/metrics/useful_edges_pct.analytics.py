"""Share of the edge slots the plane streamed whose source was on the
frontier: 100 x `plane.active_edges` / `plane.edge_slots`, the program's
exact counters (repro/obs.py), the ratio of useful work to attempts that
bounds a frontier-sparse plane. The counters are process-wide, so they
hold the set-up's warm-up job as well as the window's; every job of a
cell runs the same graph from the same root, so the ratio is that of
one job. Moves `evps`."""


def read(run):
    try:
        from repro import obs
    except ImportError:  # a program without the counters
        return None
    c = obs.counters()
    if not c.get(obs.EDGE_SLOTS):
        return None
    return 100.0 * c.get(obs.ACTIVE_EDGES, 0) / c[obs.EDGE_SLOTS]
