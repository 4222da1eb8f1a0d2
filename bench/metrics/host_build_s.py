"""Seconds of the program's host build (`core/graph.from_edges`), on the
harness's clock around the call; serving cells add the host side of
`ServingSession.__init__`. Moves `setup_s`."""


def read(run):
    s = run.spans.get("host_build_s")
    if s is None:
        return None
    return s + run.spans.get("session_build_s", 0.0)
