"""Reduce a profiler trace of the measured window to device numbers.

`extract` reads the `.xplane.pb` the JAX profiler wrote into plain event
lists; everything after that works on those lists, so the reduction is
checked on a small recorded trace (bench/tests/data) without a chip.

* busy: the union of the intervals in which an operation ran on a
  device (the leaf events of the device plane's "XLA Ops" line: a
  `while` or `conditional` event only brackets the operations inside
  it), clipped to the window; averaged over the devices that ran
  anything;
* window: the harness's `bench.window` annotation on the host;
* device_ops: self seconds per HLO instruction name (`fusion.13`,
  `gather_emit_min.3`; a bracket's own time is its length minus what
  runs inside it), summed over the window;
* idle_gaps: the longest stretches with no device operation, each named
  by the innermost host event that covers its middle (the harness's own
  `bench.*` annotations, or the Python function the host was in).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


def op_name(event_name: str) -> str:
    """`%fusion.13 = f32[...] fusion(...)` -> `fusion.13`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...]} from one .xplane.pb."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events]
    return {"device": device, "host": host}


def merge(intervals, lo: float, hi: float):
    """Union of [start, end) intervals, clipped to [lo, hi), sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """[(name, start, end, self_ns, is_leaf)] of one line's nested events:
    a bracket's self time is its length minus its direct children's."""
    order = sorted(((s, -(s + d), name) for name, s, d in events))
    out, stack = [], []
    for s, neg_end, name in order:
        e = -neg_end
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s, True]
        if stack and e <= stack[-1][2]:
            parent = stack[-1]
            parent[3] -= e - s
            parent[4] = False
        out.append(rec)
        stack.append(rec)
    return out


def _host_label(host, t: float) -> str:
    """The two innermost host events around time t: `inner < outer`."""
    around = sorted((d, name) for name, s, d in host
                    if s <= t < s + d and name != WINDOW)
    return " < ".join(name for _, name in around[:2]) or "host"


def summarize(events: dict) -> dict:
    host = events["host"]
    win = [(s, s + d) for name, s, d in host if name == WINDOW]
    dev = {p: ev for p, ev in events["device"].items() if ev}
    if win:
        lo, hi = win[0]
    elif dev:
        lo = min(s for ev in dev.values() for _, s, _ in ev)
        hi = max(s + d for ev in dev.values() for _, s, d in ev)
    else:
        lo = hi = 0.0
    busy, ops, gaps = [], defaultdict(float), []
    for plane, ev in sorted(dev.items()):
        timed = self_times(ev)
        merged = merge(((s, e) for _, s, e, _, leaf in timed if leaf), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e, own, _ in timed:
            if s < hi and e > lo and e > s:
                ops[name] += own * (min(e, hi) - max(s, lo)) / (e - s) \
                    / len(dev)
        if not gaps:  # the first device names the gaps
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_label(host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:10]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": (sum(busy) / len(busy) if busy else 0.0) * 1e-9,
            "devices": len(dev),
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": idle}


def summarize_dir(tracedir: str) -> dict:
    paths = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {tracedir}, found "
                           f"{len(paths)}")
    return summarize(extract(paths[0]))


def busy_share(summary, prefix: str):
    """Percent of device busy time in operations whose name starts with
    `prefix`; None where the trace has no such operation."""
    if not summary or not summary["busy_s"]:
        return None
    s = sum(v for k, v in summary["device_ops"] if k.startswith(prefix))
    return 100.0 * s / summary["busy_s"] if s else None


def idle_pct(summary):
    """Percent of the window with no device operation running."""
    if not summary or not summary["window_s"] or not summary["devices"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
