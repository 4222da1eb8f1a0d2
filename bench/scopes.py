"""The program's layer names in a profiler trace: `bench/trace.py`'s
reduction plus what the program names itself (repro/obs.py).

`extract` reads the `.xplane.pb` as `trace.extract` does and keeps each
device event's scope as a fourth element: the innermost `unigps.*`
component of the operation's `op_name`, `""` where there is none. A
device event names only its HLO instruction; the profile's metadata
plane holds the compiled HLO of every module that ran (its "Hlo Proto"
stats), whose instructions carry their `op_name`, and the device's "XLA
Modules" line says which module each operation ran in. `summarize`
returns `trace.summarize`'s keys, computed by it on the same events, and
adds:

* scopes: self seconds per scope, clipped to the window and averaged
  over the devices exactly as `device_ops` is, so the scopes add up to
  the operations' seconds; `""` holds the unscoped. (A kernel event can
  hold a zero-length `custom-call` event: `trace.summarize` then counts
  it as a bracket, out of `busy_s`, while its self time stays here.)
* host_spans: per program host span (`unigps.*` annotation): seconds
  inside the window, count, and the idle seconds of the first device
  inside it (the intervals `idle_gaps` names).

Events of three elements reduce too: all of their time is unscoped.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace

#: prefix of the program's scope and span names (repro/obs.py)
PROGRAM_PREFIX = "unigps."
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_PROTO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"(?<![\w.])unigps\.[A-Za-z_]+(?:\.[A-Za-z_]+)*")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(op_path: str) -> str:
    """The innermost `unigps.*` component of an op_name, or ""."""
    found = _SCOPE.findall(op_path or "")
    return found[-1] if found else ""


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, v


def _hlo_protos(xspace: bytes) -> dict:
    """{module name as the device's "XLA Modules" line names it: the
    serialized HloModuleProto} from the metadata plane of an XSpace
    (tsl/profiler/protobuf/xplane.proto; xla HloProto.hlo_module = 1)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:  # XSpace.planes
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v).decode() == METADATA_PLANE
                   for k, v in fields):
            continue
        stat_names, metas = {}, []
        for k, v in fields:
            if k in (4, 5):  # event_metadata, stat_metadata map entries
                value = dict(_fields(v)).get(2)
                if value is None:
                    continue
                m = dict(_fields(value))
                if k == 5:
                    stat_names[m.get(1, 0)] = bytes(m.get(2, b"")).decode()
                else:
                    metas.append(value)
        for value in metas:
            name, protos = "", []
            for k, v in _fields(value):
                if k == 2:
                    name = bytes(v).decode()
                elif k == 5:  # XStat: metadata_id = 1, bytes_value = 6
                    st = dict(_fields(v))
                    if 6 in st:
                        protos.append((st.get(1, 0), st[6]))
            for sid, blob in protos:
                if stat_names.get(sid) == HLO_PROTO_STAT:
                    module = dict(_fields(blob)).get(1)
                    if module is not None:
                        out[name] = bytes(module)
    return out


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction name: scope} of one HLO module's text; an instruction
    without an op_name (a fusion, say) takes that of the root of the
    computation it calls."""
    op, calls, roots, comp = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        found = _OP_NAME.search(line)
        op[name] = scope_of(found.group(1)) if found else ""
        target = _CALLS.search(line)
        if target is not None:
            calls[name] = target.group(1)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = name
    for name, target in calls.items():
        if not op[name] and target in roots:
            op[name] = op.get(roots[target], "")
    return op


def _of_module(scopes: dict, name: str) -> dict:
    """The module's scopes by its `name(program id)`, else by its name
    where one module alone bears it."""
    if name in scopes:
        return scopes[name]
    same = [v for k, v in scopes.items()
            if k.split("(")[0] == name.split("(")[0]]
    return same[0] if len(same) == 1 else {}


def _module_scopes(xspace: bytes) -> dict:
    from jax._src.lib import xla_client
    out = {}
    for name, proto in _hlo_protos(xspace).items():
        module = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            proto)
        out[name] = hlo_scopes(module.to_string())
    return out


def extract(path: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns, scope], ...]},
        "host": [[name, start_ns, dur_ns], ...]} from one .xplane.pb."""
    import jax
    with open(path, "rb") as f:
        raw = f.read()
    scopes = _module_scopes(raw)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              _of_module(scopes, e.name))
                             for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            ops = []
            for e in lines.get(trace.OPS_LINE, []):
                name = trace.op_name(e.name)
                k = bisect.bisect_right(starts, e.start_ns) - 1
                in_module = modules[k][2] if k >= 0 and \
                    e.start_ns < modules[k][1] else {}
                ops.append([name, e.start_ns, e.duration_ns,
                            in_module.get(name, "")])
            if trace.OPS_LINE in lines:
                device[plane.name] = ops
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events]
    return {"device": device, "host": host}


def summarize(events: dict) -> dict:
    dev = {p: ev for p, ev in events["device"].items() if ev}
    plain = {"device": {p: [e[:3] for e in ev] for p, ev in dev.items()},
             "host": events["host"]}
    out = trace.summarize(plain)
    win = [(s, s + d) for name, s, d in events["host"]
           if name == trace.WINDOW]
    if win:
        lo, hi = win[0]
    elif dev:
        lo = min(e[1] for ev in dev.values() for e in ev)
        hi = max(e[1] + e[2] for ev in dev.values() for e in ev)
    else:
        lo = hi = 0.0
    scopes, gaps = defaultdict(float), []
    for plane, ev in sorted(dev.items()):
        scope = {(e[0], e[1], e[1] + e[2]): e[3] if len(e) > 3 else ""
                 for e in ev}
        timed = trace.self_times(plain["device"][plane])
        for name, s, e, own, _ in timed:
            if s < hi and e > lo and e > s:
                scopes[scope[(name, s, e)]] += \
                    own * (min(e, hi) - max(s, lo)) / (e - s) / len(dev)
        if not gaps:  # the first device names them, as in trace.py
            merged = trace.merge(((s, e) for _, s, e, _, leaf in timed
                                  if leaf), lo, hi)
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    out["scopes"] = {k: v * 1e-9 for k, v in sorted(scopes.items())}
    out["host_spans"] = _host_spans(events["host"], gaps, lo, hi)
    return out


def _host_spans(host, gaps, lo: float, hi: float) -> dict:
    """{name: {"seconds", "count", "idle_seconds"}} of the program's host
    spans inside [lo, hi); `gaps` sorted by start and disjoint."""
    starts = [s for s, _ in gaps]
    out = {}
    for name, s, d in host:
        a, b = max(s, lo), min(s + d, hi)
        if not name.startswith(PROGRAM_PREFIX) or b <= a:
            continue
        idle = 0.0
        for gs, ge in gaps[max(bisect.bisect_right(starts, a) - 1, 0):]:
            if gs >= b:
                break
            idle += max(0.0, min(ge, b) - max(gs, a))
        rec = out.setdefault(name, {"seconds": 0.0, "count": 0,
                                    "idle_seconds": 0.0})
        rec["seconds"] += (b - a) * 1e-9
        rec["count"] += 1
        rec["idle_seconds"] += idle * 1e-9
    return dict(sorted(out.items()))


def scope_share(summary, scope: str):
    """Percent of device busy time in operations under `scope`; None
    where the trace has no such operation."""
    if not summary or not summary["busy_s"]:
        return None
    s = summary.get("scopes", {}).get(scope, 0.0)
    return 100.0 * s / summary["busy_s"] if s else None


def scoped_share(summary):
    """Percent of device busy time under any program scope."""
    if not summary or not summary["busy_s"]:
        return None
    s = sum(v for k, v in summary.get("scopes", {}).items() if k)
    return 100.0 * s / summary["busy_s"]
