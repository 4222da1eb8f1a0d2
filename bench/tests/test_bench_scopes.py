"""The program's layer names in a trace (bench/scopes.py) and the readers
of the program's own spans and counters."""
import glob
import importlib.util
import json
import os
import sys
import types

import pytest

from bench import scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
GATHER, KERNEL = "unigps.plane.gather", "unigps.plane.kernel"


def _events():
    # one device, window [0, 100): a loop bracket [0, 100) around a gather
    # [10, 30), a kernel [30, 40) holding a zero-length marker, an
    # unscoped copy [60, 70) and a kernel [80, 100); host spans around
    # the gaps
    return {"device": {"/device:TPU:0": [
                ["while.1", 0, 100, "unigps.vertex"],
                ["fusion.1", 10, 20, GATHER],
                ["gather_emit_min", 30, 10, KERNEL],
                ["custom-call.2", 30, 0.001, KERNEL],
                ["copy.2", 60, 10, ""],
                ["gather_emit_min", 80, 20, KERNEL]]},
            "host": [["bench.window", 0, 100], ["unigps.job", 0, 100],
                     ["unigps.prepare", 35, 30],
                     ["unigps.run", 65, 35], ["$x.py:1 f", 40, 5]]}


def test_scopes_add_up_to_the_operations_self_time():
    s = scopes.summarize(_events())
    # the loop's own time is what runs nothing inside it: 100 - 60 ns; the
    # marked kernel keeps its own time although trace.py's busy union
    # leaves it out as a bracket
    assert s["scopes"] == pytest.approx(
        {"unigps.vertex": 40e-9, GATHER: 20e-9, KERNEL: 30e-9, "": 10e-9})
    assert sum(s["scopes"].values()) == pytest.approx(
        sum(v for _, v in s["device_ops"]))
    assert s["busy_s"] == pytest.approx(50.001e-9)
    assert scopes.scope_share(s, KERNEL) == pytest.approx(
        100 * 30 / 50.001)
    assert scopes.scope_share(s, "unigps.plane.combine") is None
    assert scopes.scoped_share(s) == pytest.approx(100 * 90 / 50.001)


def test_the_reduction_of_trace_py_is_unchanged():
    ev = _events()
    plain = {"device": {p: [e[:3] for e in v]
                        for p, v in ev["device"].items()},
             "host": ev["host"]}
    s, t = scopes.summarize(ev), trace.summarize(plain)
    assert {k: s[k] for k in t} == t
    # three-element events reduce, with all their time unscoped
    u = scopes.summarize(plain)
    assert {k: u[k] for k in t} == t
    assert set(u["scopes"]) == {""}


def test_a_trace_without_scopes_keeps_its_recorded_summary():
    with open(os.path.join(DATA, "sssp16.events.json")) as f:
        rec = json.load(f)
    s = scopes.summarize(rec["events"])
    assert s["busy_s"] == pytest.approx(rec["summary"]["busy_s"], rel=1e-9)
    assert trace.busy_share(s, "gather_emit") == pytest.approx(
        rec["summary"]["gather_emit_pct"], rel=1e-9)
    assert set(s["scopes"]) == {""} and s["host_spans"] == {}


def test_host_span_idle_agrees_with_idle_gaps():
    s = scopes.summarize(_events())
    # idle: [0, 10), [30.001, 60) (the marked kernel, then nothing) and
    # [70, 80)
    assert sorted(round(sec * 1e9) for _, sec in s["idle_gaps"]) == \
        [10, 10, 30]
    spans = s["host_spans"]
    assert spans["unigps.job"] == pytest.approx(
        {"seconds": 100e-9, "count": 1,
         "idle_seconds": sum(sec for _, sec in s["idle_gaps"])})
    assert spans["unigps.prepare"]["idle_seconds"] == pytest.approx(25e-9)
    assert spans["unigps.run"]["idle_seconds"] == pytest.approx(10e-9)
    assert "bench.window" not in spans and "$x.py:1 f" not in spans


def test_scope_of_an_op_name():
    assert scopes.scope_of("jit(run)/unigps.vertex/while/body/"
                           "unigps.plane.gather/jit(_take)/gather") == GATHER
    assert scopes.scope_of("jit(run)/while/body/add") == ""
    assert scopes.scope_of("") == ""


def _scoped(rec):
    return {"device": {p: [e + [sc] for e, sc in zip(ev, rec["scopes"][p])]
                       for p, ev in rec["events"]["device"].items()},
            "host": rec["events"]["host"]}


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.scoped.events.json"))))
def test_recorded_scoped_chip_trace(path):
    """A job recorded on a TPU v5e with the program's layer names."""
    with open(path) as f:
        rec = json.load(f)
    want = rec["summary"]
    s = scopes.summarize(_scoped(rec))
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["scopes"] == pytest.approx(want["scopes"], rel=1e-9)
    for name, span in want["host_spans"].items():
        assert s["host_spans"][name] == pytest.approx(span, rel=1e-9)
    # every operation falls under a scope; the kernels' own scope is the
    # gather_emit share, give or take the empty-record fill around them
    assert scopes.scoped_share(s) >= 95.0
    assert scopes.scope_share(s, KERNEL) == pytest.approx(
        want["gather_emit_pct"], abs=1.0)
    assert set(s["host_spans"]) >= {"unigps.job", "unigps.prepare",
                                    "unigps.run"}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_program_readers_read_the_process_totals():
    from repro import obs
    run = types.SimpleNamespace(trace_summary=None, counters={"jobs": 1})
    obs.reset()
    try:
        assert _reader("useful_edges_pct.analytics").read(run) is None
        assert _reader("prep_ms.analytics").read(run) is None
        obs.add(obs.ACTIVE_EDGES, 25)
        obs.add(obs.EDGE_SLOTS, 200)
        for _ in range(2):
            with obs.span(obs.PREPARE):
                pass
        assert _reader("useful_edges_pct.analytics").read(run) == 12.5
        count, seconds = obs.span_totals()[obs.PREPARE]
        assert count == 2
        assert _reader("prep_ms.analytics").read(run) == pytest.approx(
            1000.0 * seconds / 2)
    finally:
        obs.reset()


@pytest.mark.parametrize("name", ["useful_edges_pct.analytics",
                                  "prep_ms.analytics"])
def test_program_readers_read_nothing_from_a_program_without_them(
        name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    run = types.SimpleNamespace(trace_summary=None, counters={"jobs": 1})
    assert _reader(name).read(run) is None


def test_hlo_scopes_follow_a_fusion_to_its_root():
    hlo = "\n".join([
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        "  ROOT %gather.2 = f32[8]{0} gather(f32[8]{0} %param_0), "
        'metadata={op_name="jit(run)/unigps.vertex/while/body/'
        'unigps.plane.gather/jit(_take)/gather" stack_frame_id=3}',
        "}",
        "",
        "ENTRY %main.5 (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, "
        "calls=%fused_computation.1",
        "}"])
    assert scopes.hlo_scopes(hlo) == {
        "param_0": "", "gather.2": GATHER, "x": "", "fusion.3": GATHER}


def test_module_scopes_of_a_live_profile(tmp_path):
    """The profile's metadata plane holds each module's compiled HLO."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("unigps.vertex"):
            y = jnp.cos(x) * 3
        with jax.named_scope(GATHER):
            return jnp.take(y, (jnp.arange(64) * 7) % 64) + 1

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(f)(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as fh:
        modules = scopes._module_scopes(fh.read())
    found, = [v for k, v in modules.items() if k.startswith("jit_f(")]
    assert {"unigps.vertex", GATHER} <= set(found.values())
