"""The reduction from trace events to device numbers."""
import glob
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _events():
    # one device: ops at [10, 30), [20, 40) (overlap), [60, 70) and a
    # gather_emit kernel at [80, 100); window [0, 100) on the host
    return {"device": {"/device:TPU:0": [
                ["fusion.1", 10, 20], ["gather_emit_min", 20, 20],
                ["copy.2", 60, 10], ["gather_emit_min", 80, 20]]},
            "host": [["bench.window", 0, 100], ["bench.job", 0, 100],
                     ["$graph_device.py:308 build_device_graph", 40, 20],
                     ["$x.py:1 sleep", 0, 10]]}


def test_busy_union_idle_and_kernel_share():
    s = trace.summarize(_events())
    assert s["window_s"] == pytest.approx(100e-9)
    # union: [10, 40) + [60, 70) + [80, 100) = 60 ns
    assert s["busy_s"] == pytest.approx(60e-9)
    assert trace.idle_pct(s) == pytest.approx(40.0)
    # gather_emit events cover 40 ns; share of the 60 ns busy
    assert trace.busy_share(s, "gather_emit") == pytest.approx(100 * 40 / 60)
    assert trace.busy_share(s, "no_such_kernel") is None
    names = dict(s["device_ops"])
    assert names["gather_emit_min"] == pytest.approx(40e-9)
    # gaps: [40, 60) under build_device_graph, [0, 10) and [70, 80)
    assert s["idle_gaps"][0] == [
        "$graph_device.py:308 build_device_graph < bench.job",
        pytest.approx(20e-9)]
    assert {g[0] for g in s["idle_gaps"]} == {
        "$graph_device.py:308 build_device_graph < bench.job",
        "$x.py:1 sleep < bench.job", "bench.job"}


def test_brackets_are_not_busy_on_their_own():
    """A while loop's event brackets the operations inside it: only
    those count as busy, and the bracket keeps its own time apart."""
    ev = {"device": {"/device:TPU:0": [["while.1", 0, 100],
                                       ["fusion.2", 10, 20],
                                       ["gather_emit_min.3", 50, 30]]},
          "host": [["bench.window", 0, 100]]}
    s = trace.summarize(ev)
    assert s["busy_s"] == pytest.approx(50e-9)
    assert dict(s["device_ops"])["while.1"] == pytest.approx(50e-9)
    assert trace.busy_share(s, "gather_emit") == pytest.approx(60.0)


def test_merge_clips_to_the_window():
    assert trace.merge([(5, 15), (12, 30), (40, 50)], 10, 45) == \
        [[10, 30], [40, 45]]


def test_no_device_plane_reads_nothing():
    s = trace.summarize({"device": {}, "host": [["bench.window", 0, 10]]})
    assert s["busy_s"] == 0.0 and trace.idle_pct(s) is None
    assert trace.busy_share(s, "gather_emit") is None


def test_extract_reads_a_live_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(128)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.summarize_dir(str(tmp_path))
    assert s["window_s"] > 0 and s["devices"] == 0   # the CPU has no plane


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.events.json"))))
def test_recorded_chip_trace(path):
    """A window recorded on a TPU v5e, reduced to its event lists."""
    with open(path) as f:
        rec = json.load(f)
    s = trace.summarize(rec["events"])
    want = rec["summary"]
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < trace.idle_pct(s) < 100.0
    share = trace.busy_share(s, "gather_emit")
    assert share == pytest.approx(want["gather_emit_pct"], rel=1e-9)
    assert 0.0 < share <= 100.0
