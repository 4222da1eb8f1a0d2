"""Each cell end to end at a tiny scale on the CPU, untraced and traced."""
import pytest

CELLS = [("graph500-21.sssp", 9)]


@pytest.mark.parametrize("workload,scale", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(rehearse, workload, scale, trace):
    r = rehearse(workload, scale, seconds=1.0, trace=trace)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"
    if trace:
        # CPU traces have no device plane: device readers stay silent
        assert {"host_build_s", "compile_s"} <= set(r["metrics"])
        assert r["device"]["busy_s"] == 0.0
    else:
        assert {"setup_s", "evps"} == set(r["metrics"])
