"""Each cell's control (the reference one step below the configuration's
precision) fails at least one of the cell's limits, at a small size."""
import pytest

from bench import harness


@pytest.mark.parametrize("workload,scale", [("graph500-21.sssp", 11)])
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_control_fails_a_limit(workload, scale, seed):
    run = harness.prepare(workload, seed, 2.0, require_tpu=False,
                          scale=scale)
    harness.build_graph(run, program=False)
    checks = harness.driver_of(run).control(run)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
