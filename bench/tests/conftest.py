"""CPU rehearsals of the benchmark: tiny scales, no chip, no compile
cache. `require_tpu=False` and `scale=` are arguments of
`bench.harness.run_cell` that only these tests pass."""
import pytest


@pytest.fixture
def rehearse():
    from bench import harness

    def run(workload, scale, seconds=1.0, trace=False, seed=2**33 + 7):
        return harness.run_cell(workload, seed, seconds, trace,
                                require_tpu=False, scale=scale,
                                compile_cache=False)
    return run


@pytest.fixture
def fresh_runners():
    """Drop compiled runners before and after a test that plants a fault
    under them, so neither side sees the other's programs."""
    from repro.core.engines import common
    common._jitted_runner.cache_clear()
    yield
    common._jitted_runner.cache_clear()
