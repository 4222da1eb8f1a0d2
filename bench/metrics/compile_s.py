"""Seconds JAX spent tracing, lowering and compiling in the run (set-up
and window), from `jax.monitoring` (bench/clock.py). Moves `setup_s`."""


def read(run):
    c = run.counters.get("setup_compiles")
    w = run.counters.get("window_compiles")
    if c is None or w is None:
        return None
    return c["compile_s"] + w["compile_s"]
