"""Host-environment helpers shared by tests and benchmarks."""
from __future__ import annotations

import os

#: env vars that pick the JAX backend; fresh-interpreter subprocesses MUST
#: inherit them — without JAX_PLATFORMS=cpu a libtpu-carrying image probes
#: the (absent) TPU for ~7 minutes before falling back to CPU.
BACKEND_ENV_VARS = ("JAX_PLATFORMS", "JAX_PLATFORM_NAME",
                    "TPU_SKIP_MDS_QUERY")


def subprocess_env(**extra: str) -> dict:
    """Minimal env for subprocess tests/benches that need a fresh
    interpreter (XLA device count locks at backend init)."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"}
    for k in BACKEND_ENV_VARS:
        if k in os.environ:
            env[k] = os.environ[k]
    env.update(extra)
    return env


#: repository checkout root (src/repro/envutil.py -> ../..)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs). Entry points call this — chip_smoke.py,
    benchmarks/run.py, launch/serve.py — never `import repro`: topology
    compiles in the tests would write entries that cannot be read back
    without a chip. Returns the directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
