# repro — UniGPS-in-JAX: unified vertex-centric graph processing (the paper's
# contribution, under repro.core) + the LM training/serving substrate that
# shares its mesh/launch/roofline tooling.
import jax as _jax

# The callback engine executes eager jax ops from inside `pure_callback`
# (the paper's IPC-isolation analogue). With the CPU client's async
# dispatch, those nested dispatches deadlock on small hosts once an op
# crosses the parallelization threshold (the dispatch thread is occupied
# by the enclosing executable) — batched [V, Q] lanes cross it at Q>=3
# on a 1-core box, and plain [V] ops cross it on larger graphs. The knob
# is client-creation-time only, so it must be set at import, before any
# jax op initializes the backend (same contract as launch/dryrun.py's
# XLA_FLAGS lines). Everything hot runs under jit, where the loss of
# eager dispatch/compute overlap is unobservable.
_jax.config.update("jax_cpu_enable_async_dispatch", False)

from .core.api import UniGPS  # noqa: F401
from .core.graph import PropertyGraph, from_edges, partition_graph  # noqa: F401
from .core.vcprog import BatchedProgram, VCProgram  # noqa: F401
from .core.engines import run_vcprog  # noqa: F401
from .core.operators import landmark_distances  # noqa: F401
from .core import io, operators  # noqa: F401

__version__ = "0.1.0"
