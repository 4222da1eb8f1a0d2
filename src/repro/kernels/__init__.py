"""TPU Pallas kernels for the framework's compute hot-spots.

  fused_gather_emit  the message plane (gather src props -> emit ->
                     combine at dst) as ONE streamed pass — no E-sized
                     message store in HBM
  segment_reduce     Phase-1 message combine (the paper's scatter hot
                     loop): the fused kernel with an identity emit
  flash_attention    causal GQA flash attention for the LM substrate

Each kernel ships with a pure-jnp oracle in ref.py; ops.py holds the jit'd
wrappers (Mosaic on a TPU, interpret mode elsewhere).
"""
from . import ops, ref  # noqa: F401
