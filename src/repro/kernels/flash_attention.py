"""Causal GQA flash attention (online softmax) Pallas kernel.

Used by the LM substrate for train/prefill attention (decode is a
memory-bound gather; it uses the plain jnp path). Supports:

  * grouped-query attention via the k/v BlockSpec index map
    (q head h reads kv head h // (Hq // Hkv) — no materialized expansion)
  * causal masking
  * sliding-window (local) attention (`window`), for starcoder2 /
    recurrentgemma local layers
  * un-padded key lengths (`kv_len`) masked against padded blocks

Grid (B, Hq, nq, nk); nk innermost/sequential carries the online-softmax
state (m, l, acc) in VMEM scratch. Blocks that lie entirely above the
causal diagonal or outside the window are skipped via `@pl.when` — the
flash-attention block-sparsity pattern, expressed as TPU predication.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sm_scale: float, causal: bool, window: int | None,
            block_q: int, block_k: int, n_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = iq * block_q
    k_lo = ik * block_k

    # block-level skip: above causal diagonal / outside the window / padding
    live = k_lo < kv_len
    if causal:
        live &= k_lo <= q_lo + block_q - 1
    if window is not None:
        live &= k_lo + block_k - 1 >= q_lo - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [BQ, Dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [BK, Dh]
        v = v_ref[0, 0].astype(jnp.float32)  # [BK, Dh]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= sm_scale  # [BQ, BK]

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0]  # [BQ]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # rescale of the old state
        p = jnp.exp(s - m_new[:, None])  # [BQ, BK]
        # fully-masked rows (no valid keys yet): keep state at identity
        row_dead = m_new <= _NEG_INF * 0.5
        alpha = jnp.where(row_dead, 1.0, alpha)
        p = jnp.where(row_dead[:, None], 0.0, p)

        l_ref[...] = (l_ref[...] * alpha[:, None] +
                      jnp.sum(p, axis=1)[:, None])
        acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(ik == n_k - 1)
    def _flush():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # rows with no valid keys -> 0
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_kernel(q, k, v, causal: bool = True,
                           window: int | None = None,
                           sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q [B, Hq, T, Dh], k/v [B, Hkv, S, Dh] -> [B, Hq, T, Dh]."""
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = Dh ** -0.5

    bq = min(block_q, _pow2_ge(T))
    bk = min(block_k, _pow2_ge(S))
    T_pad = pl.cdiv(T, bq) * bq
    S_pad = pl.cdiv(S, bk) * bk
    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    grid = (B, Hq, T_pad // bq, S_pad // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=float(sm_scale), causal=causal,
                          window=window, block_q=bq, block_k=bk,
                          n_k=grid[3], kv_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T_pad, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # m
            pltpu.VMEM((bq, _LANES), jnp.float32),  # l
            pltpu.VMEM((bq, Dh), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_gqa",
    )(q_p, k_p, v_p)
    return out[:, :, :T, :]


def _pow2_ge(x: int) -> int:
    p = 8
    while p < x:
        p *= 2
    return p
