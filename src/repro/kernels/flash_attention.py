"""Flash attention Pallas TPU kernel (pl.pallas_call + explicit BlockSpec
VMEM tiling).

TPU adaptation of the GPU flash algorithm (DESIGN.md §6): the k-loop is the
*grid's* trailing "arbitrary" dimension so the MXU sees (BQ, D) x (D, BK)
matmuls with BQ = BK = 512 (multiples of 128 — systolic-array aligned);
running max / denominator / accumulator live in VMEM scratch across k-steps.
GQA is handled in the index map (q head h reads kv head h * KV // H) — no
materialized KV repeat.  Causal and sliding-window masks are applied from
the global block offsets.

Validated in interpret mode against repro.kernels.ref.attention_ref (see
tests/test_kernels.py shape/dtype sweeps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BQ = 512
DEFAULT_BK = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, bq, bk, nk):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (cols <= rows)
    if window:
        mask = mask & (cols > rows - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """q: (B, H, S, D); k, v: (B, KV, T, D) -> (B, H, S, D).

    S % bq == 0 and T % bk == 0 (the ops.py wrapper pads).
    """
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    bq = min(bq, S)
    bk = min(bk, T)
    nq, nk = S // bq, T // bk
    scale = 1.0 / (D ** 0.5)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, KV=KV, H=H: (b, h * KV // H, ik, 0)),
            pl.BlockSpec((1, 1, bk, v.shape[-1]),
                         lambda b, h, iq, ik, KV=KV, H=H: (b, h * KV // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, v.shape[-1]),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, v.shape[-1]), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, v.shape[-1]), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),             # running max
            pltpu.VMEM((bq, 1), jnp.float32),             # running denom
        ],
        interpret=interpret,
    )(q, k, v)
