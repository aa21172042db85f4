"""Stochastic-quantization Pallas kernel — ECD-PSGD's compression operator
C(.) as a tiled TPU kernel (bf16/f32 -> int8 with per-tensor scale and
stochastic rounding; unbiased per the paper's Eq. 7 requirement).

The uniform noise is supplied by the wrapper (jax.random) so the kernel is
deterministic given its inputs; the scale (a global max) is a cheap XLA
reduce in the wrapper — the kernel does the bandwidth-bound elementwise pass
with explicit (BN x BD) VMEM tiles.

Oracle: repro.core.compression.quantize_stochastic (re-exported in ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256
DEFAULT_BD = 512


def _quant_kernel(x_ref, u_ref, scale_ref, q_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...]
    scale = scale_ref[0, 0]
    q = jnp.floor(x / scale + u)
    q = jnp.clip(q, -qmax - 1.0, qmax)
    q_ref[...] = q.astype(q_ref.dtype)


def _dequant_kernel(q_ref, scale_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("bits", "bn", "bd", "interpret"))
def quantize_stochastic_2d(x, key, *, bits=8, bn=DEFAULT_BN, bd=DEFAULT_BD,
                           interpret=False):
    """x: (n, d) -> (q int8/int16, scale)."""
    assert bits in (4, 8, 16)
    qmax = 2.0 ** (bits - 1) - 1.0
    n, d = x.shape
    bn = min(bn, n)
    bd = min(bd, d)
    pad_n, pad_d = (-n) % bn, (-d) % bd
    xp = jnp.pad(x, ((0, pad_n), (0, pad_d)))
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12) / qmax
    u = jax.random.uniform(key, xp.shape, jnp.float32)
    dt = jnp.int8 if bits <= 8 else jnp.int16
    grid = (xp.shape[0] // bn, xp.shape[1] // bd)
    q = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, dt),
        interpret=interpret,
    )(xp, u, scale.reshape(1, 1))
    return q[:n, :d], scale


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def dequantize_2d(q, scale, *, bn=DEFAULT_BN, bd=DEFAULT_BD, interpret=False):
    n, d = q.shape
    bn = min(bn, n)
    bd = min(bd, d)
    pad_n, pad_d = (-n) % bn, (-d) % bd
    qp = jnp.pad(q, ((0, pad_n), (0, pad_d)))
    grid = (qp.shape[0] // bn, qp.shape[1] // bd)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(qp, scale.reshape(1, 1))
    return x[:n, :d]
