"""Fused RMSNorm Pallas kernel: one VMEM pass computes the row second moment
and applies the normalization + gain (vs. the unfused mean-square / rsqrt /
mul chain).  Rows are (tokens), tiled (BR x d) with d kept whole so the
reduction is a single in-tile pass.

Oracle: repro.models.layers.apply_rmsnorm (re-exported in ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BR = 256


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)               # (br, d)
    var = jnp.mean(x * x, axis=1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "br", "interpret"))
def rmsnorm_2d(x, gain, *, eps=1e-6, br=DEFAULT_BR, interpret=False):
    """x: (n, d), gain: (d,) -> (n, d)."""
    n, d = x.shape
    br = min(br, n)
    pad = (-n) % br
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (xp.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
    )(xp, gain.reshape(1, d))
    return out[:n]
