"""C_sim (paper Eq. 3) Pallas kernel — the paper-specific compute hot spot.

The O(n * range * d) windowed-L0 sweep is tiled as: for each shift j the
wrapper rolls X by j (cheap row permutation), and the kernel counts
differing coordinates block-by-block with explicit VMEM tiles of
(BN rows x BD features), accumulating per-row-block partial counts across
the feature-tile grid dimension.

Oracle: repro.core.metrics.csim_ref (re-exported in ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256
DEFAULT_BD = 512


def _l0_kernel(x_ref, y_ref, o_ref, *, tol, nd):
    jd = pl.program_id(1)

    @pl.when(jd == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    diff = (jnp.abs(x_ref[...].astype(jnp.float32)
                    - y_ref[...].astype(jnp.float32)) > tol)
    o_ref[...] += jnp.sum(diff.astype(jnp.float32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret", "tol"))
def l0_rows(x, y, *, tol=0.0, bn=DEFAULT_BN, bd=DEFAULT_BD, interpret=False):
    """Per-row L0 distance between x and y: (n, d) x (n, d) -> (n,)."""
    n, d = x.shape
    bn = min(bn, n)
    bd = min(bd, d)
    pad_n = (-n) % bn
    pad_d = (-d) % bd
    if pad_n or pad_d:
        x = jnp.pad(x, ((0, pad_n), (0, pad_d)))
        y = jnp.pad(y, ((0, pad_n), (0, pad_d)))
    np_, dp = x.shape
    grid = (np_ // bn, dp // bd)
    out = pl.pallas_call(
        functools.partial(_l0_kernel, tol=tol, nd=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(x, y)
    return out[:n, 0]


@functools.partial(jax.jit, static_argnames=("rng", "tol", "interpret"))
def csim_kernel(X, rng: int, tol=0.0, *, interpret=False):
    """Eq. 3 via the Pallas L0 kernel, fused as one `lax.scan` over the
    shift range — one trace and one compiled pipeline regardless of rng
    (the old wrapper unrolled rng separate pallas calls)."""
    n = X.shape[0]
    rows = jnp.arange(n)

    def body(total, j):
        Xs = X[(rows + j) % n]               # == jnp.roll(X, -j, axis=0)
        total = total + jnp.sum(
            l0_rows(X, Xs, tol=tol, interpret=interpret))
        return total, None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            jnp.arange(1, rng + 1))
    return total / (n * rng)
