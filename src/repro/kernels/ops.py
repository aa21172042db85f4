"""jit'd public wrappers around the Pallas kernels.

The kernel modules default to compiled Pallas.  These wrappers pick
interpret mode when the computation will not run on a TPU (the tests and
CPU runs) and the compiled kernel when it will; the choice follows
`repro.runtime.default_platform`, so ``with jax.default_device(cpu)``
on a TPU host interprets too.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import runtime
from repro.kernels import csim as _csim
from repro.kernels import flash_attention as _fa
from repro.kernels import quantize as _q
from repro.kernels import rmsnorm as _rn


def _interpret_default():
    return runtime.default_platform() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, bq=None, bk=None):
    """Model-layout wrapper: q (B,S,H,D), k/v (B,T,KV,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = bq or min(_fa.DEFAULT_BQ, S)
    bk = bk or min(_fa.DEFAULT_BK, k.shape[1])
    # pad S/T to block multiples; padded q rows attend only to themselves
    pad_q = (-S) % bq
    pad_k = (-k.shape[1]) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   bq=bq, bk=bk,
                                   interpret=_interpret_default())
    return out[:, :, :S].transpose(0, 2, 1, 3)


def csim(X, rng: int, tol=0.0):
    return _csim.csim_kernel(X, rng, tol, interpret=_interpret_default())


def l0_rows(x, y, tol=0.0):
    return _csim.l0_rows(x, y, tol=tol, interpret=_interpret_default())


def quantize_stochastic(x, key, *, bits=8):
    """Any-shape wrapper (kernel is 2D-tiled)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim >= 2 else x.reshape(1, -1)
    q, scale = _q.quantize_stochastic_2d(x2, key, bits=bits,
                                         interpret=_interpret_default())
    return q.reshape(shape), scale


def dequantize(q, scale):
    shape = q.shape
    q2 = q.reshape(-1, shape[-1]) if q.ndim >= 2 else q.reshape(1, -1)
    x = _q.dequantize_2d(q2, scale, interpret=_interpret_default())
    return x.reshape(shape)


def rmsnorm(x, gain, eps=1e-6):
    """Any-rank wrapper: normalizes the last dim."""
    shape = x.shape
    out = _rn.rmsnorm_2d(x.reshape(-1, shape[-1]), gain, eps=eps,
                         interpret=_interpret_default())
    return out.reshape(shape)
