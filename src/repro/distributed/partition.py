"""Shard the engine's batched (m-grid x seed) simulations over a mesh.

The generic engine (`repro.experiments.engine`) runs each bucket of the
worker grid as ONE vmapped simulation — a batch whose elements are
independent ``(grid member m, seed replicate s)`` cells.  Independence is
the whole trick: the batch axis can be laid out across devices with
``jax.sharding`` and every element still computes exactly what it computes
on one device, so results are **mesh-invariant** (tested at 1e-5; see
docs/distributed.md for the contract).

:func:`run_grid_sharded` is the distributed twin of the engine's
``_run_grid``: for every bucket it

  1. flattens the bucket's (members x seeds) cells into one element axis
     — so a 4-member bucket with 8 seed replicates exposes 32 units of
     parallelism, not 4 (the seed axis shards too, per the tentpole),
  2. pads that axis to a multiple of the device count by repeating the
     first element (cheapest correct filler; the rows are dropped after),
  3. lays the padded ``(m, s)`` index arrays over the mesh's ``'shard'``
     axis with :class:`jax.sharding.NamedSharding`, replicates the other
     arguments (dataset, draws) on every device and dispatches ONE jitted
     vmap — computation follows the input sharding, so XLA splits the
     batch across devices,
  4. gathers, drops the padding rows, and scatters results back to grid
     order.

One jit per bucket signature, exactly like the unsharded path — the
engine keeps the program per process, so a later sweep of the same shapes
on the same mesh compiles nothing.  The engine owns bucket policy and its
program cache; both arrive as arguments, which keeps this module free of
engine imports.
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.mesh import DeviceMesh
from repro.telemetry import instrument, metrics, trace

#: bytes copied to the mesh's devices by replicating each bucket's
#: arguments: their bytes times the device count
_REPLICATED_BYTES = metrics.counter(
    "repro_mesh_replicated_bytes_total",
    help="bytes replicated to every device of a mesh (bytes x devices)")


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of ``k`` that is >= ``n``."""
    return -(-n // k) * k


def element_plan(pos: Sequence[int], ms: Sequence[int], n_seeds: int,
                 n_devices: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """Flattened, padded (m, seed) index arrays for one bucket.

    Element ``e`` of the batch is grid member ``pos[e // n_seeds]`` under
    seed ``e % n_seeds``; padding repeats element 0.  Returns
    ``(m_idx, s_idx, n_real)`` with ``len(m_idx) % n_devices == 0``.
    """
    m_idx = [ms[i] for i in pos for _ in range(n_seeds)]
    s_idx = [s for _ in pos for s in range(n_seeds)]
    n_real = len(m_idx)
    n_pad = pad_to_multiple(n_real, n_devices) - n_real
    m_idx += m_idx[:1] * n_pad
    s_idx += s_idx[:1] * n_pad
    return (np.asarray(m_idx, np.int32), np.asarray(s_idx, np.int32),
            n_real)


def _fresh_program(m_pad, args, build):
    return jax.jit(build()), False


def run_grid_sharded(make_sim_elem: Callable, ms: Sequence[int],
                     n_seeds: int, dmesh: DeviceMesh,
                     buckets: List[Tuple[Tuple[int, ...], int]],
                     program: Callable = _fresh_program,
                     algorithm: str = "sim") -> jnp.ndarray:
    """Run the whole grid sharded over ``dmesh``; rows follow ``ms`` order.

    ``make_sim_elem(m_pad)`` must return ``(sim_elem, args)``:
    ``sim_elem(m, s, *args) -> (n_evals,)`` obeying the engine's
    masked-simulation contract (numerics independent of ``m_pad`` for any
    ``m <= m_pad``), and the arrays it takes besides ``(m, s)``, which are
    replicated on every device (their bytes are the ``mesh_bucket``
    span's ``arg_bytes``); ``buckets`` is the engine's ``[(positions,
    m_pad), ...]`` partition (a single flat bucket for ``force_flat``
    algorithms).  ``program(m_pad, args, build)`` returns ``(jitted,
    cached)``: the jit of ``build()``, which the engine keeps per process
    (and counts in ``JIT_CALLS``) and the default builds afresh.  Each
    bucket's program is named ``bucket_<algorithm>_m<m_pad>``.

    Returns ``(S, n_evals)`` for ``n_seeds == 1``, else
    ``(S, n_seeds, n_evals)`` — the same contract as the engine's
    ``_run_grid``, so `_losses_dict` consumes either path unchanged.
    """
    sharded = dmesh.sharding()
    rows: List = [None] * len(ms)
    for pos, m_pad in buckets:
        m_idx, s_idx, n_real = element_plan(pos, ms, n_seeds,
                                            dmesh.n_devices)
        sim_elem, args = make_sim_elem(m_pad)

        def build():
            # vmapped over the flat (m, s) element axis; every element
            # shares the other arguments
            return instrument.named(
                jax.vmap(sim_elem, in_axes=(0, 0) + (None,) * len(args)),
                f"bucket_{algorithm}_m{m_pad}")

        jfn, cached = program(m_pad, args, build)
        with trace.span("shard_put", devices=dmesh.n_devices,
                        elements=len(m_idx)):
            m_arr = jax.device_put(m_idx, sharded)
            s_arr = jax.device_put(s_idx, sharded)
        arg_bytes = instrument.nbytes(args)
        _REPLICATED_BYTES.inc(arg_bytes * dmesh.n_devices)
        with trace.span("replicate", bytes=arg_bytes,
                        devices=dmesh.n_devices):
            args = jax.device_put(args, dmesh.replicated())
            if trace.enabled():
                # traced, the span waits for the copies to land so that it
                # times the transfer; untraced, dispatch is not held up
                args = jax.block_until_ready(args)
        out = instrument.dispatch(
            jfn, m_arr, s_arr, *args, span_name="mesh_bucket",
            algorithm=algorithm, m_pad=m_pad, devices=dmesh.n_devices,
            elements=len(m_idx), cached=cached, arg_bytes=arg_bytes)
        with trace.span("gather", elements=n_real):
            out = np.asarray(jax.device_get(out))[:n_real]
        out = out.reshape(len(pos), n_seeds, -1)
        for k, i in enumerate(pos):
            rows[i] = out[k] if n_seeds > 1 else out[k, 0]
    return jnp.stack([jnp.asarray(r) for r in rows])
