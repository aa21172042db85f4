"""Shard the engine's batched (m-grid x seed) simulations over a mesh.

The generic engine (`repro.experiments.engine`) runs each bucket of the
worker grid as ONE vmapped simulation — a batch whose elements are
independent ``(grid member m, seed replicate s)`` cells.  Independence is
the whole trick: the batch can be laid out across devices and every
element still computes exactly what it computes on one device, so results
are **mesh-invariant** (tested at 1e-5; see docs/distributed.md for the
contract).

:func:`run_grid_sharded` is the distributed twin of the engine's
``_run_grid``.  Each bucket takes one of two layouts, chosen by
:func:`layout` from shapes alone:

* **seed** (``n_seeds % n_devices == 0``): the seed axis is split over
  the mesh's ``'shard'`` axis by one ``jax.shard_map``.  Each device runs
  the engine's own unsharded bucket program on its ``n_seeds /
  n_devices`` seeds — the members vmapped over the seed-vmapped
  simulation, so every member of a seed shares that seed's draws, and
  with them its row gathers.  The dataset is replicated, the draws are
  split (each device holds only its seeds'), and the body holds no
  collectives.  No padding.
* **element** (otherwise, e.g. one seed on four devices): the bucket's
  (members x seeds) cells are flattened into one element axis, padded to
  a multiple of the device count by repeating the first element, and the
  ``(m, s)`` index arrays are laid over the mesh; the dataset and draws
  are replicated, and ONE jitted vmap over the elements follows the
  index sharding.  Each element gathers its own draws, so members of one
  seed share nothing; in exchange the padding wastes at most one element
  per device, where padding the seed axis would waste whole seeds.

Either way the results are gathered and scattered back to grid order.
One jit per bucket signature and layout, exactly like the unsharded
path — the engine keeps the program per process, so a later sweep of the
same shapes on the same mesh compiles nothing.  The engine owns bucket
policy and its program cache; both arrive as arguments, which keeps this
module free of engine imports.
"""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.mesh import SHARD_AXIS, DeviceMesh
from repro.telemetry import instrument, metrics, trace

#: bytes copied to the mesh's devices by replicating each bucket's
#: arguments: their bytes times the device count
_REPLICATED_BYTES = metrics.counter(
    "repro_mesh_replicated_bytes_total",
    help="bytes replicated to every device of a mesh (bytes x devices)")

#: One increment per bucket dispatched on a mesh, by its :func:`layout`.
_MESH_BUCKETS = {
    name: metrics.counter(
        "repro_mesh_buckets_total", labels={"layout": name},
        help="buckets dispatched on a mesh, by layout: seed axis split "
             "over the devices, or flat (m, seed) elements")
    for name in ("seed", "element")}


def layout(n_seeds: int, n_devices: int) -> str:
    """``"seed"`` when the seeds divide evenly over the devices, so each
    device runs whole seeds with no padding; else ``"element"``."""
    return "seed" if n_seeds % n_devices == 0 else "element"


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


def _fresh_program(m_pad, how, args, build):
    return jax.jit(build()), False


def _seed_program(sim, dmesh: DeviceMesh):
    """Members vmapped over the seed-vmapped ``sim``, run by every device
    on its own seeds: ``(m_vec, data, stacked) -> (members, n_seeds,
    n_evals)``, the seed axis split over the mesh."""
    def body(m_vec, data, stacked):
        def member(m):
            # the seed axis stays even where a device holds one seed
            return jax.vmap(lambda sub: sim(m, data, sub))(stacked)
        return jax.vmap(member)(m_vec)

    return jax.shard_map(body, mesh=dmesh.mesh,
                         in_specs=(P(), P(), P(SHARD_AXIS)),
                         out_specs=P(None, SHARD_AXIS), check_vma=False)


def _element_program(sim):
    """``sim`` over a flat (m, seed) element axis, each element taking its
    seed's draws by the traced index: ``(data, stacked, m_idx, s_idx) ->
    (elements, n_evals)``."""
    def sim_elem(data, stacked, m, s):
        return sim(m, data, jax.tree.map(lambda a: a[s], stacked))

    return jax.vmap(sim_elem, in_axes=(None, None, 0, 0))


def run_grid_sharded(make_sim: Callable, ms: Sequence[int],
                     n_seeds: int, dmesh: DeviceMesh,
                     buckets: List[Tuple[Tuple[int, ...], int]],
                     program: Callable = _fresh_program,
                     algorithm: str = "sim") -> jnp.ndarray:
    """Run the whole grid sharded over ``dmesh``; rows follow ``ms`` order.

    ``make_sim(m_pad)`` must return ``(sim, data, stacked)``: ``sim(m,
    data, sub) -> (n_evals,)`` obeying the engine's masked-simulation
    contract (numerics independent of ``m_pad`` for any ``m <= m_pad``),
    the arrays every simulation shares (replicated on every device; their
    bytes are the ``replicate`` span's ``bytes``), and the draws stacked
    on a leading axis of ``n_seeds``, of which ``sim`` takes one seed's
    as ``sub``.  ``buckets`` is the engine's ``[(positions, m_pad), ...]``
    partition (a single flat bucket for ``force_flat`` algorithms).
    ``program(m_pad, layout, args, build)`` returns ``(jitted, cached)``:
    the jit of ``build()``, which the engine keeps per process (and counts
    in ``JIT_CALLS``) and the default builds afresh.  Each bucket's
    program is named ``bucket_<algorithm>_m<m_pad>``.

    Returns ``(S, n_evals)`` for ``n_seeds == 1``, else
    ``(S, n_seeds, n_evals)`` — the same contract as the engine's
    ``_run_grid``, so `_losses_dict` consumes either path unchanged.
    """
    how = layout(n_seeds, dmesh.n_devices)
    rows: List = [None] * len(ms)
    for pos, m_pad in buckets:
        sim, data, stacked = make_sim(m_pad)

        def build():
            fn = (_seed_program(sim, dmesh) if how == "seed"
                  else _element_program(sim))
            return instrument.named(fn, f"bucket_{algorithm}_m{m_pad}")

        jfn, cached = program(m_pad, how, (data, stacked), build)
        if how == "seed":
            # the seeds are split, so only the members and data are copied
            split = [stacked]
            placed = [np.asarray([ms[i] for i in pos], np.int32), data]
            n_real = n_elems = len(pos) * n_seeds
        else:
            m_idx, s_idx, n_real = element_plan(pos, ms, n_seeds,
                                                dmesh.n_devices)
            split = [m_idx, s_idx]
            placed = [data, stacked]
            n_elems = len(m_idx)
        with trace.span("shard_put", devices=dmesh.n_devices,
                        elements=n_elems):
            split = jax.device_put(split, dmesh.sharding())
        rep_bytes = instrument.nbytes(placed)
        _REPLICATED_BYTES.inc(rep_bytes * dmesh.n_devices)
        with trace.span("replicate", bytes=rep_bytes,
                        devices=dmesh.n_devices):
            placed = jax.device_put(placed, dmesh.replicated())
            if trace.enabled():
                # traced, the span waits for the copies to land so that it
                # times the transfer; untraced, dispatch is not held up
                placed = jax.block_until_ready(placed)
        _MESH_BUCKETS[how].inc()
        out = instrument.dispatch(
            jfn, *placed, *split, span_name="mesh_bucket",
            algorithm=algorithm, m_pad=m_pad, devices=dmesh.n_devices,
            layout=how, elements=n_elems, cached=cached,
            arg_bytes=instrument.nbytes(data, stacked))
        with trace.span("gather", elements=n_real):
            out = np.asarray(jax.device_get(out))[:n_real]
        out = out.reshape(len(pos), n_seeds, -1)
        for k, i in enumerate(pos):
            rows[i] = out[k] if n_seeds > 1 else out[k, 0]
    return jnp.stack([jnp.asarray(r) for r in rows])
