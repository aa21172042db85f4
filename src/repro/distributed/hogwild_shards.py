"""True multi-device Hogwild!: worker shards racing on a shared parameter.

The engine's Hogwild! (`repro.core.algorithms.hogwild`) *emulates* the
lock-free race as a sequential staleness recurrence — gradient ``j`` is
computed against the model from iteration ``j - tau`` with ``tau``
cycling over ``[1, m]`` (Thm 1).  That recurrence is the **parity
oracle**: deterministic, single-device, and what every grid sweep and
cache artifact is defined by.

This module runs the race for real.  The ``m`` workers are split into
``D`` shards (one per mesh device) under ``jax.shard_map``;
each shard races ahead on its own copy of the parameter vector — its
local workers apply full-step SGD updates sequentially, *reading*
whatever their shard's copy currently holds — and every ``sync_every``
rounds the shards reconcile by **summing their deltas onto the shared
parameter** (``x <- x_base + psum(x_local - x_base)``), i.e. every
gradient lands with its full step exactly as Hogwild!'s writes do, but
cross-shard reads are stale by up to ``sync_every * m`` server
iterations.  The shared parameter buffer is donated
(``donate_argnums``), so the reconciled model overwrites the stale one
in place instead of allocating per sync.

When it matches the oracle and when it diverges (docs/distributed.md):
both apply every gradient at full step against a model that is at most
O(m) iterations stale, so at small ``gamma * m`` the curves track within
a loose tolerance (tested in tests/test_distributed.py).  They are NOT
bit-comparable: the oracle's lag is exactly ``tau = (j % m) + 1`` while
the race's lag depends on the shard layout — ``D = 1`` degenerates to
fresh sequential SGD (no staleness at all), and large ``sync_every * m``
or large ``gamma`` amplify the divergence the same way real Hogwild!
degrades past the paper's ``m_max``.
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.algorithms.lr import LAMBDA, lr_grad, test_logloss
from repro.distributed import mesh as mesh_mod
from repro.resilience import faults
from repro.telemetry import instrument, metrics, recorder, trace

#: compile counter for the sharded racing mode (the engine's own
#: `JIT_CALLS` only counts grid-path compiles).  Registry-backed (PR 9);
#: the module-level ``JIT_CALLS`` read stays source-compatible via
#: ``__getattr__`` below.
_JIT_CALLS = metrics.counter(
    "repro_distributed_race_jit_compiles_total",
    help="racing-mode shard_map pipelines compiled")

#: host-side communication accounting for the racing mode: every psum
#: reconcile (scheduled sync rounds plus the forced per-eval sync) is one
#: cross-device collective round — the comm-cost axis ROADMAP item 3
#: models (wider sync_every trades staleness for fewer rounds)
_PSUM_ROUNDS = metrics.counter(
    "repro_distributed_psum_rounds_total",
    help="psum reconcile rounds executed by the racing mode")


def __getattr__(name):
    # PEP 562 read alias for the legacy module global (see engine.py)
    if name == "JIT_CALLS":
        return _JIT_CALLS.value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _build_race(X, y, Xte, yte, dmesh, *, w, gamma, lam, sync_every,
                fspec=None):
    """jitted ``(x0, samples, mask[, fstream]) -> losses`` racing pipeline.

    ``samples``: (n_evals, rounds_per_eval, D, w) sample indices, worker
    axis laid out over the mesh; ``mask``: (D, w) live-worker mask (0 for
    the padding workers that round ``m`` up to a multiple of ``D``).

    ``fspec`` (a resolved `repro.resilience.faults.FaultSpec`) switches to
    the faulted pipeline, which additionally takes ``fstream`` — the
    per-(round, worker) fault events, sharded exactly like ``samples``.
    A dropped update's gradient never enters its shard's local delta, so
    the next ``psum`` reconcile genuinely never sees it: the message is
    lost on the wire, not masked after the fact.  A straggle event makes
    the worker read its shard's *round-start* model (one round extra
    stale); corruption rewrites the gradient payload.  Zero-rate streams
    are bit-exact with the unfaulted pipeline.
    """
    axis = mesh_mod.SHARD_AXIS

    def shard_fn(x0, samples, mask):
        samples = samples[:, :, 0, :]            # local view: (E, R, w)
        mask = mask[0]                           # (w,)

        def worker_step(x_loc, inp):
            i, live = inp
            g = lr_grad(x_loc, X[i], y[i], lam)
            # the racing read: the gradient saw whatever this shard's
            # copy held; the write lands at full step (masked if padded)
            return x_loc - gamma * live * g, None

        def reconcile(args):
            # every shard's accumulated delta lands on the shared
            # parameter (sum, not mean — all writes count)
            x_base, x_loc = args
            x_sync = x_base + jax.lax.psum(x_loc - x_base, axis)
            return x_sync, x_sync

        def round_step(carry, s_round):
            x_base, x_loc, r = carry
            x_loc, _ = jax.lax.scan(worker_step, x_loc, (s_round, mask))
            # the round counter is replicated, so every shard takes the
            # same branch and non-sync rounds pay NO collective — wider
            # sync windows trade staleness for communication, which is
            # the whole tradeoff this mode exists to measure
            do = (r % sync_every) == (sync_every - 1)
            x_base, x_loc = jax.lax.cond(do, reconcile,
                                         lambda args: args,
                                         (x_base, x_loc))
            return (x_base, x_loc, r + 1), None

        def eval_block(carry, samples_e):
            carry, _ = jax.lax.scan(round_step, carry, samples_e)
            x_base, x_loc, r = carry
            # force a sync at the eval boundary: the evaluated model is
            # the shared parameter, identical on every shard
            x_sync, _ = reconcile((x_base, x_loc))
            return ((x_sync, x_sync, r),
                    test_logloss(x_sync, Xte, yte))

        carry0 = (x0, x0, jnp.zeros((), jnp.int32))
        (x, _, _), losses = jax.lax.scan(eval_block, carry0, samples)
        return x, losses

    def shard_fn_faulted(x0, samples, mask, fstream):
        samples = samples[:, :, 0, :]            # local view: (E, R, w)
        mask = mask[0]                           # (w,)
        fstream = {k: v[:, :, 0, :] for k, v in fstream.items()}

        def worker_step(carry, inp):
            x_loc, b = carry
            i, live, fd = inp
            # a straggler read its shard's round-start model — one round
            # of extra staleness on top of the race's own.  Both reads
            # are evaluated and the GRADIENT is selected: a select on the
            # model before `lr_grad` changes XLA's dot-reduction fusion
            # and costs ~1 ulp/step vs the unfaulted pipeline, while the
            # post-gradient select keeps zero-rate streams bit-exact.
            g = jnp.where(fd["straggle"] > 0,
                          lr_grad(b, X[i], y[i], lam),
                          lr_grad(x_loc, X[i], y[i], lam))
            g = faults.corrupt(fspec, g, fd["corrupt"])
            # drop: the update never enters the local delta, so the next
            # psum never sums it — a genuinely lost message; dup lands it
            # twice; zero-rate scale is a computed exact 1.0
            scale = faults.delivery_scale(fd)
            return (x_loc - gamma * live * scale * g, b), None

        def reconcile(args):
            x_base, x_loc = args
            x_sync = x_base + jax.lax.psum(x_loc - x_base, axis)
            return x_sync, x_sync

        def round_step(carry, inp):
            s_round, f_round = inp
            x_base, x_loc, r = carry
            (x_loc, _), _ = jax.lax.scan(
                worker_step, (x_loc, x_loc), (s_round, mask, f_round))
            do = (r % sync_every) == (sync_every - 1)
            x_base, x_loc = jax.lax.cond(do, reconcile,
                                         lambda args: args,
                                         (x_base, x_loc))
            return (x_base, x_loc, r + 1), None

        def eval_block(carry, inp):
            samples_e, fstream_e = inp
            carry, _ = jax.lax.scan(round_step, carry, (samples_e, fstream_e))
            x_base, x_loc, r = carry
            x_sync, _ = reconcile((x_base, x_loc))
            return ((x_sync, x_sync, r),
                    test_logloss(x_sync, Xte, yte))

        carry0 = (x0, x0, jnp.zeros((), jnp.int32))
        (x, _, _), losses = jax.lax.scan(eval_block, carry0,
                                         (samples, fstream))
        return x, losses

    if fspec is None:
        mapped = jax.shard_map(
            shard_fn, mesh=dmesh.mesh,
            in_specs=(P(), P(None, None, mesh_mod.SHARD_AXIS, None),
                      P(mesh_mod.SHARD_AXIS, None)),
            out_specs=(P(), P()), check_vma=False)
    else:
        mapped = jax.shard_map(
            shard_fn_faulted, mesh=dmesh.mesh,
            in_specs=(P(), P(None, None, mesh_mod.SHARD_AXIS, None),
                      P(mesh_mod.SHARD_AXIS, None),
                      P(None, None, mesh_mod.SHARD_AXIS, None)),
            out_specs=(P(), P()), check_vma=False)
    _JIT_CALLS.inc()
    return jax.jit(mapped, donate_argnums=(0,))


def run_hogwild_sharded(train, test, *, m: int = 8, iters: int = 4000,
                        gamma: float = 0.1, lam: float = LAMBDA,
                        eval_every: int = 100, key=None,
                        mesh: mesh_mod.MeshLike = None,
                        sync_every: int = 1,
                        fault: "faults.FaultLike" = None) -> Dict:
    """Race ``m`` workers over the mesh's devices; returns a curve dict.

    Server-iteration accounting matches the oracle: ``iters`` total
    gradient applications, a test-loss eval every ``eval_every`` of them
    (``eval_every`` must be a multiple of ``m`` so eval points land on
    round boundaries).  ``mesh`` resolves via `mesh.get_mesh` (auto =
    every device); workers pad up to a multiple of the device count with
    masked (inert) slots, so any ``m`` runs on any mesh.

    ``fault`` (FaultSpec / dict / None) injects per-(round, worker)
    delivery faults into the race — see :func:`_build_race`.  The event
    stream is drawn at the race's ``(E, R, D, w)`` layout from the fault
    seed; threefry draws depend only on the element count, so at
    ``m == D * w`` it is flat-identical to the sequential oracle's
    ``(iters,)`` stream — the engine's faulted Hogwild! with the same
    spec is the parity oracle at ``sync_every=1`` (for delivery faults;
    corruption parity additionally needs a gradient-linear corruption
    model like ``sign_flip``).
    """
    dmesh = mesh_mod.get_mesh(mesh)
    fspec = faults.resolve(fault)
    D = dmesh.n_devices
    if eval_every % m:
        raise ValueError(
            f"eval_every={eval_every} must be a multiple of m={m}: the "
            f"racing mode applies m gradients per round and evals on "
            f"round boundaries")
    key = key if key is not None else jax.random.PRNGKey(0)
    n = train.X.shape[0]
    w = -(-m // D)                       # workers per shard
    m_eff = w * D
    n_evals = iters // eval_every
    rounds_per_eval = eval_every // m
    # one sample per (round, worker slot); padded slots draw but never
    # apply, keeping live workers' streams independent of the mesh size
    samples = jax.random.randint(
        key, (n_evals, rounds_per_eval, D, w), 0, n)
    mask = (jnp.arange(m_eff) < m).astype(jnp.float32).reshape(D, w)

    race = _build_race(train.X, train.y, test.X, test.y, dmesh,
                       w=w, gamma=gamma, lam=lam, sync_every=sync_every,
                       fspec=fspec)
    x0 = jnp.zeros((train.X.shape[1],))
    if fspec is None:
        x, losses = instrument.dispatch(
            race, x0, samples, mask, span_name="race",
            m=m, devices=D, sync_every=sync_every)
    else:
        fstream = faults.make_stream(
            fspec, (n_evals, rounds_per_eval, D, w))
        x, losses = instrument.dispatch(
            race, x0, samples, mask, fstream, span_name="race",
            m=m, devices=D, sync_every=sync_every, faulted=True)
    with trace.span("fetch"):
        losses = jax.device_get(losses)
    # host-side mirror of the pipeline's sync schedule: the global round
    # counter r hits (r % sync_every == sync_every - 1) exactly
    # R_total // sync_every times over R_total rounds, and every eval
    # block forces one extra reconcile at its boundary
    r_total = n_evals * rounds_per_eval
    psum_rounds = r_total // sync_every + n_evals
    _PSUM_ROUNDS.inc(psum_rounds)
    recorder.publish("race", m=m, devices=D, sync_every=sync_every,
                     psum_rounds=psum_rounds,
                     faulted=fspec is not None)
    out = {
        "algorithm": "hogwild_sharded",
        "m": m,
        "devices": D,
        "sync_every": sync_every,
        "iters": n_evals * eval_every,
        "eval_every": eval_every,
        "losses": losses,
        "x": x,
        "iters_per_worker": iters / m,
        "psum_rounds": psum_rounds,
    }
    if fspec is not None:
        out["fault"] = fspec.to_dict()
    return out


def sweep_hogwild_sharded(train, test, ms: Sequence[int], *, iters: int,
                          eval_every: int, gamma: float = 0.1,
                          lam: float = LAMBDA, key=None,
                          mesh: mesh_mod.MeshLike = None,
                          sync_every: int = 1,
                          fault: "faults.FaultLike" = None) -> Dict:
    """Racing-mode m-grid (Python loop per m — this mode parallelizes over
    *devices*, not grid members; the engine's vmapped grid with the
    staleness oracle remains the cached, mesh-invariant default).

    Each m's eval cadence is aligned DOWN to its nearest round boundary
    (``ev_m = m * (eval_every // m)``, at least one round) and its budget
    to ``(iters // eval_every) * ev_m`` — so any grid runs, every row has
    the same number of evals, and eval points sit within one round of
    the requested cadence.
    """
    dmesh = mesh_mod.get_mesh(mesh)
    n_evals = iters // eval_every
    curves = []
    for m in ms:
        ev = int(m) * max(1, eval_every // int(m))
        curves.append(run_hogwild_sharded(
            train, test, m=int(m), iters=n_evals * ev, eval_every=ev,
            gamma=gamma, lam=lam, key=key, mesh=dmesh,
            sync_every=sync_every, fault=fault)["losses"])
    return {
        "algorithm": "hogwild_sharded",
        "problem": "logistic",
        "ms": [int(m) for m in ms],
        "devices": dmesh.n_devices,
        "iters": int(iters),
        "eval_every": int(eval_every),
        "n_seeds": 1,
        "losses": [[float(v) for v in row] for row in curves],
    }
