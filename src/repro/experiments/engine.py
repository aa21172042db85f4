"""Generic batched m-sweep engine: one vmapped path over the worker axis,
dispatching through the `Algorithm` x `Problem` registries.

The legacy benchmarks re-ran each algorithm once per worker count m in a
Python loop — S separate traces, S compilations, S dispatch chains.
ENGINE_VERSION 2 re-derived each of the paper's four algorithms as a
*masked, padded* simulation over a fixed worker axis of size ``m_pad`` in
which the actual worker count m is ordinary traced data — but as four
hand-written sweepers with a hardcoded logistic loss.  ENGINE_VERSION 3
keeps that one-trace machinery and makes it *generic*: :func:`sweep` builds
the masked simulation for ANY registered `repro.core.algorithms.base.
Algorithm` on ANY registered `repro.core.problems.Problem`, so new
optimizers and objectives run through the full grid, cache, and CLI with
zero edits here.

The masked-simulation contract (unchanged from ENGINE_VERSION 2):

  * workers with index >= m are masked out of every reduction (gradient
    average, ring average, dual all-gather), so the padded run is
    numerically the m-worker run;
  * all random draws (`Algorithm.make_draws`) are made once at the *global*
    ``m_top = max(ms)`` and sliced per padding width — sweep member m
    consumes the first m columns no matter which bucket it lands in, so
    numerics are identical across flat / bucketed / sequential execution;
  * each bucket of the grid then runs as ``jax.vmap(sim)(ms_bucket)`` —
    one trace, one compile, one `lax.scan` pipeline per bucket.

**Bucketed padding** (`_buckets`): a flat padded grid does S * work(m_top)
FLOPs, so wide grids like [1, 2, 4, ..., 64] pay work(64) for the m=1
member.  `_run_grid` instead partitions the grid greedily into buckets
whose pad waste is bounded — ``max(bucket) <= MAX_PAD_RATIO * min(bucket)``
(default 2x) — and vmaps each bucket at its own ``m_pad``.  The trade is
one extra compile per bucket against the padded FLOPs, so bucketing pays
exactly when per-step work scales with the worker axis; each Algorithm
declares its own policy (``bucketed_default``: on for mini-batch and
ECD-PSGD, off for DADM) and ``force_flat`` algorithms (Hogwild!, whose
work is O(iters * d) regardless of the pad width) always run as one flat
vmap.  ``bucketed=False`` recovers the flat grid everywhere.

``use_vmap=False`` runs the *same* masked kernel (padded to m_top) once
per m in a Python loop — the sequential reference path the equivalence
tests compare against.  The per-algorithm ``sweep_*`` wrappers keep the
ENGINE_VERSION-2 signatures; for Hogwild! the sequential path still loops
the legacy per-m `run_hogwild`, so the vmapped grid is checked against the
original staleness recurrence, not against another padded kernel.

**Seed axis** (ENGINE_VERSION 4): ``n_seeds > 1`` replicates every job
over independent draw sequences — `Algorithm.make_draws` is called once
per seed (seed 0 with the caller's key, bit-identical to the
ENGINE_VERSION-3 single-seed run; seed s with ``fold_in(key, s)``), the
per-seed draws are stacked, and the per-m simulation is ``jax.vmap``-ed
over that stacked axis *inside* ``sim(m)``.  The m-grid vmap then wraps
the seed vmap, so the whole (seeds x m) grid is still ONE trace and ONE
compile per bucket — no per-seed recompiles (`scripts/bench_engine.py`
measures this via `JIT_CALLS` in BENCH_5.json).  Results keep ``losses``
as the seed-0 rows (every legacy consumer unchanged) and add
``losses_seeds`` — the full (S, n_seeds, n_evals) block `repro.analysis.
stats` turns into mean/CI curves and bootstrap m_max distributions.

**Device-mesh sharding** (ENGINE_VERSION 5): ``mesh=`` hands each
bucket's batched simulation to `repro.distributed.partition`, which
flattens the (members x seeds) cells into one element axis, pads it to
the device count, and dispatches ONE jitted vmap whose inputs are laid
over the mesh — XLA then splits the batch across devices.  Because the
cells are independent, results are **mesh-invariant** (1e-5 contract,
tests/test_distributed.py) and cache fingerprints exclude the mesh
entirely.  ``mesh=None`` (every existing caller) and single-device
meshes take the exact unsharded path below — the single-device fallback
is bit-exact with ENGINE_VERSION 4.  The sequential reference path
(``use_vmap=False``) never shards.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import problems as problems_mod
from repro.core.algorithms import base as alg_base
from repro.core.algorithms import run_hogwild
from repro.core.algorithms.lr import LAMBDA
from repro.distributed import mesh as dist_mesh
from repro.distributed import partition as dist_partition
from repro.telemetry import instrument, metrics, recorder, trace

#: Pad-waste bound for `_buckets`: within a bucket, the padded worker axis
#: is at most this multiple of the smallest member.
MAX_PAD_RATIO = 2.0

#: Counts `jax.jit` wrappers actually dispatched by `_run_grid` — each one
#: is traced and compiled exactly once here, so this is the engine's
#: compile count.  Registry-backed (PR 9): increments are locked so the
#: multi-threaded service counts exactly; the module-level ``JIT_CALLS``
#: read (`scripts/bench_engine.py` snapshots, tests) stays source-
#: compatible via ``__getattr__`` below.
_JIT_CALLS = metrics.counter(
    "repro_engine_jit_compiles_total",
    help="jax.jit wrappers dispatched by the engine (one XLA compile each)")

#: Fraction of the last vmapped grid's padded worker-axis FLOPs that were
#: padding waste: 1 - sum(m) / sum(m_pad per member).  0 for a perfectly
#: bucketed grid, approaching (1 - 1/MAX_PAD_RATIO) at the bound.
_PAD_WASTE = metrics.gauge(
    "repro_engine_pad_waste_ratio",
    help="pad-waste fraction of the last grid: 1 - sum(m)/sum(m_pad)")


def __getattr__(name):
    # PEP 562 read alias: `engine.JIT_CALLS` was a racy module global;
    # every external usage is a read, so it now reflects the registry
    # counter (writes go through `_JIT_CALLS.inc()`).
    if name == "JIT_CALLS":
        return _JIT_CALLS.value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _jit(fn):
    _JIT_CALLS.inc()
    return jax.jit(fn)


def _note_pad_waste(assignments) -> None:
    """Record the grid's pad waste from ``(m, m_pad)`` member pairs."""
    total = sum(pad for _, pad in assignments)
    if total:
        waste = 1.0 - sum(m for m, _ in assignments) / total
        _PAD_WASTE.set(waste)
        recorder.publish("grid", members=len(assignments),
                         pad_waste=round(waste, 4))


def _losses_dict(algorithm: str, ms, losses, iters: int, eval_every: int,
                 problem: str = "logistic", n_seeds: int = 1):
    """Engine output contract: curves for every m of the grid.  The
    ``problem`` key is new in ENGINE_VERSION 3, ``n_seeds``/``losses_seeds``
    in ENGINE_VERSION 4 (both additive — legacy keys are unchanged;
    ``losses`` is always the seed-0 rows)."""
    with trace.span("fetch"):
        losses = jax.device_get(losses)
    out = {
        "algorithm": algorithm,
        "problem": problem,
        "ms": [int(m) for m in ms],
        "iters": int(iters),
        "eval_every": int(eval_every),
        "n_seeds": int(n_seeds),
    }
    if n_seeds == 1:
        # (S, n_evals) float list-of-lists, row i <-> ms[i]
        out["losses"] = [[float(v) for v in row] for row in losses]
    else:
        # losses: (S, n_seeds, n_evals); seed 0 is the legacy sequence
        out["losses"] = [[float(v) for v in row[0]] for row in losses]
        out["losses_seeds"] = [[[float(v) for v in curve] for curve in row]
                               for row in losses]
    return out


def _buckets(ms: Sequence[int],
             max_pad_ratio: float = MAX_PAD_RATIO
             ) -> List[Tuple[Tuple[int, ...], int]]:
    """Greedy waste-bounded partition of the m-grid.

    Returns ``[(positions, m_pad), ...]`` where ``positions`` index into
    ``ms`` and ``m_pad = max(ms[i] for i in positions)``.  Scanning the
    grid in ascending order, a member opens a new bucket whenever it would
    exceed ``max_pad_ratio *`` the bucket's smallest m — so no member is
    ever padded past that ratio, bounding the wasted FLOPs of the padded
    vmap at ``max_pad_ratio``x per member.
    """
    order = sorted(range(len(ms)), key=lambda i: ms[i])
    out: List[Tuple[Tuple[int, ...], int]] = []
    cur: List[int] = []
    for i in order:
        if cur and ms[i] > max_pad_ratio * ms[cur[0]]:
            out.append((tuple(cur), ms[cur[-1]]))
            cur = []
        cur.append(i)
    if cur:
        out.append((tuple(cur), ms[cur[-1]]))
    return out


def _bucket_program(sim, algorithm: str, m_pad: int):
    """The vmapped simulation, named ``bucket_<algorithm>_m<m_pad>`` (the
    trace's ``jit_bucket_...`` module) and jitted."""
    return _jit(instrument.named(jax.vmap(sim),
                                 f"bucket_{algorithm}_m{m_pad}"))


def _run_grid(make_sim, ms, use_vmap: bool, bucketed: bool,
              algorithm: str):
    """Run the grid's simulations; rows follow ``ms`` order.

    ``make_sim(m_pad)`` must return ``(sim, consts)``: a closure
    ``sim(m) -> (n_evals,)`` that is numerically independent of ``m_pad``
    for any ``m <= m_pad`` (shared draws sliced, reductions masked) — that
    contract is what makes the three execution modes here interchangeable
    — and the arrays it closes over, whose bytes the ``bucket`` span
    reports as ``const_bytes``.
    """
    m_top = max(ms)
    if not use_vmap:
        _note_pad_waste([(m, m_top) for m in ms])
        jsim = _jit(make_sim(m_top)[0])   # one compile serves every m
        return jnp.stack([
            instrument.dispatch(jsim, m, span_name="grid_member",
                                m=int(m), m_pad=m_top)
            for m in jnp.asarray(ms, jnp.int32)])
    buckets = (_buckets(ms) if bucketed
               else [(tuple(range(len(ms))), m_top)])
    _note_pad_waste([(ms[i], m_pad) for pos, m_pad in buckets for i in pos])
    rows = [None] * len(ms)
    for pos, m_pad in buckets:
        sim, consts = make_sim(m_pad)
        out = instrument.dispatch(
            _bucket_program(sim, algorithm, m_pad),
            jnp.asarray([ms[i] for i in pos], jnp.int32),
            span_name="bucket", algorithm=algorithm, m_pad=m_pad,
            members=len(pos), const_bytes=instrument.nbytes(consts))
        if pos == tuple(range(len(ms))):
            return out                    # one bucket, already in order
        for k, i in enumerate(pos):
            rows[i] = out[k]
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# The generic sweep: any registered Algorithm on any registered Problem
# ---------------------------------------------------------------------------

def sweep(algorithm: Union[str, alg_base.Algorithm], train, test,
          ms: Sequence[int], *, iters: int, eval_every: int,
          problem="logistic", lam: Optional[float] = None, key=None,
          use_vmap: bool = True, bucketed: Optional[bool] = None,
          n_seeds: int = 1, mesh: "dist_mesh.MeshLike" = None,
          **alg_kwargs) -> Dict:
    """Run ``algorithm`` on ``problem`` over the worker grid ``ms``.

    ``algorithm`` is a registry name (instantiated with ``alg_kwargs``,
    e.g. ``gamma=0.05``) or a ready `Algorithm` instance; ``problem`` a
    registry name / class / instance (``lam`` overrides its regularizer,
    preserving the legacy ``lam=`` kwarg).  ``bucketed=None`` defers to the
    algorithm's declared padding policy.  ``n_seeds > 1`` replicates every
    grid member over that many independent draw sequences, vmapped inside
    the same trace (seed 0 == the single-seed run bit-exactly).

    ``mesh`` shards each bucket's batched simulation over a device mesh
    (`repro.distributed`): ``None`` keeps the unsharded path, an int /
    ``"auto"`` / `DeviceMesh` resolves via `repro.distributed.get_mesh`.
    Execution-only: results are mesh-invariant at 1e-5 and a
    single-device mesh is bit-exact with ``mesh=None``.
    """
    if isinstance(algorithm, alg_base.Algorithm):
        if alg_kwargs:
            raise TypeError("pass algorithm kwargs either via the instance "
                            "or via **alg_kwargs, not both")
        alg = algorithm
    else:
        alg = alg_base.get_algorithm(algorithm)(**alg_kwargs)
    prob = problems_mod.resolve_problem(problem, lam)
    key = key if key is not None else jax.random.PRNGKey(0)
    if n_seeds < 1:
        raise ValueError(f"n_seeds={n_seeds} must be >= 1")

    ms = list(ms)
    m_top = max(ms)
    n = train.X.shape[0]
    Xte, yte = test.X, test.y
    n_evals = iters // eval_every
    # seed 0 uses the caller's key unchanged — the ENGINE_VERSION-3 draws
    # bit-exactly — and seed s folds s into it, so growing n_seeds only
    # appends replicates, never perturbs existing ones
    seed_keys = [key] + [jax.random.fold_in(key, s)
                         for s in range(1, n_seeds)]
    draws_by_seed = [alg.make_draws(k, n, iters, m_top) for k in seed_keys]

    def make_sim_with(m_pad):
        def sim_with(sub):
            def sim(m):
                ctx = alg_base.SimContext(m, m_pad)
                state0 = alg.init_state(prob, train, ctx)

                def step(state, inp):
                    batch, t = inp
                    return alg.step(prob, train, ctx, state, batch, t), None

                def outer(state, e):
                    base = e * eval_every
                    ts = base + jnp.arange(eval_every)
                    bsl = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                        a, base, eval_every, axis=0), sub)
                    state, _ = jax.lax.scan(step, state, (bsl, ts))
                    return state, prob.test_loss(alg.readout(ctx, state),
                                                 Xte, yte)

                _, losses = jax.lax.scan(outer, state0, jnp.arange(n_evals))
                return losses

            return sim

        return sim_with

    # the dataset arrays every simulation closes over (besides its draws)
    data = (train.X, train.y, Xte, yte)

    def make_sim(m_pad):
        sim_with = make_sim_with(m_pad)
        subs = [alg.slice_draws(d, m_pad) for d in draws_by_seed]

        if n_seeds == 1:
            # the exact ENGINE_VERSION-3 path
            return sim_with(subs[0]), (data, subs)

        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *subs)

        def sim_seeded(m):
            # vmap the per-seed simulation over the stacked draw axis: the
            # m-grid vmap in `_run_grid` wraps this, so the whole
            # (seeds x m) block is one trace / one compile per bucket
            return jax.vmap(lambda sub: sim_with(sub)(m))(stacked)

        return sim_seeded, (data, stacked)

    def make_sim_elem(m_pad):
        # distributed twin of `make_sim`: one simulation per (m, seed)
        # cell, with the seed's draws gathered by the traced index — the
        # partitioner vmaps this over a flat element axis laid across the
        # mesh, so the seed axis shards exactly like the grid axis
        sim_with = make_sim_with(m_pad)
        subs = [alg.slice_draws(d, m_pad) for d in draws_by_seed]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *subs)

        def sim_elem(m, s):
            sub = jax.tree.map(lambda a: a[s], stacked)
            return sim_with(sub)(m)

        return sim_elem, (data, stacked)

    if bucketed is None:
        bucketed = alg.bucketed_default
    if alg.force_flat:
        bucketed = False
    dmesh = dist_mesh.resolve(mesh)
    with trace.span("grid", algorithm=alg.name, problem=prob.name,
                    members=len(ms), n_seeds=n_seeds):
        if dmesh is not None and dmesh.n_devices > 1 and use_vmap:
            buckets = (_buckets(ms) if bucketed
                       else [(tuple(range(len(ms))), m_top)])
            _note_pad_waste([(ms[i], m_pad)
                             for pos, m_pad in buckets for i in pos])
            losses = dist_partition.run_grid_sharded(
                make_sim_elem, ms, n_seeds, dmesh, buckets, jit_fn=_jit,
                algorithm=alg.name)
        else:
            losses = _run_grid(make_sim, ms, use_vmap, bucketed, alg.name)
        return _losses_dict(alg.name, ms, losses, iters, eval_every,
                            problem=prob.name, n_seeds=n_seeds)


def run_algorithm_sweep(algorithm: str, train, test, ms, *, iters,
                        eval_every, use_vmap=True, bucketed=None,
                        n_seeds=1, mesh=None, **kwargs) -> Dict:
    """Dispatch one (algorithm, problem, dataset) job over the worker grid.

    Every registered algorithm routes through the generic :func:`sweep`;
    the four paper algorithms go via their ``sweep_*`` compatibility
    wrappers (which only add the legacy Hogwild! sequential reference
    path).  ``bucketed=None`` keeps each algorithm's declared default;
    ``mesh`` is the execution-only device mesh (see :func:`sweep`).
    """
    fn = SWEEPERS.get(algorithm)
    if fn is None:
        return sweep(algorithm, train, test, ms, iters=iters,
                     eval_every=eval_every, use_vmap=use_vmap,
                     bucketed=bucketed, n_seeds=n_seeds, mesh=mesh,
                     **kwargs)
    if bucketed is not None:
        kwargs["bucketed"] = bucketed
    return fn(train, test, list(ms), iters=iters, eval_every=eval_every,
              use_vmap=use_vmap, n_seeds=n_seeds, mesh=mesh, **kwargs)


# ---------------------------------------------------------------------------
# ENGINE_VERSION-2 compatibility wrappers (same signatures and defaults)
# ---------------------------------------------------------------------------

def sweep_minibatch(train, test, ms: Sequence[int], *, iters: int,
                    eval_every: int, gamma=0.1, lam=LAMBDA, key=None,
                    use_vmap=True, bucketed=True, n_seeds=1,
                    problem="logistic", mesh=None) -> Dict:
    return sweep("minibatch", train, test, ms, iters=iters,
                 eval_every=eval_every, problem=problem, lam=lam, key=key,
                 use_vmap=use_vmap, bucketed=bucketed, n_seeds=n_seeds,
                 mesh=mesh, gamma=gamma)


def sweep_ecd_psgd(train, test, ms: Sequence[int], *, iters: int,
                   eval_every: int, gamma=0.1, lam=LAMBDA, compress_bits=8,
                   key=None, use_vmap=True, bucketed=True, n_seeds=1,
                   problem="logistic", mesh=None) -> Dict:
    return sweep("ecd_psgd", train, test, ms, iters=iters,
                 eval_every=eval_every, problem=problem, lam=lam, key=key,
                 use_vmap=use_vmap, bucketed=bucketed, n_seeds=n_seeds,
                 mesh=mesh, gamma=gamma, compress_bits=compress_bits)


def sweep_dadm(train, test, ms: Sequence[int], *, iters: int, eval_every: int,
               local_batch=8, lam=LAMBDA, key=None, use_vmap=True,
               bucketed=False, n_seeds=1, problem="logistic",
               mesh=None) -> Dict:
    return sweep("dadm", train, test, ms, iters=iters,
                 eval_every=eval_every, problem=problem, lam=lam, key=key,
                 use_vmap=use_vmap, bucketed=bucketed, n_seeds=n_seeds,
                 mesh=mesh, local_batch=local_batch)


def sweep_hogwild(train, test, ms: Sequence[int], *, iters: int,
                  eval_every: int, gamma=0.1, lam=LAMBDA, key=None,
                  use_vmap=True, bucketed=True, n_seeds=1,
                  problem="logistic", mesh=None, fault=None) -> Dict:
    key = key if key is not None else jax.random.PRNGKey(0)
    if (fault is None and not use_vmap and problem == "logistic"
            and n_seeds == 1):
        # Legacy per-m reference path (re-jits per m): the vmapped grid is
        # equivalence-tested against this, i.e. against the original
        # recurrence rather than against another padded kernel.
        _JIT_CALLS.inc(len(ms))
        curves = []
        for m in ms:
            with trace.span("grid_member", m=int(m), legacy=True):
                curves.append(run_hogwild(
                    train, test, m=int(m), iters=iters, gamma=gamma,
                    lam=lam, eval_every=eval_every, key=key)["losses"])
        return _losses_dict("hogwild", ms,
                            jnp.stack([jnp.asarray(c) for c in curves]),
                            iters, eval_every)
    del bucketed   # force_flat: work is O(iters * d) regardless of m_pad
    return sweep("hogwild", train, test, ms, iters=iters,
                 eval_every=eval_every, problem=problem, lam=lam, key=key,
                 use_vmap=use_vmap, n_seeds=n_seeds, mesh=mesh, gamma=gamma,
                 fault=fault)


SWEEPERS = {
    "minibatch": sweep_minibatch,
    "ecd_psgd": sweep_ecd_psgd,
    "dadm": sweep_dadm,
    "hogwild": sweep_hogwild,
}
