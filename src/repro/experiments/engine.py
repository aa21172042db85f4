"""Generic batched m-sweep engine: one vmapped path over the worker axis,
dispatching through the `Algorithm` x `Problem` registries.

The per-m `run_*` functions of `repro.core.algorithms` run each algorithm
once per worker count m — S separate traces, S compilations, S dispatch
chains over a grid of S.
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
  * each bucket of the grid then runs as ``jax.vmap(sim)(ms_bucket, data,
    draws)`` — one trace, one compile per bucket signature, per process,
    and one `lax.scan` pipeline per bucket.

**Programs kept per process** (`_program`): a simulation is a pure
function of ``(m, data, draws)``, where ``data = (X, y, Xte, yte)`` and
``draws`` are the bucket's sliced (seed-stacked) draws, passed as jit
arguments rather than closed over as constants.  Its closure holds only
static values — the frozen `Algorithm` and `Problem` instances, ``m_pad``,
``iters``, ``eval_every``, ``n_seeds`` — so the jitted program is kept in
a bounded, locked LRU (`PROGRAM_CACHE_SIZE`) keyed on those, the mode
(vmapped, sequential, or a mesh and its layout) and the shapes and dtypes
of its arguments.  A hit skips trace, lowering and compile: same-shape
datasets of one spec share a program, and later sweeps of the same shapes
build nothing.  ``JIT_CALLS`` counts the misses;
``repro_engine_program_cache_total{outcome}`` counts both.

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
tests compare against, for every algorithm alike.  The per-m `run_*`
functions stay as the tests' oracles (Hogwild!'s is the original
staleness recurrence), outside the engine.

**Seed axis** (ENGINE_VERSION 4): ``n_seeds > 1`` replicates every job
over independent draw sequences — `Algorithm.make_draws` is called once
per seed (seed 0 with the caller's key, bit-identical to the
ENGINE_VERSION-3 single-seed run; seed s with ``fold_in(key, s)``), the
per-seed draws are stacked, and the per-m simulation is ``jax.vmap``-ed
over that stacked axis *inside* ``sim``.  The m-grid vmap then wraps
the seed vmap, so the whole (seeds x m) grid is still ONE trace and ONE
compile per bucket signature — no per-seed recompiles (`JIT_CALLS`
counts them).  Results keep ``losses``
as the seed-0 rows (every legacy consumer unchanged) and add
``losses_seeds`` — the full (S, n_seeds, n_evals) block `repro.analysis.
stats` turns into mean/CI curves and bootstrap m_max distributions.

**Device-mesh sharding** (ENGINE_VERSION 5): ``mesh=`` hands each
bucket's batched simulation to `repro.distributed.partition`.  When the
seeds divide evenly over the devices, each device runs this module's
bucket program (members vmapped over the seed-vmapped simulation) on its
own seeds under one ``shard_map``; otherwise the (members x seeds) cells
are flattened into one element axis, padded to the device count, and
ONE jitted vmap follows the element indices laid over the mesh.  Because
the cells are independent, results are **mesh-invariant** (1e-5 contract,
tests/test_distributed.py) and cache fingerprints exclude the mesh
entirely.  ``mesh=None`` (every existing caller) and single-device
meshes take the exact unsharded path below — the single-device fallback
is bit-exact with ENGINE_VERSION 4.  The sequential reference path
(``use_vmap=False``) never shards.
"""

from __future__ import annotations

import collections
import threading
import types
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import problems as problems_mod
from repro.core.algorithms import base as alg_base
from repro.data import synth
from repro.distributed import mesh as dist_mesh
from repro.distributed import partition as dist_partition
from repro.telemetry import instrument, metrics, recorder, trace

#: Pad-waste bound for `_buckets`: within a bucket, the padded worker axis
#: is at most this multiple of the smallest member.
MAX_PAD_RATIO = 2.0

#: Most jitted programs `_program` keeps per process, least recently used
#: dropped first.  One ``ls`` sweep builds 6, a Table II sweep about 20.
PROGRAM_CACHE_SIZE = 64

#: signature -> jitted program (`_program`); the service sweeps from
#: several threads, so every access holds the lock
_PROGRAMS: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()

#: Counts the `jax.jit` wrappers the engine builds — program-cache misses;
#: each one is traced and compiled exactly once, so this is the engine's
#: compile count.  Registry-backed (PR 9): increments are locked so the
#: multi-threaded service counts exactly; the module-level ``JIT_CALLS``
#: read (the benchmark, tests) stays source-compatible via ``__getattr__``
#: below.
_JIT_CALLS = metrics.counter(
    "repro_engine_jit_compiles_total",
    help="jax.jit wrappers dispatched by the engine (one XLA compile each)")

#: One increment per program lookup: ``hit`` reuses a kept program (no
#: trace, lowering or compile), ``miss`` builds one.
_PROGRAM_CACHE = {
    outcome: metrics.counter(
        "repro_engine_program_cache_total", labels={"outcome": outcome},
        help="engine program lookups, by whether a kept program served")
    for outcome in ("hit", "miss")}

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


def _plan(ms: Sequence[int], bucketed: bool
          ) -> List[Tuple[Tuple[int, ...], int]]:
    """The grid's buckets — `_buckets` when ``bucketed``, else one flat
    bucket at ``max(ms)`` — with the grid's pad waste recorded."""
    buckets = (_buckets(ms) if bucketed
               else [(tuple(range(len(ms))), max(ms))])
    total = sum(m_pad * len(pos) for pos, m_pad in buckets)
    if total:
        waste = 1.0 - sum(ms) / total
        _PAD_WASTE.set(waste)
        recorder.publish("grid", members=len(ms), pad_waste=round(waste, 4))
    return buckets


def runs_sharded(dmesh: Optional["dist_mesh.DeviceMesh"],
                 use_vmap: bool) -> bool:
    """Whether `sweep` shards its grid over the resolved mesh ``dmesh``:
    only the vmapped path, and only over more than one device."""
    return dmesh is not None and dmesh.n_devices > 1 and use_vmap


def _simulation(alg, prob, m_pad: int, eval_every: int, n_evals: int):
    """``sim(m, data, sub) -> (n_evals,)``: ``alg`` on ``prob`` at pad
    width ``m_pad`` for live worker count ``m``, over ``data = (X, y, Xte,
    yte)`` and the draws ``sub`` sliced to ``m_pad``.  It closes over
    static values only, so one program serves every dataset and draw of
    the same shapes."""
    def sim(m, data, sub):
        X, y, Xte, yte = data
        train = synth.Dataset(X, y)
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
            return state, prob.test_loss(alg.readout(ctx, state), Xte, yte)

        _, losses = jax.lax.scan(outer, state0, jnp.arange(n_evals))
        return losses

    return sim


def _seeded(sim, n_seeds: int):
    """``sim`` over draws stacked on a leading seed axis when ``n_seeds >
    1``: the per-seed simulation vmapped inside, so the m-grid vmap wraps
    it and the whole (seeds x m) block is one program."""
    if n_seeds == 1:
        return sim

    def sim_seeded(m, data, stacked):
        return jax.vmap(lambda sub: sim(m, data, sub))(stacked)

    return sim_seeded


def _signature(tree):
    """What JAX specializes a program on besides its static closure: the
    tree structure and each leaf's shape, dtype and weak type, plus the
    default device and matmul precision in effect."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef, tuple(jax.typeof(x) for x in leaves),
            jax.config.jax_default_device,
            jax.config.jax_default_matmul_precision)


def _code(*objs) -> tuple:
    """The functions a program built from ``objs`` calls, as they resolve
    now: each function on each object's class, and each module-level
    callable that their code (nested functions included) names.  A method
    or function replaced at run time — a patched method, a planted fault —
    thus keys a new program instead of reusing one traced from the old
    code."""
    found = []

    def named(code, glb):
        for name in code.co_names:
            value = glb.get(name)
            if callable(value):
                found.append(value)
        for const in code.co_consts:
            if isinstance(const, types.CodeType):
                named(const, glb)

    for obj in objs:
        cls = type(obj)
        for attr in dir(cls):
            fn = getattr(cls, attr)
            if isinstance(fn, types.FunctionType):
                found.append(fn)
                named(fn.__code__, fn.__globals__)
    return tuple(found)


def _program(key, build):
    """The jitted program for ``key``, kept per process; ``build()``
    returns the function to jit on a miss.  Returns ``(program,
    cached)``."""
    try:
        hash(key)
    except TypeError:         # an array-valued hyperparameter: not kept
        _PROGRAM_CACHE["miss"].inc()
        return _jit(build()), False
    with _PROGRAMS_LOCK:
        program = _PROGRAMS.get(key)
        if program is not None:
            _PROGRAMS.move_to_end(key)
            _PROGRAM_CACHE["hit"].inc()
            return program, True
        _PROGRAM_CACHE["miss"].inc()
        program = _PROGRAMS[key] = _jit(build())
        if len(_PROGRAMS) > PROGRAM_CACHE_SIZE:
            _PROGRAMS.popitem(last=False)
        return program, False


def clear_programs() -> None:
    """Drop every kept program, so the next sweep builds cold (tests)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def _run_grid(bucket, ms, use_vmap: bool, bucketed: bool, algorithm: str):
    """Run the grid's simulations; rows follow ``ms`` order.

    ``bucket(m_pad, batched)`` must return ``(program, args, cached)``: a
    jitted ``program(m, *args) -> (n_evals,)`` (with ``batched``, vmapped
    over a vector of m) that is numerically independent of ``m_pad`` for
    any ``m <= m_pad`` (shared draws sliced, reductions masked) — that
    contract is what makes the three execution modes here interchangeable
    — the arrays it takes, whose bytes the ``bucket`` span reports as
    ``arg_bytes``, and whether the program was already built.
    """
    buckets = _plan(ms, bucketed and use_vmap)
    if not use_vmap:
        [(_, m_top)] = buckets
        jsim, args, _ = bucket(m_top, False)   # one program serves every m
        return jnp.stack([
            instrument.dispatch(jsim, m, *args, span_name="grid_member",
                                m=int(m), m_pad=m_top)
            for m in jnp.asarray(ms, jnp.int32)])
    rows = [None] * len(ms)
    for pos, m_pad in buckets:
        program, args, cached = bucket(m_pad, True)
        out = instrument.dispatch(
            program, jnp.asarray([ms[i] for i in pos], jnp.int32), *args,
            span_name="bucket", algorithm=algorithm, m_pad=m_pad,
            members=len(pos), cached=cached,
            arg_bytes=instrument.nbytes(args))
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

    # the dataset arrays every simulation takes (besides its draws)
    data = (train.X, train.y, Xte, yte)

    def operands(m_pad, stack):
        subs = [alg.slice_draws(d, m_pad) for d in draws_by_seed]
        if not stack:
            return data, subs[0]          # the exact ENGINE_VERSION-3 path
        return data, jax.tree.map(lambda *xs: jnp.stack(xs), *subs)

    code = _code(alg, prob)

    def signature(mode, m_pad, args):
        return (mode, alg, prob, code, m_pad, iters, eval_every, n_seeds,
                _signature(args))

    def bucket(m_pad, batched):
        args = operands(m_pad, n_seeds > 1)

        def build():
            sim = _seeded(_simulation(alg, prob, m_pad, eval_every, n_evals),
                          n_seeds)
            if not batched:
                return sim
            # vmapped over the bucket's m vector; data and draws are
            # shared by every member
            return instrument.named(jax.vmap(sim, in_axes=(0, None, None)),
                                    f"bucket_{alg.name}_m{m_pad}")

        program, cached = _program(
            signature("vmap" if batched else "sequential", m_pad, args),
            build)
        return program, args, cached

    def make_sim(m_pad):
        # distributed twin of `bucket`: the per-seed simulation and its
        # operands, the draws always stacked on their seed axis, which
        # the partitioner splits over the mesh or indexes per element
        return (_simulation(alg, prob, m_pad, eval_every, n_evals),
                *operands(m_pad, True))

    if bucketed is None:
        bucketed = alg.bucketed_default
    if alg.force_flat:
        bucketed = False
    dmesh = dist_mesh.resolve(mesh)
    with trace.span("grid", algorithm=alg.name, problem=prob.name,
                    members=len(ms), n_seeds=n_seeds):
        if runs_sharded(dmesh, use_vmap):
            losses = dist_partition.run_grid_sharded(
                make_sim, ms, n_seeds, dmesh, _plan(ms, bucketed),
                program=lambda m_pad, layout, args, build: _program(
                    signature(("mesh", dmesh.devices, layout), m_pad, args),
                    build),
                algorithm=alg.name)
        else:
            losses = _run_grid(bucket, ms, use_vmap, bucketed, alg.name)
        return _losses_dict(alg.name, ms, losses, iters, eval_every,
                            problem=prob.name, n_seeds=n_seeds)

