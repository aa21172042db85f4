"""Hogwild! (Alg 1) under the Perfect Computer Assumption.

TPU/SPMD adaptation (DESIGN.md §6): the x86 lock-free shared-memory race is
simulated *deterministically* — the gradient applied at server iteration j
was computed against the model at iteration j - tau, with tau cycling over
[1, m] (Thm 1: with m equal workers the lag is exactly the worker count).
Convergence behaviour depends only on tau_max (Thm 2), so the insight
survives the mechanism swap.

The staleness recurrence is *padding-safe*: :func:`masked_sim` allocates the
model history at a static pad width ``m_pad`` and takes every history index
modulo a **traced** worker count m, so shapes never depend on m — only
indices do, and those stay in ``[0, m)``.  Rows ``>= m`` of the history are
never read or written, which makes the padded run numerically the m-worker
run.  That is what lets `repro.experiments.engine` sweep the whole m-grid
as one ``jax.vmap`` (one trace, one compile) instead of re-jitting per m.

Under the PCA, wall-time for m workers = t_single / m * n_iterations, so the
figures report iterations (server) and iterations-per-worker (= cost).

Since ENGINE_VERSION 5 this sequential recurrence is also the **parity
oracle** for the true multi-device racing mode
(`repro.distributed.hogwild_shards`): there the worker set is split into
per-device shards under ``shard_map`` and the shards genuinely race on a
donated shared parameter, reconciling their deltas every sync round.
The oracle stays the cached, mesh-invariant default the engine sweeps;
the race is the hardware-validation mode (:func:`run_hogwild_sharded`
delegates; divergence regimes are documented in docs/distributed.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp

from repro.core.algorithms.base import (Algorithm, SimContext,
                                        register_algorithm)
from repro.core.algorithms.lr import lr_grad, test_logloss, LAMBDA
from repro.resilience import faults


@register_algorithm
@dataclasses.dataclass(frozen=True)
class Hogwild(Algorithm):
    """Protocol port of the traced-m staleness recurrence below: the model
    history lives at the static pad width, every history index is taken
    modulo the traced m, and the sample sequence is m-independent — so the
    engine sweeps the whole grid as ONE flat vmap (``force_flat``: the
    recurrence updates a single model, work is O(iters * d) regardless of
    the pad width, so bucketing would only add compiles).

    ``fault`` (a `repro.resilience.faults.FaultSpec` or its dict form)
    injects update-delivery faults into the recurrence: a straggle event
    deepens the staleness (``tau + straggle_rounds``, clamped to the
    m-deep history), drop/duplicate scale the landing gradient by 0 / 2,
    corruption rewrites it — all as traced transforms on an ``(iters,)``
    event stream drawn once from the fault seed, so faulted sweeps vmap
    and bucket exactly like unfaulted ones.  Zero-rate specs are
    bit-exact with ``fault=None``.
    """

    name: ClassVar[str] = "hogwild"
    asynchronous: ClassVar[bool] = True      # cost divides iters by m
    bucketed_default: ClassVar[bool] = False
    force_flat: ClassVar[bool] = True
    predictor: ClassVar[str] = "hogwild"

    gamma: float = 0.1
    fault: Optional[faults.FaultSpec] = None

    def __post_init__(self):
        object.__setattr__(self, "fault", faults.resolve(self.fault))

    def make_draws(self, key, n, iters, m_top):
        # identical draw to run_hogwild's: the sequence is m-independent.
        # The fault stream is keyed from the FAULT seed, not the sweep
        # key: faults are environment — every seed replicate faces the
        # same schedule, so the seed axis keeps measuring sampling noise.
        order = jax.random.randint(key, (iters,), 0, n)
        if self.fault is None:
            return order
        return {"i": order,
                "fault": faults.make_stream(self.fault, (iters,))}

    def init_state(self, problem, data, ctx: SimContext):
        d = data.X.shape[1]
        return (jnp.zeros((d,)), jnp.zeros((ctx.m_pad, d)))

    def step(self, problem, data, ctx: SimContext, state, batch, j):
        x, hist = state
        i = batch if self.fault is None else batch["i"]
        # stale model: the one from j - tau, tau = (j % m) + 1 (Thm 1)
        tau = (j % ctx.m) + 1
        if self.fault is not None:
            # straggler: the read is extra rounds stale, clamped to the
            # m-deep history (identity when the event did not fire)
            tau = jnp.minimum(
                tau + faults.extra_staleness(self.fault, batch["fault"]),
                ctx.m)
        x_stale = hist[(j - tau) % ctx.m]
        g = problem.point_grad(x_stale, data.X[i], data.y[i])
        if self.fault is not None:
            g = faults.corrupt(self.fault, g, batch["fault"]["corrupt"])
            g = faults.delivery_scale(batch["fault"]) * g
        x_new = x - self.gamma * g
        return (x_new, hist.at[j % ctx.m].set(x_new))

    def readout(self, ctx: SimContext, state):
        return state[0]


def masked_sim(X, y, Xte, yte, order, *, m_pad, gamma, lam, eval_every,
               n_evals):
    """Build ``sim(m) -> (x, losses)`` with the worker count m as traced data.

    ``m_pad`` is the only shape parameter (history rows); any ``m <= m_pad``
    runs bit-identically to a ``m_pad == m`` allocation because the
    recurrence indexes ``hist`` modulo m.  ``order`` is the shared
    ``(iters,)`` server sample sequence — it is m-independent, so every
    sweep member consumes the same draws.
    """
    d = X.shape[1]

    def sim(m):
        m = jnp.asarray(m, jnp.int32)

        def step(carry, j):
            x, hist = carry                   # hist: (m_pad, d) past models
            # stale model: the one from j - tau, tau = (j % m) + 1
            tau = (j % m) + 1
            x_stale = hist[(j - tau) % m]
            i = order[j]
            g = lr_grad(x_stale, X[i], y[i], lam)
            x_new = x - gamma * g
            hist = hist.at[j % m].set(x_new)
            return (x_new, hist), None

        def outer(carry, e):
            carry, _ = jax.lax.scan(
                step, carry, e * eval_every + jnp.arange(eval_every))
            return carry, test_logloss(carry[0], Xte, yte)

        carry0 = (jnp.zeros((d,)), jnp.zeros((m_pad, d)))
        (x, _), losses = jax.lax.scan(outer, carry0, jnp.arange(n_evals))
        return x, losses

    return sim


@functools.partial(jax.jit, static_argnames=("m_pad", "iters", "eval_every"))
def _run(X, y, Xte, yte, key, m, gamma, lam, *, m_pad, iters, eval_every):
    n = X.shape[0]
    order = jax.random.randint(key, (iters,), 0, n)
    sim = masked_sim(X, y, Xte, yte, order, m_pad=m_pad, gamma=gamma,
                     lam=lam, eval_every=eval_every,
                     n_evals=iters // eval_every)
    return sim(m)


def run_hogwild(train, test, *, m=4, iters=4000, gamma=0.1, lam=LAMBDA,
                eval_every=100, key=None):
    """Returns dict with the convergence curve (server-iteration indexed).

    Thin single-m wrapper over :func:`masked_sim` (padded exactly to m),
    kept as the oracle the engine's grid is tested against; sweeps over
    many m go through `engine.sweep("hogwild", ...)`, which vmaps the
    same recurrence over the whole grid in one compile.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    x, losses = _run(train.X, train.y, test.X, test.y, key, m, gamma, lam,
                     m_pad=m, iters=iters, eval_every=eval_every)
    return {
        "algorithm": "hogwild",
        "m": m,
        "iters": iters,
        "eval_every": eval_every,
        "losses": jax.device_get(losses),
        "x": x,
        "iters_per_worker": iters / m,
    }


def run_hogwild_sharded(train, test, **kwargs):
    """The real race: worker shards on a device mesh updating a donated
    shared parameter (lazy delegate to `repro.distributed.hogwild_shards`
    — `repro.core` stays importable without the distributed package's
    mesh machinery; this recurrence here remains its parity oracle)."""
    from repro.distributed.hogwild_shards import run_hogwild_sharded as fn
    return fn(train, test, **kwargs)
