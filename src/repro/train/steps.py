"""Sharded train-step factories — one per paper exchange strategy.

  sync    mini-batch SGD/AdamW (Alg 2): global loss mean => implicit gradient
          all-reduce over ('pod','data'); FSDP param layout.
  stale   Hogwild!'s insight (Alg 1) adapted to SPMD (DESIGN.md §6): the
          update applied at step t uses the gradient computed at step t-1
          (tau=1 staleness), overlapping gradient compute with exchange.
  gossip  ECD-PSGD (Alg 4): per-data-shard model replicas, ring
          collective_permute of *compressed* (stochastically quantized)
          neighbor models + extrapolation variables.  Pure-DP layout
          (replicated per shard) — used for the small/medium archs.
  (DADM, Alg 3, needs a convex conjugate pair; it lives in
   repro.core.algorithms and repro.train.convex for LR-scale models.)
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.optim import adamw_init, adamw_update
from repro.distributed import (act_constraint, batch_specs, data_axes,
                               head_constraint, inner_act_constraint,
                               layer_constraint, logits_constraint,
                               param_specs)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def init_train_state(key, cfg: ArchConfig, strategy="sync"):
    params = M.init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if strategy == "stale":
        state["prev_grads"] = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), params)
    return state


def train_state_specs(state_shapes, mesh):
    pspecs = param_specs(state_shapes["params"], mesh)
    specs = {"params": pspecs,
             "opt": {"m": pspecs, "v": pspecs, "count": P()},
             "step": P()}
    if "prev_grads" in state_shapes:
        specs["prev_grads"] = pspecs
    return specs


# ---------------------------------------------------------------------------
# sync / stale steps (FSDP layout, plain jit)
# ---------------------------------------------------------------------------

def _split_microbatches(batch, m):
    """(B, ...) -> (m, B/m, ...); M-RoPE positions (3,B,S) split on axis 1."""
    def f(path, leaf):
        name = getattr(path[-1], "key", None)
        if name == "positions":
            return leaf.reshape(leaf.shape[0], m, -1, *leaf.shape[2:]
                                ).transpose(1, 0, *range(2, leaf.ndim + 1))
        return leaf.reshape(m, -1, *leaf.shape[1:])
    return jax.tree_util.tree_map_with_path(f, batch)


def make_train_step(cfg: ArchConfig, mesh, *, strategy="sync", lr=3e-4,
                    remat=True, attention_impl="reference", seq_shard=True,
                    grad_shard=True, microbatches=1,
                    grad_accum_dtype=jnp.float32, accum_mode="explicit"):
    constrain = act_constraint(mesh, seq_shard=seq_shard)
    c_inner = inner_act_constraint(mesh, seq_shard=seq_shard, cfg=cfg)
    c_layer = layer_constraint(mesh) if grad_shard else None
    c_logits = logits_constraint(mesh) if grad_shard else None
    c_head = head_constraint(mesh) if grad_shard else None

    def loss(params, batch):
        return M.loss_fn(params, cfg, batch, remat=remat,
                         attention_impl=attention_impl, constrain=constrain,
                         constrain_layer=c_layer, constrain_logits=c_logits,
                         constrain_inner=c_inner, constrain_head=c_head)

    def _constrain_grads(params, grads):
        # pin gradients to the FSDP param layout so XLA lowers the gradient
        # reduction as reduce-scatter instead of all-reduce + slice
        if not grad_shard:
            return grads
        specs = param_specs(params, mesh)
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)), grads, specs,
            is_leaf=lambda x: isinstance(x, P))

    def grads_of(params, batch):
        """Gradient of the mean loss, microbatched (grad accumulation).

        accum_mode "in-loss": the microbatch scan lives INSIDE the
        differentiated function, so the parameter cotangent accumulates in
        the backward while-loop instead of re-realizing (and re-reducing)
        a full gradient per microbatch — measured 4x collective-byte saving
        at microbatches=8 on qwen110b (EXPERIMENTS.md §Perf).
        """
        if accum_mode == "in-loss" and microbatches > 1:
            mb = _split_microbatches(batch, microbatches)

            def total_loss(p):
                def body(acc, one):
                    l, aux = loss(p, one)
                    return acc + l, aux
                tot, auxs = jax.lax.scan(
                    body, jnp.zeros((), jnp.float32), mb)
                return tot / microbatches, jax.tree.map(lambda x: x[-1], auxs)

            (l, aux), grads = jax.value_and_grad(
                total_loss, has_aux=True)(params)
            return l, aux, _constrain_grads(params, grads)
        if microbatches <= 1:
            (l, aux), grads = jax.value_and_grad(loss, has_aux=True)(
                params, batch)
            return l, aux, _constrain_grads(params, grads)
        mb = _split_microbatches(batch, microbatches)

        def body(acc, one):
            (l, aux), g = jax.value_and_grad(loss, has_aux=True)(params, one)
            g = _constrain_grads(params, g)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(
                lambda a, x: a + x.astype(grad_accum_dtype), acc_g, g)
            acc_g = _constrain_grads(params, acc_g)
            return (acc_g, acc_l + l), aux

        zero = jax.tree.map(
            lambda x: jnp.zeros(x.shape, grad_accum_dtype), params)
        zero = _constrain_grads(params, zero)
        (g_sum, l_sum), auxs = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)), mb)
        grads = jax.tree.map(lambda x: (x / microbatches), g_sum)
        aux = jax.tree.map(lambda x: x[-1], auxs)
        return l_sum / microbatches, aux, grads

    def sync_step(state, batch):
        l, aux, grads = grads_of(state["params"], batch)
        new_params, new_opt = adamw_update(state["params"], grads,
                                           state["opt"], lr=lr)
        metrics = {"loss": l, "ce_loss": aux["ce_loss"],
                   "grad_norm": _global_norm(grads)}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    def stale_step(state, batch):
        # apply last step's gradient while computing this step's
        l, aux, grads = grads_of(state["params"], batch)
        new_params, new_opt = adamw_update(
            state["params"], state["prev_grads"], state["opt"], lr=lr)
        metrics = {"loss": l, "ce_loss": aux["ce_loss"],
                   "grad_norm": _global_norm(grads)}
        prev = jax.tree.map(lambda g, pp: g.astype(pp.dtype), grads,
                            state["params"])
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1, "prev_grads": prev}, metrics

    step = {"sync": sync_step, "stale": stale_step}[strategy]
    return step


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# gossip (ECD-PSGD) step — per-shard replicas via shard_map
# ---------------------------------------------------------------------------

def make_gossip_step(cfg: ArchConfig, mesh, *, lr=3e-4, compress_bits=8,
                     remat=False, attention_impl="reference"):
    """ECD-PSGD on the data axes: per-shard model replicas (leading axis R,
    sharded over 'data'), ring collective_permute of *compressed* neighbor
    extrapolation variables.  Returns (shard_map-wrapped step, state_specs).

    Use via ``init_gossip_state`` + the returned jit-able step:
        step(state, batch) -> (state, metrics)
    """
    from jax.sharding import PartitionSpec

    from repro.core.compression import dequantize, quantize_stochastic

    fd = data_axes(mesh)
    axis_names = (fd if isinstance(fd, tuple) else (fd,))
    R = 1
    for a in axis_names:
        R *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]

    def loss(params, batch):
        return M.loss_fn(params, cfg, batch, remat=remat,
                         attention_impl=attention_impl)

    def local_step(state, batch):
        # leading replica axis has local size 1 inside shard_map
        params = jax.tree.map(lambda x: x[0], state["params"])
        y_var = jax.tree.map(lambda x: x[0], state["y"])
        t = state["step"].astype(jnp.float32) + 2.0
        idx = jax.lax.axis_index(axis_names[0])
        for a in axis_names[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(17), state["step"]), idx)

        (l, _), grads = jax.value_and_grad(loss, has_aux=True)(params, batch)

        def ring_avg(leaf):
            total = leaf.astype(jnp.float32)
            n = 1
            for ax in axis_names:
                size = jax.lax.axis_size(ax)
                fwd = [(i, (i + 1) % size) for i in range(size)]
                bwd = [(i, (i - 1) % size) for i in range(size)]
                total = total + jax.lax.ppermute(leaf, ax, fwd).astype(jnp.float32)
                total = total + jax.lax.ppermute(leaf, ax, bwd).astype(jnp.float32)
                n += 2
            return (total / n).astype(leaf.dtype)

        # pull compressed neighbor y (Alg 4 step 3): x_{t+1/2} = sum W_ij y_j
        y_comp = jax.tree.map(
            lambda v, k: dequantize(*quantize_stochastic(
                v, k, bits=compress_bits)).astype(v.dtype),
            y_var, _key_tree(key, y_var))
        x_half = jax.tree.map(ring_avg, y_comp)
        new_params = jax.tree.map(
            lambda xh, g: (xh.astype(jnp.float32)
                           - lr * g.astype(jnp.float32)).astype(xh.dtype),
            x_half, grads)

        # extrapolate + compress (Alg 4 steps 4-5)
        def extrap(x_old, x_new, y_old, k):
            z = (1.0 - t / 2.0) * x_old.astype(jnp.float32) \
                + (t / 2.0) * x_new.astype(jnp.float32)
            cz = dequantize(*quantize_stochastic(z, k, bits=compress_bits))
            return ((1.0 - 2.0 / t) * y_old.astype(jnp.float32)
                    + (2.0 / t) * cz).astype(y_old.dtype)

        new_y = jax.tree.map(extrap, params, new_params, y_var,
                             _key_tree(jax.random.fold_in(key, 1), y_var))
        l_avg = l
        for a in axis_names:
            l_avg = jax.lax.pmean(l_avg, a)
        return ({"params": jax.tree.map(lambda x: x[None], new_params),
                 "y": jax.tree.map(lambda x: x[None], new_y),
                 "step": state["step"] + 1},
                {"loss": l_avg})

    p_stack = PartitionSpec(fd)
    state_specs = {"params": None, "y": None, "step": PartitionSpec()}

    def specs_like(tree):
        return jax.tree.map(lambda _: p_stack, tree)

    def make(state_shapes, batch_shapes):
        st_specs = {"params": specs_like(state_shapes["params"]),
                    "y": specs_like(state_shapes["y"]),
                    "step": PartitionSpec()}
        b_specs = jax.tree.map(
            lambda x: PartitionSpec(fd, *([None] * (x.ndim - 1))),
            batch_shapes)
        step = jax.shard_map(local_step, mesh=mesh,
                             in_specs=(st_specs, b_specs),
                             out_specs=(st_specs, {"loss": PartitionSpec()}),
                             check_vma=False)
        return step, st_specs, b_specs

    return make, R


def init_gossip_state(key, cfg: ArchConfig, n_replicas):
    params = M.init_params(key, cfg)
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_replicas,) + x.shape), params)
    return {"params": stack,
            "y": jax.tree.map(jnp.copy, stack),
            "step": jnp.zeros((), jnp.int32)}


def _key_tree(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))
