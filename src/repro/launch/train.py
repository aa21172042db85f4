"""Training launcher.

CPU-scale real runs (reduced configs, actual optimization):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
      --steps 50 --strategy sync

Production lowering (full config, mesh, no execution) is dryrun.py's job —
this launcher EXECUTES.  It defaults to the reduced configs; --full runs
the published widths, which needs a TPU (gemma3-1b at batch 8 x 64 fits
one v5e chip) and warns elsewhere.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

from repro import runtime
from repro.configs.registry import ARCH_IDS, get_arch
from repro.core.advisor import ScalabilityAdvisor
from repro.data.lm import LMConfig, hmm_stream, token_characters
from repro.models import model as M
from repro.optim import adamw_init, adamw_update
from repro.train.checkpoint import save_checkpoint


def train_loop(cfg, *, steps=50, batch_size=8, seq_len=64, lr=1e-3,
               strategy="sync", log_every=10, ckpt=None, advisor_every=0,
               lb_coef=0.01, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    opt = adamw_init(params)
    prev_grads = (jax.tree.map(jnp.zeros_like, params)
                  if strategy == "stale" else None)

    def loss_fn(p, batch):
        return M.loss_fn(p, cfg, batch, lb_coef=lb_coef)

    # params and optimizer state are donated: updated in place, the
    # full-width step fits one chip (no second copy of either)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def sync_step(p, opt, batch):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        p, opt = adamw_update(p, g, opt, lr=lr)
        return p, opt, l

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def stale_step(p, opt, prev_g, batch):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        p, opt = adamw_update(p, prev_g, opt, lr=lr)
        return p, opt, l, g

    lm = LMConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                  batch_size=batch_size)
    stream = hmm_stream(key, lm, steps)
    adv = ScalabilityAdvisor()
    history = []
    t0 = time.time()
    for step, batch in enumerate(stream):
        if cfg.vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (batch_size, cfg.vision_tokens, cfg.d_model), cfg.dtype)
        if cfg.encoder_layers:
            batch["frames"] = jnp.zeros(
                (batch_size, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        if strategy == "stale":
            params, opt, l, g = stale_step(params, opt, prev_grads, batch)
            prev_grads = g
        else:
            params, opt, l = sync_step(params, opt, batch)
        history.append(float(l))
        if step % log_every == 0:
            msg = f"step {step:4d} loss {float(l):.4f}"
            if advisor_every and step and step % advisor_every == 0:
                # split the batch in two shards and probe gradient characters
                half = batch_size // 2
                b1 = {k: v[:half] if v.shape[0] != 3 else v[:, :half]
                      for k, v in batch.items()}
                b2 = {k: v[half:] if v.shape[0] != 3 else v[:, half:]
                      for k, v in batch.items()}
                g1 = jax.grad(lambda p: loss_fn(p, b1)[0])(params)
                g2 = jax.grad(lambda p: loss_fn(p, b2)[0])(params)
                rep = adv.from_grads([g1, g2])
                msg += (f" | advisor: noise={rep['grad_noise_scale']:.3f} "
                        f"m_max_sync~{rep['predicted_m_max_sync']}")
            ch = token_characters(batch["tokens"])
            msg += f" | div={ch['sequence_diversity']:.2f}"
            print(msg)
    dt = time.time() - t0
    print(f"trained {steps} steps in {dt:.1f}s "
          f"({steps / dt:.2f} it/s), loss {history[0]:.3f} -> {history[-1]:.3f}")
    if ckpt:
        save_checkpoint(ckpt, {"params": params}, step=steps)
        print(f"checkpoint -> {ckpt}")
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--strategy", default="sync", choices=["sync", "stale"])
    ap.add_argument("--ckpt")
    ap.add_argument("--advisor-every", type=int, default=0)
    ap.add_argument("--json")
    args = ap.parse_args()
    runtime.enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    elif jax.default_backend() != "tpu":
        print("WARNING: --full on a non-TPU backend will be slow/OOM")
    _, history = train_loop(cfg, steps=args.steps, batch_size=args.batch_size,
                            seq_len=args.seq_len, lr=args.lr,
                            strategy=args.strategy, ckpt=args.ckpt,
                            advisor_every=args.advisor_every)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"arch": args.arch, "history": history}, f)


if __name__ == "__main__":
    main()
