"""Serving launcher: batched greedy decoding against a KV cache (reduced
configs execute on CPU; full configs belong to dryrun.py).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --requests 4 \
      --prompt-len 16 --gen 24
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import runtime
from repro.configs.registry import ARCH_IDS, get_arch
from repro.models import model as M
from repro.serve.engine import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3-1b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--reduced", action="store_true", default=True)
    args = ap.parse_args()
    runtime.enable_compile_cache()

    cfg = get_arch(args.arch).reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    prompts = jax.random.randint(key, (args.requests, args.prompt_len),
                                 0, cfg.vocab_size)
    enc = None
    if cfg.encoder_layers:
        frames = 0.1 * jax.random.normal(
            key, (args.requests, cfg.encoder_seq, cfg.d_model))
        enc = M.encode(params["encoder"], cfg, frames)
    t0 = time.time()
    out = greedy_generate(params, cfg, prompts, args.gen, enc_out=enc)
    dt = time.time() - t0
    total = args.requests * args.gen
    print(f"arch={cfg.name} generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, batch={args.requests})")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
