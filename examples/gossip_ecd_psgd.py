"""ECD-PSGD as a production exchange strategy: per-shard model replicas on
an 8-device debug mesh (4 data x 2 model), ring collective_permute of
stochastically-quantized extrapolation variables (paper Alg 4 on ICI).

  PYTHONPATH=src python examples/gossip_ecd_psgd.py
"""
import os
# eight forced host CPU devices; JAX_PLATFORMS=cpu keeps this process off
# any accelerator (a chip belongs to one process at a time)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.configs.registry import get_arch
from repro.distributed import make_debug_mesh
from repro.train.steps import make_gossip_step, init_gossip_state

mesh = make_debug_mesh(data=4, model=2)
cfg = get_arch("gemma3-1b").reduced()
make, R = make_gossip_step(cfg, mesh, lr=2e-3, compress_bits=8)
key = jax.random.PRNGKey(0)
state = init_gossip_state(key, cfg, R)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
step_fn, st_specs, b_specs = make(jax.eval_shape(lambda: state), jax.eval_shape(lambda: batch))
from jax.sharding import NamedSharding
import jax.tree_util as jtu
with mesh:
    jstep = jax.jit(step_fn)
    losses = []
    for i in range(8):
        state, metrics = jstep(state, batch)
        losses.append(float(metrics["loss"]))
print("gossip losses:", [round(x,4) for x in losses])
assert losses[-1] < losses[0], "gossip should descend on a fixed batch"
# replicas should agree approximately after ring averaging rounds
p0 = jax.tree.leaves(state["params"])[3]
spread = float(jnp.max(jnp.abs(p0 - p0.mean(0, keepdims=True))))
print("replica spread:", spread, "OK")
