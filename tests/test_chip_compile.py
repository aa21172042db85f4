"""Compile each Pallas kernel of the main path for a described TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (tiles not
aligned to the layout, too much fast memory).  These tests compile every
kernel at the shapes the sweep engine and the gemma3-1b model use, for a
v5e chip that is described, not attached, and check that the kernel is
really in the program (``tpu_custom_call``).  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import csim, flash_attention, quantize, rmsnorm


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2``, with the persistent compile
    cache off (a program compiled for a described chip is written to the
    cache but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _arg(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(s, batch, seq):
    # gemma3-1b attention: 4 query heads, 1 KV head, head_dim 256
    return flash_attention.flash_attention_bhsd.lower(
        _arg(s, (batch, 4, seq, 256), jnp.bfloat16),
        _arg(s, (batch, 1, seq, 256), jnp.bfloat16),
        _arg(s, (batch, 1, seq, 256), jnp.bfloat16))


#: kernel -> lowering at a main-path shape: the upper_bound spec's datasets
#: (ub 4000x400, dense 4000x28, sparse8 1000x300), ECD-PSGD's gradient
#: (1x28) and gemma3-1b's widths (d_model 1152, attention heads)
LOWERINGS = {
    "l0_rows-4000x400": lambda s: csim.l0_rows.lower(
        _arg(s, (4000, 400)), _arg(s, (4000, 400))),
    "l0_rows-4000x28": lambda s: csim.l0_rows.lower(
        _arg(s, (4000, 28)), _arg(s, (4000, 28))),
    "csim_kernel-4000x400-range8": lambda s: csim.csim_kernel.lower(
        _arg(s, (4000, 400)), 8),
    "quantize-4000x28": lambda s: quantize.quantize_stochastic_2d.lower(
        _arg(s, (4000, 28)), _arg(s, (2,), jnp.uint32)),
    "quantize-1x28": lambda s: quantize.quantize_stochastic_2d.lower(
        _arg(s, (1, 28)), _arg(s, (2,), jnp.uint32)),
    "dequantize-1000x300-int8": lambda s: quantize.dequantize_2d.lower(
        _arg(s, (1000, 300), jnp.int8), _arg(s, ())),
    "rmsnorm-512x1152-bf16": lambda s: rmsnorm.rmsnorm_2d.lower(
        _arg(s, (512, 1152), jnp.bfloat16), _arg(s, (1152,), jnp.bfloat16)),
    "flash_attention-gemma3-s64": lambda s: _flash(s, 8, 64),
    "flash_attention-gemma3-s4096": lambda s: _flash(s, 1, 4096),
}


@pytest.mark.parametrize("name", sorted(LOWERINGS))
def test_kernel_compiles_for_v5e(name, one_chip):
    compiled = LOWERINGS[name](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
