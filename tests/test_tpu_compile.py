"""Compiles for a described TPU v5e: the main path's kernels at real widths.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(tiling, fast-memory limits, programs that do not fit). The topology is
described inside a module fixture, never at import, so every test worker
collects the same tests and only the one given this file loads the TPU
library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.models.model import make_model
from repro.serving.engine import _init_ops, get_serve_kernels

HBM_BYTES = 16e9           # one TPU v5e
# granite-3-8b decode: the one-chip fleet of 2 nodes x 2 replicas x 4 slots
FLEET, SLOTS, MAX_SEQ = 4, 4, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_decode_compiles_to_a_tpu_kernel(one_chip, dtype):
    """Called with ``interpret=False`` directly: the serving attention
    picks interpret mode from the sandbox's backend."""
    cfg = get_config("granite-3-8b")
    B, d = FLEET * SLOTS, cfg.resolved_head_dim
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, cfg.num_heads, d), dtype),
        jax.ShapeDtypeStruct((B, cfg.num_kv_heads, MAX_SEQ, d), dtype),
        jax.ShapeDtypeStruct((B, cfg.num_kv_heads, MAX_SEQ, d), dtype),
        jax.ShapeDtypeStruct((B,), jnp.int32)))
    fn = jax.jit(functools.partial(flash_decode, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", ["afleet", "afleet_prefill"])
def test_serve_kernel_fits_one_chip(one_chip, variant):
    """The einsum-backend async fleet decode and one fleet prefill bucket
    of the one-chip granite share (20 layers, bf16) compile for the chip
    with arguments + temporaries inside its 16 GB."""
    cfg = get_config("granite-3-8b-1chip")
    model = make_model(cfg, tp=1)
    dtype = jnp.dtype(cfg.dtype)
    params = _on(one_chip, jax.eval_shape(
        lambda k: model.init(k, dtype), jax.random.PRNGKey(0)))
    slab = _on(one_chip, jax.tree.map(
        lambda c: jax.ShapeDtypeStruct((FLEET,) + c.shape, c.dtype),
        jax.eval_shape(lambda: model.init_serve_state(SLOTS, MAX_SEQ,
                                                      dtype))))
    ops = _on(one_chip, jax.eval_shape(lambda: _init_ops(FLEET, SLOTS)))
    kernels = get_serve_kernels(model, MAX_SEQ, dtype)
    if variant == "afleet":
        lowered = kernels.afleet.lower(params, slab, ops)
    else:
        K, sb = FLEET * SLOTS, 16          # every slot admits one bucket
        ivec = jax.ShapeDtypeStruct((K,), jnp.int32, sharding=one_chip)
        toks = jax.ShapeDtypeStruct((K, sb), jnp.int32, sharding=one_chip)
        lowered = kernels.afleet_prefill.lower(params, slab, ops, toks,
                                               *(ivec,) * 5)
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 8e9       # the weights are real
    assert used < HBM_BYTES, (variant, used)
