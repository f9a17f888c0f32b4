"""Compiles for a described TPU v5e: the main path's kernels at real widths.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(tiling, fast-memory limits, programs that do not fit). The topology is
described inside a module fixture, never at import, so every test worker
collects the same tests and only the one given this file loads the TPU
library.
"""
import functools
import itertools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.models.attention import SERVED_QKV, serve_layout
from repro.models.model import make_model
from repro.serving.engine import _init_ops, get_serve_kernels

HBM_BYTES = 16e9           # one TPU v5e
V5E_USABLE_BYTES = 15.75 * 2**30   # what its allocator hands out
# granite-3-8b decode: the one-chip fleet of 2 nodes x 2 replicas x 4 slots
FLEET, SLOTS, MAX_SEQ = 4, 4, 1024
# the benchmark's granite-chat cell: 2 nodes x 4 replicas x 4 slots of 512
CELL_FLEET, CELL_SLOTS, CELL_SEQ = 8, 4, 512


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


def _prefill_lowered(kernels, params, slab, ops, sharding, K, sb):
    """A fleet prefill of ``K`` rows at the ``sb``-position bucket."""
    ivec = jax.ShapeDtypeStruct((K,), jnp.int32, sharding=sharding)
    toks = jax.ShapeDtypeStruct((K, sb), jnp.int32, sharding=sharding)
    return kernels.afleet_prefill.lower(params, slab, ops, toks, *(ivec,) * 5)


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


def _served_params(sharding, model, dtype):
    """Abstract params in the layout the engine serves them
    (``serving.engine.served_weights``: q/k/v as (heads, head_dim,
    d_model), ``wq``'s heads split by kv group)."""
    return _on(sharding, jax.eval_shape(
        lambda k: serve_layout(model.init(k, dtype)), jax.random.PRNGKey(0)))


def _granite_fleet(sharding, fleet, slots, max_seq):
    """The one-chip granite share (20 layers, bf16) as abstract params in
    the served layout, a fleet slab of ``fleet`` x ``slots`` x ``max_seq``,
    its operands and serve kernels."""
    cfg = get_config("granite-3-8b-1chip")
    model = make_model(cfg, tp=1)
    dtype = jnp.dtype(cfg.dtype)
    params = _served_params(sharding, model, dtype)
    slab = _on(sharding, jax.tree.map(
        lambda c: jax.ShapeDtypeStruct((fleet,) + c.shape, c.dtype),
        jax.eval_shape(lambda: model.init_serve_state(slots, max_seq,
                                                      dtype))))
    ops = _on(sharding, jax.eval_shape(lambda: _init_ops(fleet, slots)))
    return params, slab, ops, get_serve_kernels(model, max_seq, dtype)


@pytest.mark.parametrize("variant", ["afleet", "afleet_prefill"])
def test_serve_kernel_fits_one_chip(one_chip, variant):
    """The einsum-backend async fleet decode and one fleet prefill bucket
    of the one-chip granite share (20 layers, bf16) compile for the chip
    with arguments + temporaries inside its 16 GB."""
    params, slab, ops, kernels = _granite_fleet(one_chip, FLEET, SLOTS,
                                                MAX_SEQ)
    if variant == "afleet":
        lowered = kernels.afleet.lower(params, slab, ops)
    else:                                  # every slot admits one bucket
        lowered = _prefill_lowered(kernels, params, slab, ops, one_chip,
                                   FLEET * SLOTS, 16)
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 8e9       # the weights are real
    assert used < HBM_BYTES, (variant, used)


def test_fleet_decode_moves_no_slab(one_chip):
    """The benchmark cell's fleet decode writes each step's new tokens into
    the donated slab and moves nothing else of it: no copy or transpose the
    size of a slab leaf (a cache carried through the layer scan is relaid
    layer-major in and out, four slab copies per step), and temporaries
    far below one slab (1.34 GB at these shapes)."""
    params, slab, ops, kernels = _granite_fleet(one_chip, CELL_FLEET,
                                                CELL_SLOTS, CELL_SEQ)
    compiled = kernels.afleet.lower(params, slab, ops).compile()
    leaves = {",".join(map(str, c.shape)) for c in jax.tree.leaves(slab)}
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(",
        compiled.as_text()) if m.group(1) in leaves]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def _qkv_weight_shapes(config: str) -> set:
    """Every shape a q/k/v projection weight of configuration ``config``
    takes, whole or as one layer's slice (with or without its unit layer
    axis), with its axes in any order: the published (d_model, heads,
    head_dim), the served one with ``wq``'s heads split by kv group, and
    any relayout."""
    cfg = get_config(config)
    model = make_model(cfg, tp=1)
    params = jax.eval_shape(lambda k: model.init(k, jnp.dtype(cfg.dtype)),
                            jax.random.PRNGKey(0))
    g = model.dims.n_kv
    out = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if path[-1].key not in SERVED_QKV:
            continue
        *lead, d, n, h = leaf.shape
        for core in {(d, n, h), (g, n // g, h, d)}:
            for trail in itertools.permutations(core):
                for pre in ((), (1,), tuple(lead)):
                    out.add(",".join(map(str, pre + trail)))
    return out


def _weight_moves(compiled, config: str) -> list:
    """The copies, transposes and materialised dynamic slices in
    ``compiled`` shaped like a q/k/v projection weight of ``config`` or
    its per-layer slice. A copy of a ``wo`` parameter is not one, though
    the hybrid's (heads, head_dim, d_model) ``wo`` has the shape of a
    ``wq``."""
    shapes = _qkv_weight_shapes(config)
    return [m.group(0) for m in re.finditer(
        r"(%[\w.\-]+) = \w+\[([\d,]+)\]\{[^}]*\} "
        r"(copy|transpose|fusion)\((%[\w.\-]+)", compiled.as_text())
        if m.group(2) in shapes and "_wo__" not in m.group(4)
        and (m.group(3) != "fusion" or "dynamic-slice" in m.group(1))]


@pytest.mark.parametrize("variant", ["afleet", "afleet_prefill"])
def test_fleet_programs_read_qkv_weights_in_place(one_chip, variant):
    """Served in (heads, head_dim, d_model) order, ``wq``'s heads split by
    kv group, the granite-chat cell's fleet decode and a fleet prefill
    bucket (32 rows of 16) read each layer's q/k/v projection weights
    where they lie: no copy, transpose or materialised slice shaped like
    one, whole or per layer. In the published (d_model, heads, head_dim)
    order every layer of both programs materialised its ``wq`` slice and
    relaid it and ``wk`` and ``wv`` d_model-minor, about 3.4 GB of extra
    traffic per decode call at these shapes; with ``wq``'s heads as one
    axis it still materialised the ``wq`` slice to regroup them."""
    params, slab, ops, kernels = _granite_fleet(one_chip, CELL_FLEET,
                                                CELL_SLOTS, CELL_SEQ)
    if variant == "afleet":
        lowered = kernels.afleet.lower(params, slab, ops)
    else:
        lowered = _prefill_lowered(kernels, params, slab, ops, one_chip,
                                   CELL_FLEET * CELL_SLOTS, 16)
    moved = _weight_moves(lowered.compile(), "granite-3-8b-1chip")
    assert not moved, moved


def _hsmall_fleet(sharding):
    """The one-chip granite-4.0-h-small share (20 layers: 18 Mamba-2 and 2
    attention, 9 of 72 experts held, bf16) as abstract params in the served
    layout, the benchmark cell's fleet slab, its operands and serve
    kernels."""
    cfg = get_config("granite-4.0-h-small-1chip")
    model = make_model(cfg, tp=1)
    dtype = jnp.dtype(cfg.dtype)
    params = _served_params(sharding, model, dtype)
    slab = _on(sharding, jax.tree.map(
        lambda c: jax.ShapeDtypeStruct((CELL_FLEET,) + c.shape, c.dtype),
        jax.eval_shape(lambda: model.init_serve_state(CELL_SLOTS, CELL_SEQ,
                                                      dtype))))
    ops = _on(sharding, jax.eval_shape(lambda: _init_ops(CELL_FLEET,
                                                         CELL_SLOTS)))
    return params, slab, ops, get_serve_kernels(model, CELL_SEQ, dtype)


@pytest.mark.parametrize("variant", ["afleet", "afleet_prefill"])
def test_hybrid_moe_serve_kernel_fits_one_chip(one_chip, variant):
    """At the granite-h-small-chat cell's shapes (8.84 GB of weights, a slab
    of 32 slots of 512 holding 2.58 GB of SSM, conv and K/V state) the
    async fleet decode and the largest fleet prefill the cell compiles, all
    32 slots at the 256-position bucket, compile for the chip with
    arguments + temporaries inside what its allocator hands out, 15.75 GiB
    (a prefill that returns every row's state of every layer beside the
    slab needs 17.6 GB there)."""
    params, slab, ops, kernels = _hsmall_fleet(one_chip)
    if variant == "afleet":
        lowered = kernels.afleet.lower(params, slab, ops)
    else:
        lowered = _prefill_lowered(kernels, params, slab, ops, one_chip,
                                   CELL_FLEET * CELL_SLOTS, 256)
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 11e9      # weights and slab are real
    assert used < V5E_USABLE_BYTES, (variant, used)


def test_hybrid_moe_decode_moves_no_state(one_chip):
    """The granite-h-small-chat decode steps the SSM and conv state in place
    and holds no temporary of the slab's size: no copy or transpose the
    size of those leaves (a state leaf scanned as the layer scan's inputs
    and outputs, or mapped over the fleet, is relaid layer-major in and out:
    two 2.42 GB copies and a slab-sized temporary), and temporaries under a
    fifth of the 2.58 GB slab. The K/V leaves (33.5 MB each) are relaid
    for the attention layers' dynamic index in the scan."""
    params, slab, ops, kernels = _hsmall_fleet(one_chip)
    compiled = kernels.afleet.lower(params, slab, ops).compile()
    state = {",".join(map(str, slab[n].shape)) for n in ("ssm", "conv")}
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(",
        compiled.as_text()) if m.group(1) in state]
    assert not moved, moved
    slab_bytes = sum(c.size * c.dtype.itemsize for c in jax.tree.leaves(slab))
    assert slab_bytes > 2.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes / 5


@pytest.mark.parametrize("variant", ["afleet", "afleet_prefill"])
def test_hybrid_moe_programs_read_qkv_weights_in_place(one_chip, variant):
    """The granite-h-small-chat cell's two attention layers read their
    served q/k/v projection weights in place in its fleet decode and a
    fleet prefill bucket (32 rows of 16): no copy, transpose or
    materialised slice shaped like one. Its ``wo`` copy is another matter
    and not checked here."""
    params, slab, ops, kernels = _hsmall_fleet(one_chip)
    if variant == "afleet":
        lowered = kernels.afleet.lower(params, slab, ops)
    else:
        lowered = _prefill_lowered(kernels, params, slab, ops, one_chip,
                                   CELL_FLEET * CELL_SLOTS, 16)
    moved = _weight_moves(lowered.compile(), "granite-4.0-h-small-1chip")
    assert not moved, moved
