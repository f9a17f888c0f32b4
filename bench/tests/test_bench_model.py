"""The benchmark's yardstick off the chip, reached through the cell's model
(``bench/models/dense/``) as the harness reaches it: the weights it makes
from a seed, its float32 reference against the program's forward pass, and
its operation and byte counters against hand counts."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import modelkit  # noqa: E402


@pytest.fixture(scope="module")
def small():
    return harness.reduced(harness.load_cell("granite-chat"))["config"]


def test_layer_draw_equals_stacked_draw(small):
    """The reference redraws one layer at a time; it must see the numbers
    the program was given, to float32 rounding of the normal draw (the
    bf16 weights served on the chip round both alike)."""
    import jax
    import jax.numpy as jnp

    weights = harness.load_model(small).weights
    params = weights.make_params(small, 2 ** 32 + 5, dtype=jnp.float32)
    key = modelkit.root_key(2 ** 32 + 5)
    for i in range(small["num_hidden_layers"]):
        one = weights.layer(small, key, i, jnp.float32)
        stacked = jax.tree.map(lambda x: x[i], params["layers"])
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
    assert ("lm_head" in params) == (not small["tie_word_embeddings"])


def test_reference_matches_program_forward(small):
    """At the reduced size in float32 the plain reference and the program's
    ``Model.forward`` compute the same logits."""
    import jax.numpy as jnp

    from repro.models.model import make_model

    dense = harness.load_model(small)
    arch = harness.arch_config(small)
    model = make_model(arch, tp=1)
    seed = 11
    params = dense.make_params(small, seed, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(1, small["vocab_size"], (2, 24)).astype(np.int32)
    want, _ = model.forward(params, {"tokens": jnp.asarray(toks)})
    got = dense.logits(small, seed, toks, dtype=jnp.float32)
    want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
    scale = np.sqrt(np.mean(want ** 2))
    assert np.max(np.abs(got - want)) / scale < 1e-4


def test_control_departs_from_reference(small):
    """The float8 control computes other logits than the reference: its
    gaps are positive somewhere, the reference's own are all zero."""
    import jax.numpy as jnp

    logits = harness.load_model(small).logits
    rng = np.random.default_rng(1)
    toks = rng.integers(1, small["vocab_size"], (2, 32)).astype(np.int32)
    ref = np.asarray(logits(small, 3, toks, dtype=jnp.float32))
    own = modelkit.served_gaps(logits, small, 3, toks, ref.argmax(-1),
                               dtype=jnp.float32)
    ctl = modelkit.served_gaps(logits, small, 3, toks, ref.argmax(-1),
                               control=True, dtype=jnp.float32)
    assert np.max(own) == 0.0
    assert np.max(ctl) > 0.0


GRANITE = harness.load_json(BENCH, "configs", "granite-3-8b-1chip.json")
DENSE = harness.load_model(GRANITE)


def test_weight_bytes_by_hand():
    # granite: per layer q 4096*32*128, k and v 4096*8*128 each, o 32*128*4096,
    # SwiGLU 3*4096*12800; 20 layers, tied head 4096*49155, bf16; norms f32
    layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12800
    assert layer == 199_229_440
    assert DENSE.weight_bytes(GRANITE) == \
        2 * (20 * layer + 4096 * 49155) + 4 * 41 * 4096
    assert DENSE.flops.layer_matmul_params(GRANITE) == layer
    # the whole share with its norms: 8.373 GB, as the program measured
    assert DENSE.weight_bytes(GRANITE) == 8_372_527_104


def test_kv_and_attention_by_hand():
    # granite: 20 layers x (K and V) x 8 heads x 128 x 2 bytes per position
    assert DENSE.flops.kv_bytes_per_position(GRANITE) == 81_920
    # a token at position 99 attends 100 keys: 4 * 32 * 128 * 100 per layer
    assert DENSE.flops.attention_flops(GRANITE, 100) == 4 * 32 * 128 * 100 * 20
    assert DENSE.decode_token_flops(GRANITE, 99) == \
        2 * (20 * 199_229_440 + 4096 * 49155) + 4 * 32 * 128 * 100 * 20
    assert DENSE.decode_token_bytes(GRANITE, 9) == 81_920 * 10 + 4096 * 2


def test_prefill_by_hand():
    n = 7
    causal = n * (n + 1) // 2          # 28 query-key pairs
    assert DENSE.prefill_flops(GRANITE, n) == (
        2 * 20 * 199_229_440 * n
        + 4 * 32 * 128 * causal * 20 + 2 * 4096 * 49155)
    assert DENSE.prefill_bytes(GRANITE, n) == (81_920 + 4096 * 2) * n
