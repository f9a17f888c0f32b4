"""The granite-4.0-h hybrid cell off the chip, at the program's reduced size
in float32 (one whole period of 9 Mamba-2 layers and 1 attention layer, 4 of
8 experts held, top 3): the fleet path against the plain reference, the
expert shares against the uncut layer, a padded bucket against the
exact-length batch, the engine's expert counters against a recount of the
routing, and the work counts against the served weights."""
import dataclasses
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

SEED = 2 ** 31 + 41
MAX_SEQ = 64


@pytest.fixture(scope="module")
def small():
    return harness.reduced(harness.load_cell("granite-h-small-chat"),
                           max_seq=MAX_SEQ)["config"]


@pytest.fixture(scope="module")
def served(small):
    import jax.numpy as jnp

    from repro.models.model import make_model

    mod = harness.load_model(small)
    arch = harness.arch_config(small)
    return arch, make_model(arch, tp=1), mod.make_params(
        small, SEED, dtype=jnp.float32), mod


def _rel(got, want, scale=None):
    """The widest difference over ``scale`` (default: ``want``'s RMS)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if scale is None:
        scale = np.sqrt(np.mean(want ** 2))
    return np.max(np.abs(got - want)) / scale


def test_reduced_share_is_one_period(served):
    arch = served[0]
    assert arch.family == "hybrid_moe" and arch.num_layers == arch.period
    assert "attention" in arch.mixers and "mamba" in arch.mixers
    assert arch.experts_held < arch.num_experts


def test_fleet_path_matches_reference(small, served):
    """Prompts of three lengths and a pad row in one bucket go through the
    fleet's async prefill kernel into a stale slab, then four ``afleet``
    decode steps. The same steps through ``Model.prefill`` and
    ``Model.decode_fleet`` (what the kernels run) give the logits, whose
    argmax are the served tokens; those logits match the float32
    reference's at every position. Tolerance 1e-4 of the logits' RMS: both
    sides are float32 and differ only in summation order (chunked against
    sequential SSD, grouped against dense experts), about 1e-6."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import _init_ops, get_serve_kernels

    arch, model, params, mod = served
    F, B, steps = 2, 2, 4
    kern = get_serve_kernels(model, MAX_SEQ, jnp.float32)
    rng = np.random.default_rng(7)
    stale = jax.tree.map(
        lambda t: rng.uniform(-1, 1, (F,) + t.shape).astype(t.dtype),
        model.init_serve_state(B, MAX_SEQ, jnp.float32))
    lens = np.array([5, 9, 13, 1], np.int32)
    toks = np.zeros((4, 16), np.int32)
    for i, n in enumerate(lens[:3]):
        toks[i, :n] = rng.integers(1, small["vocab_size"], n)
    rows = np.array([0, 0, 1, F], np.int32)          # the pad row drops
    slots = np.array([0, 1, 0, B], np.int32)
    first, slab, ops = kern.afleet_prefill(
        params, jax.tree.map(jnp.asarray, stale), _init_ops(F, B), toks,
        lens, rows, slots,
        np.full(4, 50, np.int32), np.full(4, -1, np.int32))
    seqs = [list(toks[i, :n]) + [int(first[i])]
            for i, n in enumerate(lens[:3])]

    # the same through the model: prefill, scatter, decode_fleet
    logits, small_state, _ = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=MAX_SEQ,
                                   cache_dtype=jnp.float32))(
        params, {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lens)})
    decode = jax.jit(model.decode_fleet)
    got = [[np.asarray(logits[i])] for i in range(3)]
    mirror = jax.tree.map(
        lambda s, sm: jnp.asarray(s).at[rows, :, slots].set(
            sm.swapaxes(0, 1), mode="drop"), stale, small_state)
    pos = np.zeros((F, B), np.int32)
    for i in range(3):
        pos[rows[i], slots[i]] = lens[i]
    for _ in range(steps):
        nxt, _, stepped, _, slab, ops = kern.afleet(params, slab, ops)
        last = np.zeros((F, B), np.int32)
        for i in range(3):
            last[rows[i], slots[i]] = seqs[i][-1]
        lg, mirror, _ = decode(params, mirror, jnp.asarray(last)[..., None],
                               jnp.asarray(pos))
        for i in range(3):
            f, b = rows[i], slots[i]
            assert int(np.argmax(lg[f, b])) == int(nxt[f, b])
            got[i].append(np.asarray(lg[f, b]))
            seqs[i].append(int(nxt[f, b]))
        pos += 1
    full = np.zeros((3, max(lens) + steps), np.int32)   # right-padded
    for i, n in enumerate(lens[:3]):
        full[i, :n + steps] = seqs[i][:n + steps]
    ref = np.asarray(mod.logits(small, SEED, full, dtype=jnp.float32))
    for i, n in enumerate(lens[:3]):
        assert _rel(np.stack(got[i]), ref[i, n - 1:n + steps]) < 1e-4, i


def test_expert_shares_add_up_to_the_uncut_layer(small):
    """Every share of the experts (experts 0-3 and 4-7 of 8), each computed
    by the program's held-expert layer, plus the shared expert counted once,
    add up to the reference's uncut layer (all 8 experts held)."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import moe_held_apply

    mod = harness.load_model(small)
    E = small["router_outputs"]
    held = small["num_local_experts"]
    key = modelkit.root_key(SEED)

    def moe_weights(cfg):
        return jax.jit(lambda k: mod.weights.layer(cfg, k, 0, "mamba",
                                                   jnp.float32)["moe"])(key)

    uncut = dict(small, num_local_experts=E, first_held_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, small["hidden_size"]))
    total = 0.0
    for i, first in enumerate(range(0, E, held)):
        w = moe_weights(dict(small, first_held_expert=first))
        if i:
            w = {k: v for k, v in w.items() if k != "shared"}
        y, _ = moe_held_apply(w, x, num_experts=E,
                              top_k=small["num_experts_per_tok"],
                              first=first)
        total = total + y
    want, _ = jax.jit(lambda x, w: mod.reference._moe(x, w, uncut, False))(
        x[None], moe_weights(uncut))
    assert _rel(total, want[0]) < 1e-5


def test_rows_past_the_pairs_cannot_leak(small, monkeypatch):
    """The TPU's grouped matmul leaves the rows past a pass's pairs
    unwritten, and they can hold an Inf from another program: the layer
    selects them away, so an Inf there changes nothing (a zero gate times
    Inf would be NaN)."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import moe_held_apply

    mod = harness.load_model(small)
    w = jax.jit(lambda k: mod.weights.layer(small, k, 0, "mamba",
                                            jnp.float32)["moe"])(
        modelkit.root_key(SEED))
    x = jax.random.normal(jax.random.PRNGKey(4), (24, small["hidden_size"]))
    kw = dict(num_experts=small["router_outputs"],
              top_k=small["num_experts_per_tok"],
              first=small["first_held_expert"], rows=16)
    want, _ = moe_held_apply(w, x, **kw)
    real = jax.lax.ragged_dot

    def stale(lhs, rhs, sizes, **k):
        rows = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(rows, real(lhs, rhs, sizes, **k), jnp.inf)

    monkeypatch.setattr(jax.lax, "ragged_dot", stale)
    got, _ = moe_held_apply(w, x, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_padded_bucket_matches_exact_length_batch(served):
    """Three prompts of 11 tokens right-padded into a bucket of 16 with a
    pad row give the rows of the unpadded batch: logits and every state
    leaf. To float32 rounding and not bit for bit: XLA's CPU matmul rounds
    a row differently with the batch's row count (a plain ``x @ w`` of 33
    and of 64 rows already differs in the last bit)."""
    import jax
    import jax.numpy as jnp

    arch, model, params, _ = served
    n, sb = 11, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, n), 0,
                              arch.vocab_size)
    le, se, pe = model.prefill(params, {"tokens": toks}, cache_len=MAX_SEQ,
                               cache_dtype=jnp.float32)
    pad = jnp.zeros((4, sb), jnp.int32).at[:3, :n].set(toks)
    lp, sp, pp = model.prefill(
        params, {"tokens": pad, "lengths": jnp.asarray([n, n, n, 1])},
        cache_len=MAX_SEQ, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(pp[:3]), n)
    assert _rel(lp[:3], le) < 1e-5
    # state leaves over their largest entry: the SSM state is heavy-tailed
    # (largest about 30 times its RMS)
    for name in ("ssm", "conv"):
        want = np.asarray(se[name])
        assert _rel(sp[name][:, :3], want, np.abs(want).max()) < 1e-5, name
    for name in ("k", "v"):
        want = np.asarray(se[name][:, :, :n])
        assert _rel(sp[name][:, :3, :n], want,
                    np.abs(want).max()) < 1e-5, name


def test_prefill_writes_each_rows_state_into_the_slab(served):
    """``Model.prefill_into`` (each layer's state written into the fleet
    slab as the layer scan reaches it) leaves in the slab what a prefill of
    the same batch followed by a scatter leaves: row k's state at (fleet
    row ``rows[k]``, slot ``slots[k]``), an out-of-bounds row dropped, and
    every other slot as it was."""
    import jax
    import jax.numpy as jnp

    arch, model, params, _ = served
    F, B, n = 2, 3, 11
    toks = jax.random.randint(jax.random.PRNGKey(5), (3, 16), 0,
                              arch.vocab_size)
    batch = {"tokens": toks, "lengths": jnp.asarray([n, 16, 4])}
    rows, slots = jnp.asarray([1, 0, F]), jnp.asarray([2, 0, 1])
    slab = jax.tree.map(
        lambda t: jnp.full((F,) + t.shape, 7.0, t.dtype),
        model.init_serve_state(B, MAX_SEQ, jnp.float32))
    lw, want, pw = model.prefill(params, batch, cache_len=MAX_SEQ,
                                 cache_dtype=jnp.float32)
    lg, got, pg = model.prefill_into(params, batch, slab, rows, slots,
                                     cache_len=MAX_SEQ,
                                     cache_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(pg), np.asarray(pw))
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
    for name, leaf in got.items():
        leaf, st = np.asarray(leaf), np.asarray(want[name])
        np.testing.assert_array_equal(leaf[1, :, 2], st[:, 0], name)
        np.testing.assert_array_equal(leaf[0, :, 0], st[:, 1], name)
        kept = np.ones((F, B), bool)
        kept[1, 2] = kept[0, 0] = False
        assert (leaf.swapaxes(1, 2)[kept] == 7.0).all(), name


def test_engine_expert_counts_equal_a_recount(small, served):
    """Requests served to the end through the async fleet: the engine's
    ``decode_counts`` equal a host recount, over every token a decode step
    took in, of the reference's routing to the held experts, and the routed
    pairs are those tokens times layers times top-k."""
    import jax.numpy as jnp

    from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request

    arch, model, params, mod = served

    def factory(rid):
        return ReplicaEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, cache_dtype=jnp.float32)

    fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                async_tick=True)
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(1, arch.vocab_size,
                                    rng.integers(4, 8)).tolist(),
                    max_new_tokens=int(rng.integers(3, 9)))
            for i in range(6)]
    for r in reqs:
        fe.submit(r)
    fe.run_until_drained()
    (g,) = fe._fleets.values()
    first, held = small["first_held_expert"], small["num_local_experts"]
    seqs = np.zeros((len(reqs), 16), np.int32)            # right-padded
    for i, r in enumerate(reqs):
        seq = r.prompt + r.output[:-1]
        seqs[i, :len(seq)] = seq
    experts = mod.reference.routes(small, SEED, seqs, dtype=jnp.float32)
    want, steps = np.zeros(held, np.int64), 0
    for i, r in enumerate(reqs):
        taken = range(len(r.prompt), len(r.prompt) + len(r.output) - 1)
        local = experts[:, i, taken] - first          # decode inputs
        want += np.bincount(local[(local >= 0) & (local < held)],
                            minlength=held)
        steps += len(taken)
    assert steps > 0 and want.sum() > 0
    np.testing.assert_array_equal(g.decode_counts["expert_tokens"], want)
    assert g.decode_counts["routed_pairs"] == \
        steps * arch.num_layers * arch.num_experts_per_tok


def test_layer_draw_equals_stacked_draw(small, served):
    """The reference redraws one layer at a time; it sees the numbers the
    program was given."""
    import jax
    import jax.numpy as jnp

    params, mod = served[2], served[3]
    key = modelkit.root_key(SEED)
    kinds = mod.weights.kinds(small)
    seen = {"mamba": 0, "attention": 0}
    draw = jax.jit(lambda k, i, kind: mod.weights.layer(small, k, i, kind,
                                                        jnp.float32),
                   static_argnums=2)
    for i, kind in enumerate(kinds):
        one = draw(key, i, kind)
        name = "mamba" if kind == "mamba" else "attn"
        stacked = jax.tree.map(lambda x: x[seen[kind]], params[name])
        seen[kind] += 1
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


def test_control_departs_from_reference(small):
    """The float8 control computes other logits than the reference."""
    import jax.numpy as jnp

    logits = harness.load_model(small).logits
    rng = np.random.default_rng(1)
    toks = rng.integers(1, small["vocab_size"], (2, 24)).astype(np.int32)
    ref = np.asarray(logits(small, 3, toks, dtype=jnp.float32))
    own = modelkit.served_gaps(logits, small, 3, toks, ref.argmax(-1),
                               dtype=jnp.float32)
    ctl = modelkit.served_gaps(logits, small, 3, toks, ref.argmax(-1),
                               control=True, dtype=jnp.float32)
    assert np.max(own) == 0.0
    assert np.max(ctl) > 0.0


HSMALL = harness.load_json(BENCH, "configs", "granite-4.0-h-small-1chip.json")
COUNTS = harness.load_model(HSMALL)


def test_weight_bytes_are_the_served_weights():
    """With every held expert counted, the work counts' weight bytes are
    the bytes of the weights the program serves (8.84 GB); the parts are
    the parameter counts by hand of the one-chip share."""
    import jax
    import jax.numpy as jnp

    f = COUNTS.flops
    assert f.mamba_matmul_params(HSMALL) == 4096 * 16768 + 8192 * 4096
    assert f.attention_params(HSMALL) == 2 * 4096 * 40 * 128
    assert f.expert_params(HSMALL) == 3 * 4096 * 768
    assert f.dense_moe_params(HSMALL) == 4096 * 72 + 3 * 4096 * 1536
    shapes = jax.eval_shape(lambda: COUNTS.make_params(HSMALL, 1))
    served = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert COUNTS.weight_bytes(HSMALL) == served
    assert 8.83e9 < served < 8.85e9
    assert shapes["mamba"]["moe"]["w_gate"].dtype == jnp.bfloat16


def test_reached_experts_and_state_bytes_by_hand():
    f = COUNTS.flops
    # n tokens reach 9 (1 - (62/72)^n) of the 9 held experts of a layer
    assert f.experts_reached(HSMALL, 0) == 0.0
    assert f.experts_reached(HSMALL, 1) == pytest.approx(9 * 10 / 72)
    assert f.experts_reached(HSMALL, 32) == pytest.approx(
        9 * (1 - (62 / 72) ** 32))
    expert = 2 * 20 * 3 * 4096 * 768
    assert COUNTS.weight_bytes(HSMALL) - COUNTS.weight_bytes(HSMALL, 0) == \
        pytest.approx(9 * expert)
    # a slot: 18 x 128 x 64 x 128 float32 SSM state, 18 x 3 x 8448 bf16
    # conv window; K and V of 2 layers x 8 heads x 128 per position
    assert f.state_bytes(HSMALL) == 18 * (4 * 128 * 64 * 128 + 2 * 3 * 8448)
    assert f.kv_bytes_per_position(HSMALL) == 2 * 2 * 8 * 128 * 2
    assert COUNTS.decode_token_bytes(HSMALL, 9) == (
        2 * f.state_bytes(HSMALL) + 8192 * 10 + 4096 * 2)
    # per token: 18 mamba + 2 attention layers, 20 routers and shared
    # experts, 10 x 9/72 expert FFNs each, and the head
    per_token = (18 * f.mamba_matmul_params(HSMALL)
                 + 2 * f.attention_params(HSMALL)
                 + 20 * (f.dense_moe_params(HSMALL)
                         + 1.25 * f.expert_params(HSMALL))
                 + 4096 * 100352)
    assert COUNTS.model_flops_per_token(HSMALL) == pytest.approx(
        2 * per_token)
    assert COUNTS.decode_token_flops(HSMALL, 99) == pytest.approx(
        2 * per_token + 4 * 128 * 64 * 128 * 18 + 4 * 32 * 128 * 100 * 2)


def test_dataclass_fields_in_the_file(served):
    """The share's file states what the program holds: 9 of 72 experts,
    experts 0-8, 20 layers with attention at 5 and 15."""
    from repro.configs import get_config

    one = get_config("granite-4.0-h-small-1chip")
    cfg = harness.arch_config(HSMALL)
    assert dataclasses.replace(cfg, name=one.name) == one
    assert [i for i, t in enumerate(one.mixers) if t == "attention"] == \
        [5, 15]
    assert (one.experts_held, one.num_experts) == (9, 72)
