"""Model substrate: per-arch smoke, serve-path consistency, padding
equivalence, MoE dispatch vs dense oracle, SSD vs naive recurrence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_no_drop
from repro.configs import ARCH_NAMES, get_config
from repro.models import make_model
from repro.models.dims import padded_dims
from repro.models.model import make_train_step
from repro.models.optim import AdamW


def _batch(c, key, B=2, S=32, full_tokens=None):
    toks = full_tokens if full_tokens is not None else \
        jax.random.randint(key, (B, S), 0, c.vocab_size)
    b = {"tokens": toks}
    if c.family == "vlm":
        b["patch_embeds"] = jax.random.normal(
            key, (B, c.num_patches, c.d_model)) * 0.1
    if c.family == "audio":
        b["frame_embeds"] = jax.random.normal(
            key, (B, c.encoder_seq_len, c.d_model)) * 0.1
    return b


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_and_train_step(arch, key):
    """Reduced config: one forward + one train step, shapes + finiteness."""
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    B, S = 2, 64
    batch = _batch(c, key, B, S)
    logits, aux = m.forward(params, batch)
    S_total = S + (c.num_patches if c.family == "vlm" else 0)
    assert logits.shape == (B, S_total, m.dims.vocab)
    assert bool(jnp.isfinite(logits).all())
    opt = AdamW(lr=1e-3)
    st = opt.init(params)
    step = jax.jit(make_train_step(m, opt))
    p2, st2, metrics = step(params, st, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p2),
                                jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_consistency(arch, key):
    """prefill(S) == forward(S) last logits; decode(S+1th) == forward(S+1),
    and a second decode, reading the state the first one wrote, ==
    forward(S+2)."""
    c = reduced_no_drop(get_config(arch))
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    B, S = 2, 32
    toks = jax.random.randint(key, (B, S + 2), 0, c.vocab_size)
    batch = _batch(c, key, B, S, full_tokens=toks[:, :S])
    full, _ = m.forward(params, batch)
    pre, state, pos = m.prefill(params, batch, cache_len=64,
                                cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(pre),
                               atol=2e-4, rtol=2e-4)
    for t in (S, S + 1):
        full2, _ = m.forward(params, dict(batch, tokens=toks[:, :t + 1]))
        dec, state = m.decode(params, state, toks[:, t:t + 1],
                              jnp.int32(pos + t - S))
        np.testing.assert_allclose(np.asarray(full2[:, -1]), np.asarray(dec),
                                   atol=2e-4, rtol=2e-4)


def test_head_padding_equivalence(key):
    """A tp-padded model built from an unpadded one's weights (zero-filled
    pad slots) computes identical outputs — padding is exactly inert."""
    c = get_config("qwen2.5-14b").reduced()  # 40H-style padding arch family
    c = dataclasses.replace(c, num_heads=5 * 2, num_kv_heads=2, head_dim=16)
    m1 = make_model(c, tp=1)
    d1 = m1.dims
    m4 = make_model(c, tp=4)   # kv=2 < tp=4 -> replication + q padding
    d4 = m4.dims
    assert d4.n_kv == 4 and d4.n_q % 4 == 0
    p1 = m1.init(key, jnp.float32)
    p4 = jax.tree.map(jnp.copy, m4.init(key, jnp.float32))

    # map unpadded weights into the padded layout, leaf-by-leaf
    rep = d4.kv_rep
    qpg1, qpg4 = d1.q_per_group, d4.q_per_group
    p4 = jax.device_get(p4)
    p1_np = jax.device_get(p1)
    for lname in ("layers",):
        a1 = p1_np[lname]["attn"]
        a4 = p4[lname]["attn"]
        wq = np.zeros_like(a4["wq"])
        wo = np.zeros_like(a4["wo"])
        wk = np.zeros_like(a4["wk"])
        wv = np.zeros_like(a4["wv"])
        bq = np.zeros_like(a4["bq"]) if "bq" in a4 else None
        bk = np.zeros_like(a4["bk"]) if "bk" in a4 else None
        bv = np.zeros_like(a4["bv"]) if "bv" in a4 else None
        for g in range(d1.n_kv):
            for r in range(rep):
                pg = g * rep + r
                wk[:, :, pg] = a1["wk"][:, :, g]
                wv[:, :, pg] = a1["wv"][:, :, g]
                if bk is not None:
                    bk[:, pg] = a1["bk"][:, g]
                    bv[:, pg] = a1["bv"][:, g]
            for j in range(qpg1):
                r, jj = divmod(j, qpg4)
                p_phys = (g * rep + r) * qpg4 + jj
                p_log = g * qpg1 + j
                wq[:, :, p_phys] = a1["wq"][:, :, p_log]
                wo[:, p_phys] = a1["wo"][:, p_log]
                if bq is not None:
                    bq[:, p_phys] = a1["bq"][:, p_log]
        p4[lname]["attn"].update(
            {k: v for k, v in dict(wq=wq, wk=wk, wv=wv, wo=wo, bq=bq,
                                   bk=bk, bv=bv).items() if v is not None})
        p4[lname]["ffn_norm"] = p1_np[lname]["ffn_norm"]
        p4[lname]["attn_norm"] = p1_np[lname]["attn_norm"]
        p4[lname]["mlp"] = p1_np[lname]["mlp"]
    # shared non-layer leaves: vocab may be padded
    v1 = p1_np["embed"].shape[0]
    emb = np.zeros_like(p4["embed"])
    emb[:v1] = p1_np["embed"]
    p4["embed"] = emb
    if "lm_head" in p4:
        head = np.zeros_like(p4["lm_head"])
        head[:, :v1] = p1_np["lm_head"]
        p4["lm_head"] = head
    p4["final_norm"] = p1_np["final_norm"]
    p4 = jax.tree.map(jnp.asarray, p4)

    m = make_model(c, tp=1)
    batch = _batch(c, key, 2, 16)
    out1, _ = m1.forward(p1, batch)
    out4, _ = m4.forward(p4, batch)
    np.testing.assert_allclose(np.asarray(out1),
                               np.asarray(out4[:, :, :v1]),
                               atol=1e-4, rtol=1e-4)


def test_moe_dispatch_matches_dense_oracle(key):
    from repro.models.moe import init_moe, moe_apply, moe_dense_oracle
    E, K, d, ff = 4, 2, 32, 64
    p = init_moe(key, d, ff, E, jnp.float32, shared_expert=True,
                 activation="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, d))
    y, aux = moe_apply(p, x, num_experts=E, top_k=K,
                       capacity_factor=float(E), activation="swiglu")
    y_ref = moe_dense_oracle(p, x, num_experts=E, top_k=K,
                             activation="swiglu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_bounded(key):
    """With cf=1.0 some tokens may drop, but outputs stay finite and within
    the convex hull scale of expert outputs."""
    from repro.models.moe import init_moe, moe_apply
    E, K, d, ff = 4, 2, 16, 32
    p = init_moe(key, d, ff, E, jnp.float32, False, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, d))
    y, aux = moe_apply(p, x, num_experts=E, top_k=K, capacity_factor=1.0,
                       activation="swiglu")
    assert bool(jnp.isfinite(y).all())


def test_ssd_chunked_matches_recurrence(key):
    from repro.models.ssd import ssd_chunked, ssd_reference
    B, T, H, P, G, N = 2, 96, 4, 16, 1, 8
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=1e-3, rtol=1e-3)


def test_ssd_decode_continues_prefill(key):
    """Running T steps with the decode recurrence == chunked full-seq."""
    from repro.models.ssd import ssd_chunked, ssd_decode_step
    B, T, H, P, G, N = 1, 33, 2, 8, 1, 4
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    y_full, s_full = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    s = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(T):
        y, s = ssd_decode_step(s, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_seq),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s),
                               atol=1e-3, rtol=1e-3)


def test_grad_accum_equivalence(key):
    """grad_accum=2 gives (numerically) the same update as grad_accum=1."""
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    opt = AdamW(lr=1e-3)
    batch = _batch(c, key, 4, 16)
    s1 = jax.jit(make_train_step(m, opt, grad_accum=1))(
        params, opt.init(params), batch)
    s2 = jax.jit(make_train_step(m, opt, grad_accum=2))(
        params, opt.init(params), batch)
    for a, b in zip(jax.tree.leaves(s1[0]), jax.tree.leaves(s2[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)
