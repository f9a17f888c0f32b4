"""Pure-jnp oracles for every Pallas kernel (the correctness source of truth).

Each function mirrors its kernel's signature exactly; tests sweep shapes and
dtypes and assert allclose between kernel (interpret=True on CPU) and oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q, k, v, *, causal=True):
    """q: (B, Hq, S, d); k, v: (B, Hkv, T, d). GQA by kv-head repetition."""
    B, Hq, S, d = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, pos):
    """q: (B, Hq, d); caches: (B, Hkv, S, d); pos: scalar int or (B,) per-row
    ints (row b attends 0..pos[b])."""
    B, Hq, d = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k_cache, rep, axis=1).astype(jnp.float32)
    v = jnp.repeat(v_cache, rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32), k) / np.sqrt(d)
    mask = jnp.arange(S)[None, :] <= jnp.reshape(jnp.asarray(pos), (-1, 1))
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bhtd->bhd", p, v).astype(q.dtype)


def ssd_chunk_ref(x, a, Bm, Cm, state):
    """One SSD chunk, per the blocked algorithm.

    x:  (B, Q, H, P) — dt-preweighted inputs
    a:  (B, Q, H)    — log decays
    Bm, Cm: (B, Q, N) (single group)
    state: (B, H, P, N) carried in
    Returns (y (B,Q,H,P), new_state).
    """
    a = a.astype(jnp.float32)
    x = x.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)
    Q = x.shape[1]
    a_cum = jnp.cumsum(a, axis=1)                          # (B,Q,H)
    diff = a_cum[:, :, None, :] - a_cum[:, None, :, :]     # (B,Q,K,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bqn,bkn->bqk", Cm, Bm)
    y_diag = jnp.einsum("bqkh,bqk,bkhp->bqhp", L, scores, x)
    y_off = jnp.einsum("bqn,bhpn,bqh->bqhp", Cm, state, jnp.exp(a_cum))
    decay_out = jnp.exp(a_cum[:, -1:, :] - a_cum)          # (B,Q,H)
    new_state = state * jnp.exp(a_cum[:, -1])[:, :, None, None] + \
        jnp.einsum("bkn,bkhp,bkh->bhpn", Bm, x, decay_out)
    return (y_diag + y_off), new_state


def ssd_scan_ref(x, a, Bm, Cm, chunk):
    """Multi-chunk reference: sequential ssd_chunk_ref over chunks."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for c0 in range(0, T, chunk):
        y, state = ssd_chunk_ref(x[:, c0:c0 + chunk], a[:, c0:c0 + chunk],
                                 Bm[:, c0:c0 + chunk], Cm[:, c0:c0 + chunk],
                                 state)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state


def gcn_layer_ref(a_hat, x, w, b, *, relu=True):
    """relu(Â @ X @ W + b) — one GCN layer (paper Eq.6)."""
    h = (a_hat.astype(jnp.float32) @ x.astype(jnp.float32)) @ \
        w.astype(jnp.float32) + b.astype(jnp.float32)
    if relu:
        h = jnp.maximum(h, 0.0)
    return h.astype(x.dtype)
