"""granite-4.0-h style hybrid (family ``hybrid_moe``): every layer has its own
mixer, Mamba-2 or attention (``ArchConfig.layer_types``), and its own MoE FFN
of held experts beside a shared expert.

    h = embed[tokens] * embedding_multiplier
    per layer:  h += r * mixer(rmsnorm(h))     Mamba-2 (``ssd.py``) or GQA
                                               attention, no RoPE when
                                               ``rope_theta`` is 0, softmax
                                               scale ``attention_multiplier``
                x  = rmsnorm(h)
                h += r * (moe_held(x) + shared(x))   (``moe.moe_held_apply``)
    logits = rmsnorm(h) @ head / logits_scaling

with r = ``residual_multiplier``. Parameters are stacked by mixer kind:
``params["mamba"]`` holds the Mamba blocks in layer order, ``params["attn"]``
the attention blocks; each block carries its mixer, its two norms and its
MoE FFN. The layer scan runs over the Mamba blocks; an attention block runs
inside the step of the Mamba block that follows it (a ``lax.cond`` on
``plan``'s table), so every layer pattern whose attention layers are each
followed by a Mamba layer keeps one compact scan body.

Serve state, per replica: ``ssm`` (Lm, B, H, P, N) float32 and ``conv`` (Lm,
B, W-1, C) for the Lm Mamba layers, ``k``/``v`` (La, B, S, G, hd) for the La
attention layers — the slot on axis 1 of every leaf, as the fleet slab wants.
``hmoe_prefill_into`` writes each layer's state straight into that slab as
the layer scan reaches it, so a fleet prefill never holds every row's state
of every layer beside the slab.

``hmoe_decode`` takes the fleet's stacked state directly (a leading fleet
axis on every leaf) instead of being mapped over it: under ``vmap`` the
per-layer state of a layer scan is relaid layer-major on the way in and out,
a copy of the whole slab each step. Its scan carries the hidden state, the
routing counts and the SSM/conv leaves, whose layer slice is read and
written once per step in place (one dynamic update per leaf and step); the
attention layers read K/V from the loop-invariant slab, and their new tokens
are written by one scatter after the scan.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.dims import PaddedDims
from repro.models.layers import he_init, rms_norm
from repro.models.lm import _remat_policy
from repro.models.moe import moe_held_apply
from repro.models.ssd import (init_mamba2, mamba2_decode, mamba2_forward,
                              mamba2_init_state)


def plan(cfg: ArchConfig) -> tuple:
    """(mamba layer ids, attention layer ids, ``before``): ``before[j]`` is
    the index among the attention blocks of the attention layer just before
    Mamba block j, or -1."""
    mix = cfg.mixers
    m_ids = [i for i, t in enumerate(mix) if t == "mamba"]
    a_ids = [i for i, t in enumerate(mix) if t == "attention"]
    if len(m_ids) + len(a_ids) != len(mix) or len(mix) != cfg.num_layers:
        raise ValueError(f"{cfg.name}: layer_types {mix} must name "
                         f"{cfg.num_layers} mamba or attention layers")
    if any(i + 1 not in m_ids for i in a_ids):
        raise ValueError(f"{cfg.name}: each attention layer must be followed "
                         f"by a mamba layer, got {mix}")
    before = [a_ids.index(i - 1) if i - 1 in a_ids else -1 for i in m_ids]
    return m_ids, a_ids, before


MOE_PASS = 4096      # the fewest (token, expert) pairs a serving pass runs


def _attn_scale(cfg: ArchConfig):
    return cfg.attention_multiplier or None


def _init_moe(key, cfg: ArchConfig, dtype) -> dict:
    d, e_ff, fs = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.moe_shared_d_ff
    held = cfg.experts_held
    ks = jax.random.split(key, 7)
    p = {"router": he_init(ks[0], (d, cfg.num_experts), dtype, d),
         "w_gate": he_init(ks[1], (held, d, e_ff), dtype, d),
         "w_up": he_init(ks[2], (held, d, e_ff), dtype, d),
         "w_down": he_init(ks[3], (held, e_ff, d), dtype, e_ff)}
    if fs:
        p["shared"] = {"w_gate": he_init(ks[4], (d, fs), dtype, d),
                       "w_up": he_init(ks[5], (d, fs), dtype, d),
                       "w_down": he_init(ks[6], (fs, d), dtype, fs)}
    return p


def init_hybrid_moe(key, cfg: ArchConfig, dims: PaddedDims,
                    dtype=jnp.float32) -> dict:
    m_ids, a_ids, _ = plan(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    zeros = jnp.zeros((d,), jnp.float32)

    def mamba_block(k):
        k1, k2 = jax.random.split(k)
        return {"norm": zeros, "ffn_norm": zeros,
                "mixer": init_mamba2(k1, d, cfg.d_inner, cfg.ssm_heads,
                                     cfg.ssm_head_dim, cfg.ssm_state,
                                     cfg.ssm_groups, cfg.ssm_conv_width,
                                     dtype),
                "moe": _init_moe(k2, cfg, dtype)}

    def attn_block(k):
        k1, k2 = jax.random.split(k)
        return {"norm": zeros, "ffn_norm": zeros,
                "mixer": attn.init_attention(k1, d, dims,
                                             cfg.resolved_head_dim,
                                             cfg.qkv_bias, dtype),
                "moe": _init_moe(k2, cfg, dtype)}

    params = {
        "embed": (jax.random.normal(ks[0], (dims.vocab, d)) * 0.02
                  ).astype(dtype),
        "final_norm": zeros,
        "mamba": jax.vmap(mamba_block)(jax.random.split(ks[1], len(m_ids))),
        "attn": jax.vmap(attn_block)(jax.random.split(ks[2], len(a_ids))),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = he_init(ks[3], (d, dims.vocab), dtype, d)
    return params


# ------------------------------------------------------------------ blocks
def _embed(params, tokens, cfg):
    h = params["embed"][tokens]
    return (h * cfg.embedding_multiplier).astype(h.dtype)


def _logits(params, h, cfg):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = h @ head if head is not None else h @ params["embed"].T
    return logits / cfg.logits_scaling


def _moe(bp, h, cfg, serve=True):
    """``h += r * (moe_held(x) + shared(x))``, x = rmsnorm(h), over h's
    leading dims flattened; the held experts are the first ``moe_held`` of
    ``num_experts``. Returns (h, routed (tokens, held)). Serving
    runs the expert pairs in passes of twice the tokens (about 1.6 times
    the pairs that uniform routing brings) and of no fewer than
    ``MOE_PASS``: each pass reads the held experts' weights, so a decode
    step runs one; training runs one fixed pass."""
    x = rms_norm(h, bp["ffn_norm"], cfg.norm_eps).reshape(-1, h.shape[-1])
    with jax.named_scope("moe"):
        y, routed = moe_held_apply(
            bp["moe"], x, num_experts=cfg.num_experts,
            top_k=cfg.num_experts_per_tok,
            activation=cfg.activation,
            rows=max(2 * x.shape[0], MOE_PASS) if serve else 0)
    y = y.reshape(h.shape)
    return (h + cfg.residual_multiplier * y).astype(h.dtype), routed


def _pick(tree, i):
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0,
                                                               False), tree)


def _blocked(cfg: ArchConfig, S: int) -> ArchConfig:
    """The SSD blocking for a prompt of ``S`` positions: the configured
    chunk, or the power of two that covers S when that is shorter (the
    chunked scan is exact for any chunk; a padded bucket and its
    exact-length prompts then block alike)."""
    c = 1
    while c < S:
        c <<= 1
    return dataclasses.replace(cfg, ssm_chunk=min(cfg.ssm_chunk, c))


def _stack(params, h, cfg: ArchConfig, dims: PaddedDims, *, lengths=None,
           state=None, put=None, remat="none", shard_fn=None):
    """Every layer over a full sequence h (B, S, d). With ``state`` (serve
    state to write into: ``ssm``/``conv`` of the Mamba layers, ``k``/``v``
    of the attention layers) each layer writes its part as soon as it has
    it, by ``put(leaf, layer, value)`` with value (B, ...): a Mamba layer
    its final SSM and conv state, an attention layer its K/V filled over
    the leaf's cache length. Returns (h, state); without ``state`` nothing
    is kept (training)."""
    _, _, before = plan(cfg)
    blk = _blocked(cfg, h.shape[1])
    r = cfg.residual_multiplier
    serve = state is not None

    def attn_layer(args):
        h, kv, a = args
        bp = _pick(params["attn"], a)
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        with jax.named_scope("attention_mixer"):
            if serve:
                empty = {n: jnp.zeros((h.shape[0],) + t.shape[-3:], t.dtype)
                         for n, t in zip("kv", kv)}
                y, filled = attn.prefill_attention(
                    bp["mixer"], x, dims, empty, rope_theta=cfg.rope_theta,
                    scale=_attn_scale(cfg))
                kv = tuple(put(t, a, filled[n]) for n, t in zip("kv", kv))
            else:
                y = attn.attention(bp["mixer"], x, dims,
                                   rope_theta=cfg.rope_theta, causal=True,
                                   shard_fn=shard_fn, scale=_attn_scale(cfg))
        h = (h + r * y).astype(h.dtype)
        h, _ = _moe(bp, h, cfg, serve)
        return h, kv

    def body(carry, xs):
        h, kv, ssm, conv = carry
        bp, j, ab = xs
        h, kv = jax.lax.cond(ab >= 0, attn_layer, lambda c: c[:2],
                             (h, kv, jnp.maximum(ab, 0)))
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        with jax.named_scope("mamba_mixer"):
            out = mamba2_forward(bp["mixer"], x, blk, return_state=serve,
                                 shard_fn=shard_fn, lengths=lengths)
        if serve:
            y, st = out
            ssm, conv = put(ssm, j, st["ssm"]), put(conv, j, st["conv"])
        else:
            y = out
        h = (h + r * y).astype(h.dtype)
        h, _ = _moe(bp, h, cfg, serve)
        if shard_fn is not None:
            h = shard_fn(h, "act_btd")
        return (h, kv, ssm, conv), None

    pol = _remat_policy(remat)
    fn = jax.checkpoint(body, policy=pol) if pol is not None else body
    st = state or {"k": None, "v": None, "ssm": None, "conv": None}
    (h, kv, ssm, conv), _ = jax.lax.scan(
        fn, (h, (st["k"], st["v"]), st["ssm"], st["conv"]),
        (params["mamba"], jnp.arange(len(before), dtype=jnp.int32),
         jnp.asarray(before, jnp.int32)))
    if not serve:
        return h, None
    return h, {"ssm": ssm, "conv": conv, "k": kv[0], "v": kv[1]}


# ------------------------------------------------------------------ forward
def hmoe_forward(params, batch, cfg: ArchConfig, dims: PaddedDims, *,
                 remat="none", shard_fn=None, return_features=False):
    """Training forward: (logits (B,S,V), aux=0); with ``return_features``
    the final-normed features instead (the chunked loss applies the head,
    so it gets the features scaled by 1/logits_scaling)."""
    h = _embed(params, batch["tokens"], cfg)
    h, _ = _stack(params, h, cfg, dims, remat=remat, shard_fn=shard_fn)
    if return_features:
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return h / cfg.logits_scaling, jnp.float32(0.0)
    logits = _logits(params, h, cfg)
    if shard_fn is not None:
        logits = shard_fn(logits, "logits")
    return logits, jnp.float32(0.0)


# ------------------------------------------------------------------ serving
def hmoe_init_state(cfg: ArchConfig, dims: PaddedDims, batch: int,
                    max_len: int, dtype=jnp.bfloat16) -> dict:
    m_ids, a_ids, _ = plan(cfg)
    st = mamba2_init_state(batch, cfg, dtype)
    kv = (len(a_ids), batch, max_len, dims.n_kv, cfg.resolved_head_dim)
    return {"ssm": jnp.zeros((len(m_ids),) + st["ssm"].shape, jnp.float32),
            "conv": jnp.zeros((len(m_ids),) + st["conv"].shape, dtype),
            "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}


def _prefill(params, batch, cfg, dims, state, put, shard_fn):
    tokens = batch["tokens"]
    lengths = batch.get("lengths")
    h = _embed(params, tokens, cfg)
    h, state = _stack(params, h, cfg, dims, lengths=lengths, state=state,
                      put=put, shard_fn=shard_fn)
    if lengths is None:
        last, pos = h[:, -1], tokens.shape[1]
    else:
        idx = (lengths - 1).astype(jnp.int32)
        last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        pos = lengths.astype(jnp.int32)
    return _logits(params, last, cfg), state, pos


def hmoe_prefill(params, batch, cfg: ArchConfig, dims: PaddedDims, *,
                 cache_len: int, cache_dtype=jnp.bfloat16, shard_fn=None):
    """Prefill: (last-token logits, serve state, pos). ``batch["lengths"]``
    (B,) marks right-padded rows: the Mamba layers keep pad steps inert
    (``mamba2_forward``'s ``lengths``), causal attention never lets a real
    position see a pad, and the MoE drops nothing, so each row's result is
    the exact-length prompt's."""
    B = batch["tokens"].shape[0]
    st0 = hmoe_init_state(cfg, dims, B, cache_len, cache_dtype)
    return _prefill(params, batch, cfg, dims, st0,
                    lambda t, i, x: jax.lax.dynamic_update_index_in_dim(
                        t, x.astype(t.dtype), i, 0), shard_fn)


def hmoe_prefill_into(params, batch, slab, rows, slots, cfg: ArchConfig,
                      dims: PaddedDims, *, shard_fn=None):
    """``hmoe_prefill`` whose rows write their state straight into a fleet
    slab (every leaf (F, layers, slots, ...)): row k's state of each layer
    goes to (``rows[k]``, layer, ``slots[k]``) as soon as the layer has it
    (an out-of-bounds row drops its writes), so the prefill never holds
    every row's state of every layer at once (2.42 GB for 32 rows of the
    one-chip granite-4.0-h-small share). Returns (logits, slab, pos)."""
    return _prefill(params, batch, cfg, dims, slab,
                    lambda t, i, x: t.at[rows, i, slots].set(
                        x.astype(t.dtype), mode="drop"), shard_fn)


def hmoe_decode(params, state, tokens, pos, cfg: ArchConfig,
                dims: PaddedDims, *, rows=None):
    """One decode step of a stacked fleet. Every leaf of ``state`` has a
    leading fleet axis F (then the per-replica layout); tokens (F, B, 1);
    pos (F, B) the cache index of each slot's token; ``rows`` (F,) bool
    marks the rows whose step commits (None: all). Returns (logits (F, B,
    V), state, counts) with ``counts["expert_tokens"]`` (F, B, held): how
    many of each slot's (layer, token) pairs went to each held expert, and
    ``counts["routed_pairs"]`` (F, B): its pairs routed over all experts."""
    _, a_ids, before = plan(cfg)
    F, B = tokens.shape[:2]
    T = F * B
    r = cfg.residual_multiplier
    held = cfg.experts_held
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (F, B))
    pos_t = pos.reshape(T)
    G, hd = dims.n_kv, cfg.resolved_head_dim
    k_slab, v_slab = state["k"], state["v"]
    S = k_slab.shape[3]
    rowwise = lambda t: t.reshape((F, B) + t.shape[1:])

    def attn_layer(h, a):
        bp = _pick(params["attn"], a)
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        with jax.named_scope("attention_mixer"):
            q, k_new, v_new = attn.project_decode_qkv(bp["mixer"], x, dims,
                                                      pos_t, cfg.rope_theta)
            k_new = k_new.astype(k_slab.dtype)
            v_new = v_new.astype(v_slab.dtype)

            def attend(q, kn, vn, kc, vc, p):
                # one fleet row: its slots' cache of layer ``a`` with this
                # step's token written in
                kc, vc = attn.write_token(kc, kn, p), attn.write_token(vc,
                                                                       vn, p)
                return attn.decode_attend(bp["mixer"], q, kc, vc, p, dims,
                                          scale=_attn_scale(cfg))

            y = jax.vmap(attend)(
                rowwise(q), rowwise(k_new), rowwise(v_new),
                jax.lax.dynamic_index_in_dim(k_slab, a, 1, False),
                jax.lax.dynamic_index_in_dim(v_slab, a, 1, False), pos)
        h = (h + r * y.reshape(h.shape)).astype(h.dtype)
        h, routed = _moe(bp, h, cfg)
        return h, k_new[:, 0], v_new[:, 0], routed

    def no_attn(h, a):
        z = jnp.zeros((T, G, hd), k_slab.dtype)
        return h, z, z, jnp.zeros((T, held), jnp.int32)

    def step(c, t):
        """A layer slice (F, B, ...) of a state leaf, back to the slab's
        dtype, kept as it was in rows that do not commit."""
        t = t.reshape(c.shape).astype(c.dtype)
        if rows is None:
            return t
        return jnp.where(rows.reshape((F,) + (1,) * (c.ndim - 1)), t, c)

    def body(carry, xs):
        h, ssm, conv, routed = carry
        bp, j, ab = xs
        h, kt, vt, ra = jax.lax.cond(ab >= 0, attn_layer, no_attn, h,
                                     jnp.maximum(ab, 0))
        x = rms_norm(h, bp["norm"], cfg.norm_eps)
        s_j = jax.lax.dynamic_index_in_dim(ssm, j, 1, False)
        c_j = jax.lax.dynamic_index_in_dim(conv, j, 1, False)
        with jax.named_scope("mamba_mixer"):
            y, new = mamba2_decode(
                bp["mixer"], x, cfg,
                {"ssm": s_j.reshape((T,) + s_j.shape[2:]),
                 "conv": c_j.reshape((T,) + c_j.shape[2:])})
        ssm = jax.lax.dynamic_update_index_in_dim(
            ssm, step(s_j, new["ssm"]), j, 1)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, step(c_j, new["conv"]), j, 1)
        h = (h + r * y).astype(h.dtype)
        h, rm = _moe(bp, h, cfg)
        return (h, ssm, conv, routed + ra + rm), (kt, vt)

    (h, ssm, conv, routed), (kt, vt) = jax.lax.scan(
        body, (_embed(params, tokens.reshape(T, 1), cfg), state["ssm"],
               state["conv"], jnp.zeros((T, held), jnp.int32)),
        (params["mamba"], jnp.arange(len(before), dtype=jnp.int32),
         jnp.asarray(before, jnp.int32)))
    # each attention layer's new token, written at [row, layer, slot, pos]
    at = np.asarray([before.index(a) for a in range(len(a_ids))], np.int32)
    wpos = pos if rows is None else jnp.where(rows[:, None], pos, S)
    fi = jnp.arange(F)[:, None, None]
    ai = jnp.arange(len(a_ids))[None, :, None]
    bi = jnp.arange(B)[None, None, :]

    def write(c, toks):
        toks = toks[at].reshape((len(a_ids), F, B) + toks.shape[2:])
        return c.at[fi, ai, bi, wpos[:, None, :]].set(
            toks.swapaxes(0, 1), mode="drop")

    new_state = {"ssm": ssm, "conv": conv, "k": write(k_slab, kt),
                 "v": write(v_slab, vt)}
    logits = _logits(params, h, cfg)[:, 0].reshape(F, B, -1)
    pairs = cfg.num_layers * cfg.num_experts_per_tok
    counts = {"expert_tokens": routed.reshape(F, B, held),
              "routed_pairs": jnp.full((F, B), pairs, jnp.int32)}
    return logits, new_state, counts
