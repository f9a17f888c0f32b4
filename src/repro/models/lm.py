"""Decoder-only LM covering the dense / moe / vlm families.

Layers are stacked and applied with ``lax.scan`` (small HLO, bounded compile
time at 40-64 layers) with configurable remat. MoE archs with
``moe_every > 1`` scan over *layer groups* (one MoE sublayer + ``moe_every-1``
dense sublayers per group, llama4-maverick style).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.dims import PaddedDims
from repro.models.layers import gelu, he_init, rms_norm, silu
from repro.models.moe import init_moe, moe_apply


def _remat_policy(name: str):
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def init_mlp(key, d_model, d_ff, activation, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_gate": he_init(ks[0], (d_model, d_ff), dtype, d_model),
         "w_down": he_init(ks[2], (d_ff, d_model), dtype, d_ff)}
    if activation == "swiglu":
        p["w_up"] = he_init(ks[1], (d_model, d_ff), dtype, d_model)
    return p


def mlp_apply(p, x, activation):
    g = x @ p["w_gate"]
    h = silu(g) * (x @ p["w_up"]) if activation == "swiglu" else gelu(g)
    return h @ p["w_down"]


def _init_layer(key, cfg: ArchConfig, dims: PaddedDims, dtype, is_moe: bool):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": attn.init_attention(k1, cfg.d_model, dims,
                                    cfg.resolved_head_dim, cfg.qkv_bias, dtype),
        "ffn_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if is_moe:
        p["moe"] = init_moe(k2, cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                            cfg.num_experts, dtype, cfg.moe_shared_expert,
                            cfg.activation)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def _stack_layers(key, cfg, dims, dtype, n, is_moe):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _init_layer(k, cfg, dims, dtype, is_moe))(keys)


def init_lm(key, cfg: ArchConfig, dims: PaddedDims, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    params = {
        "embed": (jax.random.normal(ks[0], (dims.vocab, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = he_init(ks[1], (cfg.d_model, dims.vocab), dtype,
                                    cfg.d_model)
    if cfg.uses_moe and cfg.moe_every > 1:
        n_groups = cfg.num_layers // cfg.moe_every
        params["moe_layers"] = _stack_layers(ks[2], cfg, dims, dtype,
                                             n_groups, True)
        dense_keys = jax.random.split(ks[3], n_groups * (cfg.moe_every - 1))
        dense = jax.vmap(lambda k: _init_layer(k, cfg, dims, dtype, False))(
            dense_keys)
        params["dense_layers"] = jax.tree.map(
            lambda x: x.reshape(n_groups, cfg.moe_every - 1, *x.shape[1:]),
            dense)
    else:
        params["layers"] = _stack_layers(ks[2], cfg, dims, dtype,
                                         cfg.num_layers, cfg.uses_moe)
    if cfg.family == "vlm":
        params["patch_proj"] = he_init(ks[4], (cfg.d_model, cfg.d_model),
                                       dtype, cfg.d_model)
    return params


# ------------------------------------------------------------------ sublayers
def _attn_sublayer(lp, h, cfg, dims, positions, shard_fn):
    y = attn.attention(lp["attn"], rms_norm(h, lp["attn_norm"], cfg.norm_eps),
                       dims, positions=positions, rope_theta=cfg.rope_theta,
                       causal=True, shard_fn=shard_fn)
    return h + y


def _ffn_sublayer(lp, h, cfg, shard_fn):
    x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
    if "moe" in lp:
        y, aux = moe_apply(lp["moe"], x, num_experts=cfg.num_experts,
                           top_k=cfg.num_experts_per_tok,
                           capacity_factor=cfg.capacity_factor,
                           activation=cfg.activation, shard_fn=shard_fn)
        return h + y, aux
    return h + mlp_apply(lp["mlp"], x, cfg.activation), 0.0


def _layer(lp, h, cfg, dims, positions, shard_fn):
    h = _attn_sublayer(lp, h, cfg, dims, positions, shard_fn)
    h, aux = _ffn_sublayer(lp, h, cfg, shard_fn)
    if shard_fn is not None:
        h = shard_fn(h, "act_btd")
    return h, aux


# ------------------------------------------------------------------- forward
def _embed_inputs(params, cfg, dims, batch, dtype_ref):
    """Token (+ optional patch) embedding. Returns (h, positions, text_start)."""
    tok = params["embed"][batch["tokens"]]                  # (B,S,d)
    text_start = 0
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(tok.dtype) @ params["patch_proj"]
        tok = jnp.concatenate([patches, tok], axis=1)
        text_start = cfg.num_patches
    positions = jnp.arange(tok.shape[1], dtype=jnp.int32)
    return tok, positions, text_start


def lm_forward(params, batch, cfg: ArchConfig, dims: PaddedDims, *,
               remat="none", shard_fn=None, return_features=False):
    """Full-sequence forward. Returns (logits (B,S_total,V), aux_loss) — or
    (features (B,S_total,d), aux) with ``return_features`` (the chunked-CE
    loss path applies the LM head itself, so the (T,V) logits tensor is never
    materialized)."""
    h, positions, _ = _embed_inputs(params, cfg, dims, batch, None)
    if shard_fn is not None:
        h = shard_fn(h, "act_btd")

    def group_body(carry, lps):
        h, aux = carry
        if "moe_layers" in params:
            moe_lp, dense_lp = lps
            h, a = _layer(moe_lp, h, cfg, dims, positions, shard_fn)
            aux += a
            for j in range(cfg.moe_every - 1):
                sub = jax.tree.map(lambda x: x[j], dense_lp)
                h, _ = _layer(sub, h, cfg, dims, positions, shard_fn)
        else:
            h, a = _layer(lps, h, cfg, dims, positions, shard_fn)
            aux += a
        return (h, aux), None

    body = group_body
    pol = _remat_policy(remat)
    if pol is not None:
        body = jax.checkpoint(group_body, policy=pol)
    if "moe_layers" in params:
        xs = (params["moe_layers"], params["dense_layers"])
    else:
        xs = params["layers"]
    (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if return_features:
        return h, aux
    head = params.get("lm_head")
    logits = h @ head if head is not None else h @ params["embed"].T
    if shard_fn is not None:
        logits = shard_fn(logits, "logits")
    return logits, aux


# ---------------------------------------------------------------- serve path
def _is_int8(dtype) -> bool:
    """The string sentinel "int8" selects the quantized KV codec (per-token,
    per-head absmax scales — see ``repro.serving.kv_quant``)."""
    return isinstance(dtype, str) and dtype == "int8"


def lm_init_cache(cfg, dims, batch: int, max_len: int, dtype=jnp.bfloat16):
    n_layers = cfg.num_layers
    hd = cfg.resolved_head_dim
    if cfg.family == "vlm":
        max_len = max_len + cfg.num_patches
    if _is_int8(dtype):
        return {
            "k_q": jnp.zeros((n_layers, batch, max_len, dims.n_kv, hd),
                             jnp.int8),
            "v_q": jnp.zeros((n_layers, batch, max_len, dims.n_kv, hd),
                             jnp.int8),
            "k_s": jnp.ones((n_layers, batch, max_len, dims.n_kv),
                            jnp.float32),
            "v_s": jnp.ones((n_layers, batch, max_len, dims.n_kv),
                            jnp.float32),
        }
    return {
        "k": jnp.zeros((n_layers, batch, max_len, dims.n_kv, hd), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, dims.n_kv, hd), dtype),
    }


def lm_decode(params, cache, tokens, pos, cfg: ArchConfig, dims: PaddedDims, *,
              shard_fn=None, attn_backend=None, commit=None):
    """One decode step. tokens: (B,1) int32; pos: scalar int32 or (B,) int32
    (cache write index, counting any VLM patch prefix).
    ``attn_backend="pallas"`` reads the cache through the flash-decode
    kernel instead of the dense einsum (see ``attention.decode_attend``).
    ``commit`` (scalar bool) False computes the step but leaves the cache
    as it was.

    The stacked cache (L,B,S,G,hd) is read-only inside the layer scan,
    whose carry is the hidden state alone: each layer writes its new token
    into its own slice for its attention and returns the token as a scan
    output, and one scatter after the scan writes them all at [layer, row,
    pos[row]] (donate the cache for an in-place update). A cache carried
    through the scan would be rewritten slice by slice and, under the fleet
    ``vmap``, relaid layer-major on the way in and out.
    An int8 quantized cache (``k_q``/``v_q``/``k_s``/``v_s`` leaves) is
    detected from its structure: new tokens quantize on write, reads
    dequantize on the fly (the HBM stream is the int8 bytes + scales).
    """
    quant = "k_q" in cache
    if quant:
        from repro.serving.kv_quant import dequantize, quantize_kv
    backend = attn_backend or "einsum"
    h = params["embed"][tokens]                              # (B,1,d)
    me = cfg.moe_every if "moe_layers" in params else 1
    n_groups = cfg.num_layers // me

    def sublayer(h, lp, layer_idx):
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = attn.project_decode_qkv(lp["attn"], x, dims, pos,
                                                  cfg.rope_theta)
        if quant:
            new = quantize_kv(k_new, v_new)
        else:
            new = {"k": k_new.astype(cache["k"].dtype),
                   "v": v_new.astype(cache["v"].dtype)}
        lc = {n: attn.write_token(
                  jax.lax.dynamic_index_in_dim(c, layer_idx, 0, False),
                  new[n], pos)
              for n, c in cache.items()}
        if quant:
            kc = dequantize(lc["k_q"], lc["k_s"]).astype(q.dtype)
            vc = dequantize(lc["v_q"], lc["v_s"]).astype(q.dtype)
        else:
            kc, vc = lc["k"], lc["v"]
        y = attn.decode_attend(lp["attn"], q, kc, vc, pos, dims,
                               backend=backend)
        h = h + y
        h, _ = _ffn_sublayer(lp, h, cfg, shard_fn)
        return h, {n: t[:, 0] for n, t in new.items()}

    def body(h, xs):
        lps, g = xs
        toks = []
        for j in range(me):
            lp = lps if me == 1 else (
                lps[0] if j == 0
                else jax.tree.map(lambda x: x[j - 1], lps[1]))
            h, t = sublayer(h, lp, g * me + j)
            toks.append(t)
        return h, jax.tree.map(lambda *t: jnp.stack(t), *toks)

    if me == 1:
        xs = (params["layers"], jnp.arange(n_groups))
    else:
        xs = ((params["moe_layers"], params["dense_layers"]),
              jnp.arange(n_groups))
    h, toks = jax.lax.scan(body, h, xs)         # toks: (groups, me, B, ...)
    B, S = tokens.shape[0], jax.tree.leaves(cache)[0].shape[2]
    rows = jnp.arange(B)
    wpos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if commit is not None:
        wpos = jnp.where(commit, wpos, S)       # out of bounds: dropped
    new_cache = {
        n: c.at[:, rows, wpos].set(
            toks[n].reshape((cfg.num_layers,) + toks[n].shape[2:]),
            mode="drop")
        for n, c in cache.items()}
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = h @ head if head is not None else h @ params["embed"].T
    return logits[:, 0], new_cache


def lm_prefill(params, batch, cfg, dims, *, cache_len: int,
               cache_dtype=jnp.bfloat16, shard_fn=None):
    """Prefill: full forward + cache fill. Returns (last-token logits, cache,
    pos). Cache is a scan carry (in-place per-layer writes).

    ``batch["lengths"]`` (B,) marks the true prompt length per row when the
    token matrix is right-padded to a bucket length: logits are gathered at
    ``lengths-1`` and ``pos`` comes back per-row. Causal masking keeps real
    positions exact under trailing pads; pad K/V beyond ``pos`` is masked by
    the decode path until overwritten. (MoE capacity routing sees the pad
    tokens, so padded prefill is exact only when nothing drops.)

    ``cache_dtype="int8"`` runs the forward in f32 and quantizes the filled
    cache once at the end (prefill is compute-bound; only decode needs the
    int8 memory stream)."""
    quant = _is_int8(cache_dtype)
    h, positions, _ = _embed_inputs(params, cfg, dims, batch, None)
    cache = lm_init_cache(cfg, dims, h.shape[0], cache_len,
                          jnp.float32 if quant else cache_dtype)
    S = h.shape[1]
    me = cfg.moe_every if "moe_layers" in params else 1
    n_groups = cfg.num_layers // me

    def sublayer(h, lp, layer_idx, kc_full, vc_full):
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        kc = jax.lax.dynamic_index_in_dim(kc_full, layer_idx, 0, False)
        vc = jax.lax.dynamic_index_in_dim(vc_full, layer_idx, 0, False)
        y, filled = attn.prefill_attention(lp["attn"], x, dims,
                                           {"k": kc, "v": vc},
                                           rope_theta=cfg.rope_theta)
        kc_full = jax.lax.dynamic_update_index_in_dim(kc_full, filled["k"],
                                                      layer_idx, 0)
        vc_full = jax.lax.dynamic_update_index_in_dim(vc_full, filled["v"],
                                                      layer_idx, 0)
        h = h + y
        h, _ = _ffn_sublayer(lp, h, cfg, shard_fn)
        if shard_fn is not None:
            h = shard_fn(h, "act_btd")
        return h, kc_full, vc_full

    def body(carry, xs):
        h, kc_full, vc_full = carry
        lps, g = xs
        for j in range(me):
            lp = lps if me == 1 else (
                lps[0] if j == 0
                else jax.tree.map(lambda x: x[j - 1], lps[1]))
            h, kc_full, vc_full = sublayer(h, lp, g * me + j, kc_full,
                                           vc_full)
        return (h, kc_full, vc_full), None

    if me == 1:
        xs = (params["layers"], jnp.arange(n_groups))
    else:
        xs = ((params["moe_layers"], params["dense_layers"]),
              jnp.arange(n_groups))
    (h, new_k, new_v), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]), xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    lengths = batch.get("lengths")
    if lengths is None:
        last, pos = h[:, -1], S
    else:
        text_start = cfg.num_patches if cfg.family == "vlm" else 0
        idx = (text_start + lengths - 1).astype(jnp.int32)
        last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        pos = (text_start + lengths).astype(jnp.int32)
    logits = last @ head if head is not None else last @ params["embed"].T
    if quant:
        from repro.serving.kv_quant import quantize
        kq, ks = quantize(new_k)
        vq, vs = quantize(new_v)
        return logits, {"k_q": kq, "v_q": vq, "k_s": ks, "v_s": vs}, pos
    return logits, {"k": new_k, "v": new_v}, pos


def lm_prefill_chunk(params, cache, tokens, offsets, lengths, cfg, dims, *,
                     shard_fn=None):
    """Continue a prefill: run ``tokens`` (B,C) at per-row cache ``offsets``
    (B,) against an existing KV cache (leaves (L,B,S,G,hd)), writing the
    chunk's K/V at [offset, offset+length) and attending causally over the
    whole prefix. ``lengths`` (B,) is each row's true token count within the
    chunk (rows are right-padded to the fixed chunk width). Returns
    (last-real-token logits (B,V), cache, pos (B,) = offset+length).

    Chunk-by-chunk equals single-shot prefill exactly: causal attention
    decomposes over chunks, pad columns never write (parked out of bounds)
    and stale cache beyond a row's frontier is masked by ``k_pos <= q_pos``.
    Only the float cache codec is supported (the int8 path quantizes whole
    prompts at prefill end; the engine routes int8 replicas to single-shot).
    """
    assert "k_q" not in cache, "chunked prefill requires a float KV cache"
    h = params["embed"][tokens]
    C = tokens.shape[1]
    posmat = offsets[:, None].astype(jnp.int32) + \
        jnp.arange(C, dtype=jnp.int32)[None, :]
    me = cfg.moe_every if "moe_layers" in params else 1
    n_groups = cfg.num_layers // me

    def sublayer(h, lp, layer_idx, kc_full, vc_full):
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        kc = jax.lax.dynamic_index_in_dim(kc_full, layer_idx, 0, False)
        vc = jax.lax.dynamic_index_in_dim(vc_full, layer_idx, 0, False)
        y, filled = attn.chunk_prefill_attention(
            lp["attn"], x, dims, {"k": kc, "v": vc}, posmat, lengths,
            rope_theta=cfg.rope_theta)
        kc_full = jax.lax.dynamic_update_index_in_dim(kc_full, filled["k"],
                                                      layer_idx, 0)
        vc_full = jax.lax.dynamic_update_index_in_dim(vc_full, filled["v"],
                                                      layer_idx, 0)
        h = h + y
        h, _ = _ffn_sublayer(lp, h, cfg, shard_fn)
        if shard_fn is not None:
            h = shard_fn(h, "act_btd")
        return h, kc_full, vc_full

    def body(carry, xs):
        h, kc_full, vc_full = carry
        lps, g = xs
        for j in range(me):
            lp = lps if me == 1 else (
                lps[0] if j == 0
                else jax.tree.map(lambda x: x[j - 1], lps[1]))
            h, kc_full, vc_full = sublayer(h, lp, g * me + j, kc_full,
                                           vc_full)
        return (h, kc_full, vc_full), None

    if me == 1:
        xs = (params["layers"], jnp.arange(n_groups))
    else:
        xs = ((params["moe_layers"], params["dense_layers"]),
              jnp.arange(n_groups))
    (h, new_k, new_v), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]), xs)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    idx = (lengths - 1).astype(jnp.int32)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
    logits = last @ head if head is not None else last @ params["embed"].T
    pos = (offsets + lengths).astype(jnp.int32)
    return logits, {"k": new_k, "v": new_v}, pos
