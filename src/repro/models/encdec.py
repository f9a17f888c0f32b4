"""Whisper-style encoder-decoder (audio family).

The conv/mel frontend is a stub per the brief: inputs are precomputed frame
embeddings (B, encoder_seq_len, d_model). Encoder adds sinusoidal positions;
decoder uses learned positions, causal self-attention and cross-attention to
the encoder output. LayerNorm + GELU (original Whisper choices).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.dims import PaddedDims
from repro.models.layers import he_init, layer_norm, sinusoidal_positions
from repro.models.lm import init_mlp, mlp_apply, _remat_policy


def _ln_init(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _enc_layer_init(key, cfg, dims, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": _ln_init(cfg.d_model),
        "attn": attn.init_attention(k1, cfg.d_model, dims,
                                    cfg.resolved_head_dim, True, dtype),
        "ffn_norm": _ln_init(cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype),
    }


def _dec_layer_init(key, cfg, dims, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = _enc_layer_init(k1, cfg, dims, dtype)
    p["cross_norm"] = _ln_init(cfg.d_model)
    p["cross"] = attn.init_attention(k2, cfg.d_model, dims,
                                     cfg.resolved_head_dim, True, dtype)
    return p


def init_encdec(key, cfg: ArchConfig, dims: PaddedDims, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "embed": (jax.random.normal(ks[0], (dims.vocab, cfg.d_model))
                  * 0.02).astype(dtype),
        "dec_pos": (jax.random.normal(ks[1], (cfg.max_decoder_pos,
                                              cfg.d_model)) * 0.01).astype(dtype),
        "enc_layers": jax.vmap(
            lambda k: _enc_layer_init(k, cfg, dims, dtype))(
                jax.random.split(ks[2], cfg.encoder_layers)),
        "enc_final_norm": _ln_init(cfg.d_model),
        "dec_layers": jax.vmap(
            lambda k: _dec_layer_init(k, cfg, dims, dtype))(
                jax.random.split(ks[3], cfg.num_layers)),
        "dec_final_norm": _ln_init(cfg.d_model),
    }


def _ln(x, p, eps):
    return layer_norm(x, p["scale"], p["bias"], eps)


def encode(params, frame_embeds, cfg, dims, *, remat="none", shard_fn=None):
    pos = jnp.asarray(sinusoidal_positions(frame_embeds.shape[1], cfg.d_model))
    h = frame_embeds + pos.astype(frame_embeds.dtype)[None]

    def body(h, lp):
        x = _ln(h, lp["attn_norm"], cfg.norm_eps)
        h = h + attn.attention(lp["attn"], x, dims, rope_theta=0.0,
                               causal=False, shard_fn=shard_fn)
        x = _ln(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + mlp_apply(lp["mlp"], x, cfg.activation)
        return h, None

    pol = _remat_policy(remat)
    fn = jax.checkpoint(body, policy=pol) if pol is not None else body
    h, _ = jax.lax.scan(fn, h, params["enc_layers"])
    return _ln(h, params["enc_final_norm"], cfg.norm_eps)


def _decoder_stack(params, h, enc_out, cfg, dims, *, remat="none",
                   shard_fn=None):
    def body(h, lp):
        x = _ln(h, lp["attn_norm"], cfg.norm_eps)
        h = h + attn.attention(lp["attn"], x, dims, rope_theta=0.0,
                               causal=True, shard_fn=shard_fn)
        x = _ln(h, lp["cross_norm"], cfg.norm_eps)
        h = h + attn.attention(lp["cross"], x, dims, rope_theta=0.0,
                               causal=False, kv_x=enc_out, shard_fn=shard_fn)
        x = _ln(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + mlp_apply(lp["mlp"], x, cfg.activation)
        return h, None

    pol = _remat_policy(remat)
    fn = jax.checkpoint(body, policy=pol) if pol is not None else body
    h, _ = jax.lax.scan(fn, h, params["dec_layers"])
    return _ln(h, params["dec_final_norm"], cfg.norm_eps)


def encdec_forward(params, batch, cfg: ArchConfig, dims: PaddedDims, *,
                   remat="none", shard_fn=None, return_features=False):
    """Training forward (teacher forcing). batch: frame_embeds + tokens."""
    enc_out = encode(params, batch["frame_embeds"], cfg, dims, remat=remat,
                     shard_fn=shard_fn)
    toks = batch["tokens"]
    S = toks.shape[1]
    h = params["embed"][toks] + params["dec_pos"][:S][None]
    h = _decoder_stack(params, h, enc_out, cfg, dims, remat=remat,
                       shard_fn=shard_fn)
    if return_features:
        return h, jnp.float32(0.0)
    logits = h @ params["embed"].T
    if shard_fn is not None:
        logits = shard_fn(logits, "logits")
    return logits, jnp.float32(0.0)


# ------------------------------------------------------------------ serving
def encdec_init_state(cfg, dims, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    L, Le = cfg.num_layers, cfg.encoder_seq_len
    return {
        "self_k": jnp.zeros((L, batch, max_len, dims.n_kv, hd), dtype),
        "self_v": jnp.zeros((L, batch, max_len, dims.n_kv, hd), dtype),
        "cross_k": jnp.zeros((L, batch, Le, dims.n_kv, hd), dtype),
        "cross_v": jnp.zeros((L, batch, Le, dims.n_kv, hd), dtype),
    }


def encdec_prefill(params, batch, cfg, dims, *, cache_len: int,
                   cache_dtype=jnp.bfloat16, shard_fn=None):
    """Encode + decoder prefill. Returns (last logits, state, pos)."""
    enc_out = encode(params, batch["frame_embeds"], cfg, dims,
                     shard_fn=shard_fn)
    toks = batch["tokens"]
    B, S = toks.shape
    h = params["embed"][toks] + params["dec_pos"][:S][None]
    state = encdec_init_state(cfg, dims, B, cache_len, cache_dtype)

    def body(carry, xs):
        h, sk_full, sv_full = carry
        lp, idx = xs
        x = _ln(h, lp["attn_norm"], cfg.norm_eps)
        kc = jax.lax.dynamic_index_in_dim(sk_full, idx, 0, False)
        vc = jax.lax.dynamic_index_in_dim(sv_full, idx, 0, False)
        y, filled = attn.prefill_attention(lp["attn"], x, dims,
                                           {"k": kc, "v": vc}, rope_theta=0.0)
        sk_full = jax.lax.dynamic_update_index_in_dim(sk_full, filled["k"],
                                                      idx, 0)
        sv_full = jax.lax.dynamic_update_index_in_dim(sv_full, filled["v"],
                                                      idx, 0)
        h = h + y
        x = _ln(h, lp["cross_norm"], cfg.norm_eps)
        ck = attn.project(lp["cross"], "wk", enc_out) + lp["cross"]["bk"]
        cv = attn.project(lp["cross"], "wv", enc_out) + lp["cross"]["bv"]
        h = h + attn.attention(lp["cross"], x, dims, rope_theta=0.0,
                               causal=False, kv_x=enc_out)
        x = _ln(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + mlp_apply(lp["mlp"], x, cfg.activation)
        return (h, sk_full, sv_full), (ck.astype(cache_dtype),
                                       cv.astype(cache_dtype))

    (h, sk, sv), (ck, cv) = jax.lax.scan(
        body, (h, state["self_k"], state["self_v"]),
        (params["dec_layers"], jnp.arange(cfg.num_layers)))
    h = _ln(h, params["dec_final_norm"], cfg.norm_eps)
    logits = h[:, -1] @ params["embed"].T
    return logits, {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}, S


def _cross_decode(lp, x, dims, ck, cv):
    """Cross-attn for one query token against cached encoder k/v."""
    q = attn.project(lp["cross"], "wq", x) + lp["cross"]["bq"]
    B = x.shape[0]
    q = q.reshape(B, 1, dims.n_kv, dims.q_per_group, -1)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.einsum("bsgqh,btgh->bgqst", q, ck.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgqst,btgh->bsgqh", probs.astype(cv.dtype), cv)
    from repro.models.attention import _mask_pad_heads
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, 1, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, lp["cross"]["wo"])


def encdec_decode(params, state, tokens, pos, cfg, dims, *, shard_fn=None):
    """One decode step. Returns (logits (B,V), state)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:  # per-sequence positions (continuous batching)
        pe = params["dec_pos"][pos][:, None]
    else:
        pe = jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1, 0)[None]
    h = params["embed"][tokens] + pe

    def body(carry, xs):
        h, sk_full, sv_full = carry
        lp, ck, cv, idx = xs
        x = _ln(h, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = attn.project_decode_qkv(lp["attn"], x, dims, pos,
                                                  0.0)
        kc = jax.lax.dynamic_index_in_dim(sk_full, idx, 0, False)
        vc = jax.lax.dynamic_index_in_dim(sv_full, idx, 0, False)
        kc, vc = attn.write_kv(kc, vc, k_new, v_new, pos)
        sk_full = jax.lax.dynamic_update_index_in_dim(sk_full, kc, idx, 0)
        sv_full = jax.lax.dynamic_update_index_in_dim(sv_full, vc, idx, 0)
        h = h + attn.decode_attend(lp["attn"], q, kc, vc, pos, dims)
        x = _ln(h, lp["cross_norm"], cfg.norm_eps)
        h = h + _cross_decode(lp, x, dims, ck, cv)
        x = _ln(h, lp["ffn_norm"], cfg.norm_eps)
        h = h + mlp_apply(lp["mlp"], x, cfg.activation)
        return (h, sk_full, sv_full), None

    (h, sk, sv), _ = jax.lax.scan(
        body, (h, state["self_k"], state["self_v"]),
        (params["dec_layers"], state["cross_k"], state["cross_v"],
         jnp.arange(cfg.num_layers)))
    h = _ln(h, params["dec_final_norm"], cfg.norm_eps)
    logits = h[:, 0] @ params["embed"].T
    return logits, {"self_k": sk, "self_v": sv,
                    "cross_k": state["cross_k"], "cross_v": state["cross_v"]}
