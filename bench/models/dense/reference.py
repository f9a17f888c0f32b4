"""The plain float32 reference of the served decoder, written from the layer
equations and independent of the program's model code.

For each layer l (pre-norm, residual):

    x  = rmsnorm(h) * (1 + g_attn)            rmsnorm(h) = h / sqrt(mean(h^2) + eps)
    q, k, v = x Wq, x Wk, x Wv                 GQA: head i reads kv head i // (H / KV)
    q, k = rope(q), rope(k)                    rotate-half, theta from the config
    a  = softmax(q k^T / sqrt(head_dim) + causal mask) v
    h  = h + a Wo
    x  = rmsnorm(h) * (1 + g_ffn)
    h  = h + (silu(x Wgate) * (x Wup)) Wdown

then logits = (rmsnorm(h) * (1 + g_final)) Whead, with Whead the embedding's
transpose when the head is tied. Everything is float32 with matrix products
at ``precision="highest"``; the weights are the seed's bf16 draws
(``weights.py``), made again here one layer at a time.

Departures from the published models, shared with the program: granite's
muP multipliers (embedding 12, attention 1/128, residual 0.22, logits 1/16)
are not modelled; attention scales by 1/sqrt(head_dim) and the others are 1.

``lower`` switches the reference to the control: every matrix product takes
its operands rounded to float8 (e4m3, per-tensor absmax scale) and
accumulates in float32, the precision step below the configuration's bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from modelkit import root_key, round_f8

from . import weights


def _mm(spec, a, b, lower):
    if lower:
        a, b = round_f8(a), round_f8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rmsnorm(h, g, eps):
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return h * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"))
def _layer(h, w, *, cfg_items, lower):
    cfg = dict(cfg_items)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h_heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f32 = lambda t: t.astype(jnp.float32)
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rope(_mm("bsd,dnh->bsnh", x, f32(w["attn"]["wq"]), lower), theta)
    k = _rope(_mm("bsd,dnh->bsnh", x, f32(w["attn"]["wk"]), lower), theta)
    v = _mm("bsd,dnh->bsnh", x, f32(w["attn"]["wv"]), lower)
    rep = h_heads // kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = _mm("bqnh,bknh->bnqk", q, k, lower) / np.sqrt(q.shape[-1])
    S = h.shape[1]
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("bnqk,bknh->bqnh", p, v, lower)
    h = h + _mm("bqnh,nhd->bqd", a, f32(w["attn"]["wo"]), lower)
    x = _rmsnorm(h, w["ffn_norm"], eps)
    g = _mm("bsd,df->bsf", x, f32(w["mlp"]["w_gate"]), lower)
    u = _mm("bsd,df->bsf", x, f32(w["mlp"]["w_up"]), lower)
    return h + _mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                   f32(w["mlp"]["w_down"]), lower)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"))
def _head(h, outer, *, cfg_items, lower):
    cfg = dict(cfg_items)
    x = _rmsnorm(h, outer["final_norm"], cfg["rms_norm_eps"])
    head = outer.get("lm_head")
    head = outer["embed"].T if head is None else head
    return _mm("bsd,dv->bsv", x, head.astype(jnp.float32), lower)


_ARCH_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "tie_word_embeddings", "rope_theta",
              "rms_norm_eps")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _ARCH_KEYS)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(cfg, key, index, dtype):
    return weights.layer(dict(cfg), key, index, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _outer_weights(cfg, key, dtype):
    return weights.outer(dict(cfg), key, dtype)


def logits(cfg: dict, seed: int, tokens: np.ndarray, *, lower=False,
           dtype=jnp.bfloat16):
    """Float32 logits (B, S, V), on the device, of right-padded ``tokens``
    (B, S): causal, so the logits at real positions do not see the padding.
    The weights are drawn again from ``seed`` one layer at a time."""
    items = _items(cfg)
    key = root_key(seed)
    outer = _outer_weights(items, key, dtype)
    h = outer["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(items, key, i, dtype)
        h = _layer(h, w, cfg_items=items, lower=lower)
        del w
    return _head(h, outer, cfg_items=items, lower=lower)
