"""Random weights from a seed, made on the device in one jitted call, in the
layout the repo's ``hybrid_moe`` family takes and the type it serves.

The reference draws the same numbers one layer at a time (``layer``), so it
never takes what the program was given: both sides call these functions with
the seed. Layer l's key is ``fold_in(root, l)``; expert e of a layer is drawn
from ``fold_in(<the layer's expert key>, e)`` with e its index among all the
router's experts, so an expert has the same weights whichever share of the
experts holds it.

Matrices are normal / sqrt(fan_in) in the served dtype; norm scales are
float32 draws of 0.1·normal (the blocks apply ``1 + scale``); the Mamba
scalars follow Mamba-2's initialisation (A in [1, 16], dt in [1e-3, 1e-1]
through the inverse softplus, D near 1), and the conv bias is 0.1·normal.
The embedding is 0.002·normal: with the ×12 embedding multiplier and the
tied head, 0.02 would give each token's own row a logit some 11 standard
deviations above the rest, so every model, the float8 control included,
would predict its input token and the comparison with the reference would
test nothing; at 0.002 that margin is about one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from modelkit import normal, root_key

NORM_STD = 0.1
EMBED_STD = 0.002


def kinds(cfg: dict) -> list:
    """The mixer of each of the file's ``num_hidden_layers`` layers."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _std(key, shape, std=NORM_STD):
    return std * jax.random.normal(key, shape, jnp.float32)


def _mamba(cfg: dict, key, dtype) -> dict:
    d, H = cfg["hidden_size"], cfg["mamba_n_heads"]
    di = cfg["mamba_expand"] * d
    N, G, W = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    conv_ch = di + 2 * G * N
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[6], (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": normal(ks[0], (d, 2 * di + 2 * G * N + H), d, dtype),
        "conv_w": normal(ks[1], (W, conv_ch), W, dtype),
        "conv_b": _std(ks[2], (conv_ch,)).astype(dtype),
        "out_proj": normal(ks[3], (di, d), di, dtype),
        "A_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32,
                                            1.0, 16.0)),
        "D": 1.0 + _std(ks[5], (H,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm_scale": _std(ks[7], (di,)),
    }


def _attention(cfg: dict, key, dtype) -> dict:
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg["head_dim"]
    ks = jax.random.split(key, 4)
    return {"wq": normal(ks[0], (d, h, hd), d, dtype),
            "wk": normal(ks[1], (d, kv, hd), d, dtype),
            "wv": normal(ks[2], (d, kv, hd), d, dtype),
            "wo": normal(ks[3], (h, hd, d), h * hd, dtype)}


def expert(cfg: dict, key, e, dtype) -> tuple:
    """Expert ``e`` (of all the router's experts): gate, up, down."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, e), 3)
    return (normal(ks[0], (d, f), d, dtype), normal(ks[1], (d, f), d, dtype),
            normal(ks[2], (f, d), f, dtype))


def _moe(cfg: dict, key, dtype) -> dict:
    d, fs = cfg["hidden_size"], cfg["shared_intermediate_size"]
    first, held = cfg["first_held_expert"], cfg["num_local_experts"]
    k_router, k_experts, k_shared = jax.random.split(key, 3)
    g, u, dn = jax.vmap(lambda e: expert(cfg, k_experts, e, dtype))(
        first + jnp.arange(held))
    ks = jax.random.split(k_shared, 3)
    return {"router": normal(k_router, (d, cfg["router_outputs"]), d, dtype),
            "w_gate": g, "w_up": u, "w_down": dn,
            "shared": {"w_gate": normal(ks[0], (d, fs), d, dtype),
                       "w_up": normal(ks[1], (d, fs), d, dtype),
                       "w_down": normal(ks[2], (fs, d), fs, dtype)}}


def layer(cfg: dict, key, index, kind: str, dtype=jnp.bfloat16) -> dict:
    """Layer ``index``'s block (its ``kind`` of mixer, its norms and its MoE
    FFN), keyed ``fold_in(key, index)``."""
    d = cfg["hidden_size"]
    ks = jax.random.split(jax.random.fold_in(key, index), 4)
    mixer = _mamba if kind == "mamba" else _attention
    return {"norm": _std(ks[0], (d,)), "ffn_norm": _std(ks[1], (d,)),
            "mixer": mixer(cfg, ks[2], dtype), "moe": _moe(cfg, ks[3], dtype)}


def outer(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, -1 & 0x7FFFFFFF), 3)
    out = {"embed": _std(ks[0], (v, d), EMBED_STD).astype(dtype),
           "final_norm": _std(ks[1], (d,))}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = normal(ks[2], (d, v), d, dtype)
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every weight in one jitted call on the device: the Mamba blocks and
    the attention blocks each stacked in layer order, drawn one layer at a
    time (``lax.map``) so that no float32 draw of a whole stack is ever
    held beside the weights."""
    ids = {k: [i for i, t in enumerate(kinds(cfg)) if t == k]
           for k in ("mamba", "attention")}

    def build(key):
        params = outer(cfg, key, dtype)
        for name, kind in (("mamba", "mamba"), ("attn", "attention")):
            params[name] = jax.lax.map(
                lambda i, kind=kind: layer(cfg, key, i, kind, dtype),
                jnp.asarray(ids[kind]))
        return params

    return jax.jit(build)(root_key(seed))
