"""Random weights from a seed, made on the device in one jitted call, in the
layout the repo's dense decoder takes and the type it serves.

The reference draws the same numbers one layer at a time (``layer``), so it
never takes what the program was given: both sides call these functions with
the seed. Every layer's key is ``fold_in(root, layer)``, so one layer's draw
equals its slice of the stacked draw (to float32 rounding of the normal
transform, which the cast to bf16 absorbs).

Matrices are normal / sqrt(fan_in) in bf16; norm scales are float32 draws of
0.1·normal (the block applies ``1 + scale``), so a path that dropped a norm's
scale would not match the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from modelkit import normal, root_key

NORM_STD = 0.1
EMBED_STD = 0.02


def layer(cfg: dict, key, index, dtype=jnp.bfloat16) -> dict:
    """Layer ``index``'s weights, keyed ``fold_in(key, index)``."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, index), 9)
    return {
        "attn_norm": NORM_STD * jax.random.normal(ks[0], (d,), jnp.float32),
        "attn": {
            "wq": normal(ks[1], (d, h, hd), d, dtype),
            "wk": normal(ks[2], (d, kv, hd), d, dtype),
            "wv": normal(ks[3], (d, kv, hd), d, dtype),
            "wo": normal(ks[4], (h, hd, d), h * hd, dtype),
        },
        "ffn_norm": NORM_STD * jax.random.normal(ks[5], (d,), jnp.float32),
        "mlp": {
            "w_gate": normal(ks[6], (d, ff), d, dtype),
            "w_up": normal(ks[7], (d, ff), d, dtype),
            "w_down": normal(ks[8], (ff, d), ff, dtype),
        },
    }


def outer(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, -1 & 0x7FFFFFFF), 3)
    out = {
        "embed": (EMBED_STD * jax.random.normal(ks[0], (v, d), jnp.float32)
                  ).astype(dtype),
        "final_norm": NORM_STD * jax.random.normal(ks[1], (d,), jnp.float32),
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = normal(ks[2], (d, v), d, dtype)
    return out


def make_params(cfg: dict, seed: int, sharding=None, dtype=jnp.bfloat16):
    """Every weight in one jitted call on the device (replicated over a mesh
    when ``sharding`` is given)."""
    n = cfg["num_hidden_layers"]

    def build(key):
        params = outer(cfg, key, dtype)
        params["layers"] = jax.vmap(lambda i: layer(cfg, key, i, dtype))(
            jnp.arange(n))
        return params

    fn = jax.jit(build) if sharding is None else \
        jax.jit(build, out_shardings=sharding)
    return fn(root_key(seed))
