"""Int8 KV-cache quantization (per-token, per-head scales).

Backs §Perf cell C iteration 2: halves the decode memory stream. Writes
quantize each new (token, head) k/v vector to int8 with an f32 absmax scale;
reads dequantize on the fly (the matmul runs in bf16/f32 — v5e has no int8
MXU path exposed via XLA, so the win is HBM bytes, which is exactly what
decode is bound by).

Error model: absmax int8 over head_dim-sized vectors keeps relative error
~0.4%/√d; the attention-output error bound is checked by
tests/test_kv_quant.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import write_token


def quantize(x, axis=-1):
    """x: (..., d) -> (int8 values, f32 scales with `axis` reduced)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                     keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.squeeze(axis).astype(jnp.float32)


def dequantize(q, scale, axis=-1):
    return q.astype(jnp.float32) * jnp.expand_dims(scale, axis)


def init_quant_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int):
    """Quantized analogue of attention.init_kv_cache."""
    return {
        "k_q": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
        "v_q": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
        "k_s": jnp.ones((batch, max_len, n_kv), jnp.float32),
        "v_s": jnp.ones((batch, max_len, n_kv), jnp.float32),
    }


def quantize_kv(k_new, v_new) -> dict:
    """One token's k/v (B, 1, G, d) as the quantized cache's four leaves."""
    kq, ks = quantize(k_new)
    vq, vs = quantize(v_new)
    return {"k_q": kq, "v_q": vq, "k_s": ks, "v_s": vs}


def write_kv_quant(cache, k_new, v_new, pos):
    """Write one token's k/v (B, 1, G, d) at `pos` (scalar, or (B,) for the
    per-slot vector-``pos`` serving path)."""
    new = quantize_kv(k_new, v_new)
    return {n: write_token(cache[n], new[n], pos) for n in cache}


def decode_attend_quant(q, cache, pos):
    """Single-token GQA attention over the quantized cache.

    q: (B, G, qpg, d); returns (B, G, qpg, d). Dequantizes K/V tile-wise —
    on TPU the dequant fuses into the VMEM load epilogue, so HBM traffic is
    the int8 bytes + scales (~half of bf16).
    """
    import numpy as np
    k = dequantize(cache["k_q"], cache["k_s"])      # (B, S, G, d) f32
    v = dequantize(cache["v_q"], cache["v_s"])
    d = q.shape[-1]
    s = jnp.einsum("bgqh,btgh->bgqt", q.astype(jnp.float32), k) / np.sqrt(d)
    mask = jnp.arange(k.shape[1]) <= pos
    s = jnp.where(mask[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgqt,btgh->bgqh", p, v).astype(q.dtype)
