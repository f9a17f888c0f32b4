"""GQA attention (prefill, chunked-prefill, decode-with-cache, cross-attn).

Layout is *grouped*: q is (B, S, G, qpg, hd) where G = physical kv heads and
qpg = physical q-heads-per-group (see repro.models.dims). This keeps the TP
sharding of q and kv heads aligned on the same mesh axis ("model") and makes
GQA exact under kv replication.

Long sequences (S >= CHUNK_THRESHOLD) use query-chunked attention via
``lax.scan`` so the (S × S) score matrix is never materialized — each chunk
sees the full key set, so a plain per-row softmax is exact (no online-softmax
needed at this level; the Pallas flash kernel tiles the KV axis too).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.dims import PaddedDims, q_head_mask
from repro.models.layers import apply_rope, he_init

CHUNK_THRESHOLD = 8192
Q_CHUNK = 1024
NEG_INF = -1e9


def init_attention(key, d_model: int, dims: PaddedDims, head_dim: int,
                   qkv_bias: bool, dtype) -> dict:
    ks = jax.random.split(key, 4)
    mask = q_head_mask(dims)  # zero-out padded q heads at init
    p = {
        "wq": he_init(ks[0], (d_model, dims.n_q, head_dim), dtype, d_model)
              * mask[None, :, None].astype(dtype),
        "wk": he_init(ks[1], (d_model, dims.n_kv, head_dim), dtype, d_model),
        "wv": he_init(ks[2], (d_model, dims.n_kv, head_dim), dtype, d_model),
        "wo": he_init(ks[3], (dims.n_q, head_dim, d_model), dtype,
                      dims.n_q * head_dim),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((dims.n_q, head_dim), dtype)
        p["bk"] = jnp.zeros((dims.n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((dims.n_kv, head_dim), dtype)
    return p


# The served layout of the q/k/v projections: heads, head_dim, d_model,
# the order the TPU's matmul reads, with ``wq``'s heads split into its kv
# groups as the grouped q is, (G, qpg, head_dim, d_model). In the
# published (d_model, heads, head_dim) order a layer scan materialises
# each layer's slice and relays it d_model-minor before every projection,
# in every decode and prefill call; with ``wq``'s heads as one axis it
# still materialises the ``wq`` slice to regroup them. In this order it
# reads every slice in place.
SERVED_QKV = {"wq": "wq_served", "wk": "wk_served", "wv": "wv_served"}


def serve_layout(params):
    """``params`` with every attention's ``wq``/``wk``/``wv``, kept in the
    published (d_model, heads, head_dim) order (after any stacked layer
    axes) as training, checkpoints and ``init`` make them, replaced by
    ``wq_served`` (G, qpg, head_dim, d_model), G the kv heads of ``wk``,
    and ``wk_served``/``wv_served`` (G, head_dim, d_model). Every other
    leaf is the same object as in ``params``, so a tree already in the
    served layout keeps every leaf. The serving engine calls this once per
    weights object (``serving.engine.served_weights``)."""
    if not isinstance(params, dict):
        return params
    if all(n in params for n in SERVED_QKV):
        out = {k: v for k, v in params.items() if k not in SERVED_QKV}
        for n, served in SERVED_QKV.items():
            out[served] = jnp.moveaxis(params[n], -3, -1)
        wq, g = out["wq_served"], params["wk"].shape[-2]
        out["wq_served"] = wq.reshape(wq.shape[:-3] + (g, -1)
                                      + wq.shape[-2:])
        return out
    return {k: serve_layout(v) for k, v in params.items()}


def project(params, name: str, x):
    """x (B,S,d) through projection ``name`` (``wq``, ``wk`` or ``wv``) to
    (B,S,heads,head_dim): from its served leaf when ``params`` holds one
    (``serve_layout``), else from the published (d_model, heads, head_dim)
    leaf."""
    w = params.get(SERVED_QKV[name])
    if w is None:
        return jnp.einsum("bsd,dnh->bsnh", x, params[name])
    # wq gives (B,S,G,qpg,hd); ``_project_qkv`` regroups what this merges,
    # and the two reshapes fold away, leaving the weight's slice in place
    y = jnp.einsum("bsd,...hd->bs...h", x, w)
    return y.reshape(y.shape[:2] + (-1, y.shape[-1]))


def _project_qkv(params, x, kv_x, dims: PaddedDims):
    """q grouped (B,S,G,qpg,hd), k and v (B,S,G,hd). The weights may be in
    either order: the served leaves are taken when present (see
    ``serve_layout``), detected from the tree's keys."""
    q = project(params, "wq", x)
    k = project(params, "wk", kv_x)
    v = project(params, "wv", kv_x)
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    B, S = q.shape[:2]
    q = q.reshape(B, S, dims.n_kv, dims.q_per_group, q.shape[-1])
    return q, k, v


def _mask_pad_heads(ctx, dims: PaddedDims):
    """Zero the padded q-head outputs so they are exactly inert."""
    if all(dims.q_real):
        return ctx
    m = jnp.asarray(q_head_mask(dims).reshape(dims.n_kv, dims.q_per_group))
    return ctx * m[None, None, :, :, None].astype(ctx.dtype)


def _scale(scale, head_dim: int):
    """The softmax scale: ``scale``, or 1/sqrt(head_dim) when it is None."""
    return 1.0 / np.sqrt(head_dim) if scale is None else scale


def _attend(q, k, v, q_pos, k_pos, causal: bool, scale=None):
    """q: (B,Cq,G,qpg,hd); k,v: (B,T,G,hd); positions are int32 vectors."""
    scale = _scale(scale, q.shape[-1])
    scores = jnp.einsum("bsgqh,btgh->bgqst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]          # (Cq, T)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgqst,btgh->bsgqh", probs.astype(v.dtype), v)
    return ctx


def _attend_maybe_chunked(q, k, v, positions, k_pos, causal, scale=None):
    """Query-chunked attention when S is long; exact either way.

    Non-multiple S is zero-padded on the query axis (padded rows are computed
    against position 0 and sliced off — keys are never padded, so real rows
    are exact)."""
    B, S = q.shape[:2]
    if S < CHUNK_THRESHOLD:
        return _attend(q, k, v, positions, k_pos, causal, scale)
    S_pad = ((S + Q_CHUNK - 1) // Q_CHUNK) * Q_CHUNK
    if S_pad != S:
        pad = S_pad - S
        q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * (q.ndim - 2))
        positions = jnp.pad(positions, (0, pad))
    n_chunks = S_pad // Q_CHUNK
    q_chunks = q.reshape(B, n_chunks, Q_CHUNK, *q.shape[2:]).swapaxes(0, 1)
    pos_chunks = positions.reshape(n_chunks, Q_CHUNK)

    def body(_, qc_pc):
        qc, pc = qc_pc
        return None, _attend(qc, k, v, pc, k_pos, causal, scale)

    _, ctx = jax.lax.scan(body, None, (q_chunks, pos_chunks))
    return ctx.swapaxes(0, 1).reshape(B, S_pad, *q.shape[2:])[:, :S]


def attention(params, x, dims: PaddedDims, *, positions=None, rope_theta=0.0,
              causal=True, kv_x=None, shard_fn=None, scale=None):
    """Full-sequence (training / prefill) attention. Returns (B,S,d_model).
    ``scale`` is the softmax scale (None: 1/sqrt(head_dim))."""
    B, S, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    T = kv_x.shape[1]
    q, k, v = _project_qkv(params, x, kv_x, dims)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    k_pos = jnp.arange(T, dtype=jnp.int32)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, k_pos, rope_theta)
    if shard_fn is not None:
        q, k, v = shard_fn(q, "qkv"), shard_fn(k, "kv"), shard_fn(v, "kv")
    ctx = _attend_maybe_chunked(q, k, v, positions, k_pos, causal, scale)
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, S, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, params["wo"])


def init_kv_cache(batch: int, max_len: int, dims: PaddedDims, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, dims.n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, dims.n_kv, head_dim), dtype),
    }


def prefill_attention(params, x, dims: PaddedDims, cache, *, rope_theta=0.0,
                      shard_fn=None, scale=None):
    """Attention that also fills the KV cache for positions [0, S).
    ``scale`` is the softmax scale (None: 1/sqrt(head_dim))."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, x, dims)
    positions = jnp.arange(S, dtype=jnp.int32)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1),
    }
    ctx = _attend_maybe_chunked(q, k, v, positions, positions, causal=True,
                                scale=scale)
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, S, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, params["wo"]), cache


def chunk_prefill_attention(params, x, dims: PaddedDims, cache, positions,
                            lengths, *, rope_theta=0.0, scale=None):
    """Continue a prefill one chunk at a time against an existing KV cache.

    x: (B,C,d) chunk activations; ``positions`` (B,C) are per-row absolute
    cache positions (``offset + arange(C)``) and ``lengths`` (B,) the true
    (un-padded) token count of each row's chunk. The chunk's K/V are written
    at those positions (pad columns park at an out-of-bounds index so the
    scatter drops them), then the chunk queries attend causally over the
    *full* cache — prefix chunks included — so chunk-by-chunk prefill equals
    the single-shot forward. Stale cache entries beyond a row's frontier are
    masked by ``k_pos <= q_pos`` exactly like slot reuse in the decode path.
    Returns (out (B,C,d), filled cache)."""
    B, C, _ = x.shape
    q, k, v = _project_qkv(params, x, x, dims)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    S = cache["k"].shape[1]
    j = jnp.arange(C, dtype=jnp.int32)
    wpos = jnp.where(j[None, :] < lengths[:, None], positions, S)
    rows = jnp.arange(B)[:, None]
    kc = cache["k"].at[rows, wpos].set(k.astype(cache["k"].dtype),
                                       mode="drop")
    vc = cache["v"].at[rows, wpos].set(v.astype(cache["v"].dtype),
                                       mode="drop")
    k_pos = jnp.arange(S, dtype=jnp.int32)
    scale = _scale(scale, q.shape[-1])
    scores = jnp.einsum("bsgqh,btgh->bgqst", q, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    mask = (k_pos[None, None, :] <= positions[:, :, None])[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgqst,btgh->bsgqh", probs.astype(vc.dtype), vc)
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, C, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, params["wo"]), {"k": kc, "v": vc}


def project_decode_qkv(params, x, dims: PaddedDims, pos, rope_theta):
    """Project the new token's q/k/v with RoPE at `pos` (scalar or (B,)).
    The serving engine hands the served q/k/v leaves (heads, head_dim,
    d_model; ``serve_layout``), converted once when it takes its weights;
    the published order works too (``_project_qkv``)."""
    pos = jnp.asarray(pos, jnp.int32)
    per_seq = pos.ndim == 1
    q, k_new, v_new = _project_qkv(params, x, x, dims)
    pos_vec = pos[:, None] if per_seq else jnp.full((1,), pos, jnp.int32)
    if rope_theta:
        q = apply_rope(q, pos_vec, rope_theta)
        k_new = apply_rope(k_new, pos_vec, rope_theta)
    return q, k_new, v_new


def write_token(cache, x, pos):
    """Write one token's (B,1,...) leaf ``x`` at ``pos`` (scalar or (B,))
    into a (B,S,...) cache leaf — in-place under jit (the cache should be a
    loop carry or donated)."""
    pos = jnp.asarray(pos, jnp.int32)
    x = x.astype(cache.dtype)
    if pos.ndim == 1:
        return cache.at[jnp.arange(cache.shape[0]), pos].set(x[:, 0])
    return jax.lax.dynamic_update_slice(
        cache, x, (0, pos) + (0,) * (cache.ndim - 2))


def write_kv(k_cache, v_cache, k_new, v_new, pos):
    """Write one token's k/v at `pos` into (B,S,G,hd) caches."""
    return write_token(k_cache, k_new, pos), write_token(v_cache, v_new, pos)


def decode_attend(params, q, k_cache, v_cache, pos, dims: PaddedDims,
                  backend: str = "einsum", scale=None):
    """Read-only attention of a single-token q over cache[0..pos].

    ``backend="pallas"`` routes through the flash-decode kernel
    (``repro.kernels.decode_attention``) instead of the dense einsum: the
    online-softmax tiles the KV axis and skips blocks past the filled cache
    length, so decode cost follows the *filled* cache. The serve cache
    layout is (B, S, G, hd) while the kernel wants (B, G, S, hd) — the
    transpose here is the price of keeping one cache layout for both
    backends (a TPU deployment would store the pool kernel-native). Runs in
    interpret mode off-TPU so the CPU parity tests exercise the same code
    path. ``scale`` is the softmax scale (None: 1/sqrt(head_dim), the only
    one the kernel takes)."""
    if backend == "pallas":
        if scale is not None:
            raise ValueError("the flash-decode kernel scales by "
                             "1/sqrt(head_dim) only")
        return _decode_attend_pallas(params, q, k_cache, v_cache, pos, dims)
    B = q.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_seq = pos.ndim == 1
    T = k_cache.shape[1]
    k_pos = jnp.arange(T, dtype=jnp.int32)
    scale = _scale(scale, q.shape[-1])
    scores = jnp.einsum("bsgqh,btgh->bgqst", q, k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if per_seq:
        mask = (k_pos[None, :] <= pos[:, None])[:, None, None, None, :]
    else:
        mask = (k_pos <= pos)[None, None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgqst,btgh->bsgqh", probs.astype(v_cache.dtype), v_cache)
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, 1, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, params["wo"])


def _decode_attend_pallas(params, q, k_cache, v_cache, pos, dims: PaddedDims):
    """Flash-decode backend: grouped q (B,1,G,qpg,hd) and the (B,S,G,hd)
    serve caches reshaped to the kernel's (B,Hq,d) / (B,G,S,d) layout, with
    per-row ``pos`` forwarded as the kernel's scalar-prefetch lengths. The
    padded-q-head mask applies after the kernel exactly like the einsum
    path."""
    from repro.kernels.decode_attention import flash_decode

    B = q.shape[0]
    hd = q.shape[-1]
    qf = q.reshape(B, dims.n_kv * dims.q_per_group, hd)
    kc = k_cache.swapaxes(1, 2)                  # (B,S,G,hd) -> (B,G,S,hd)
    vc = v_cache.swapaxes(1, 2)
    out = flash_decode(qf, kc.astype(qf.dtype), vc.astype(qf.dtype), pos,
                       interpret=jax.default_backend() != "tpu")
    ctx = out.reshape(B, 1, dims.n_kv, dims.q_per_group, hd)
    ctx = _mask_pad_heads(ctx, dims)
    ctx = ctx.reshape(B, 1, dims.n_q, -1)
    return jnp.einsum("bsnh,nhd->bsd", ctx, params["wo"])


def decode_attention(params, x, dims: PaddedDims, cache, pos, *,
                     rope_theta=0.0, shard_fn=None):
    """Single-token decode. x: (B,1,d); pos scalar or (B,). Returns
    (out, updated cache). Prefer the split project/write/attend API inside
    scan loops (keeps cache updates in-place on the loop carry)."""
    q, k_new, v_new = project_decode_qkv(params, x, dims, pos, rope_theta)
    k_cache, v_cache = write_kv(cache["k"], cache["v"], k_new, v_new, pos)
    out = decode_attend(params, q, k_cache, v_cache, pos, dims)
    return out, {"k": k_cache, "v": v_cache}
