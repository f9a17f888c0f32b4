"""The plain float32 reference of the served granite-4.0-h style hybrid,
written from the layer equations and independent of the program's model
code.

    h = embed[tokens] * embedding_multiplier
    for each layer l (pre-norm, residual scaled by r = residual_multiplier):
        x = rmsnorm(h) * (1 + g_l)      rmsnorm(h) = h / sqrt(mean(h^2) + eps)
        h = h + r * mixer_l(x)
        x = rmsnorm(h) * (1 + g'_l)
        h = h + r * (sum over held experts e of gate_e(x) * swiglu_e(x)
                     + swiglu_shared(x))
    logits = (rmsnorm(h) * (1 + g_final)) embed^T / logits_scaling

Mamba-2 mixer (one group, heads of P, state N, depthwise causal conv of
width W with bias):

    z, xBC, dt = x Win                      split d_inner | d_inner + 2N | H
    xBC = silu(conv(xBC) + b);  xs, B, C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    per position t, head h:  s = exp(dt A) s + dt * xs_t (outer) B_t
                             y_t = s C_t + D xs_t
    y = y * silu(z);  y = y / sqrt(mean(y^2) + eps) * (1 + g_gate)
    mixer = y Wout

run as a plain sequential scan over positions. Attention mixer: GQA, no
positional encoding (NoPE), softmax(q k^T * attention_multiplier + causal
mask) v, then Wo. MoE: the router scores all of its experts, the top k are
kept with a softmax over their scores, and the experts this chip holds are
run densely over every token, each weighted by its gate (0 where the token
did not choose it); the shared expert is added for every token.

Everything is float32 with matrix products at ``precision="highest"``; the
weights are the seed's draws (``weights.py``), made again here one layer at
a time. ``lower`` switches the reference to the control: every matrix
product takes its operands rounded to float8 (e4m3, per-tensor absmax
scale) and accumulates in float32, the precision step below the
configuration's bf16.
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


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


def _mamba(x, w, cfg, lower):
    d = cfg["hidden_size"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di, W = cfg["mamba_expand"] * d, cfg["mamba_d_conv"]
    Bsz, S, _ = x.shape
    proj = _mm("bsd,dk->bsk", x, w["in_proj"], lower)
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * N],
                  proj[..., 2 * di + 2 * N:])
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * w["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[..., :di].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                    # (B,S,H)
    A = -jnp.exp(w["A_log"])

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp          # (B,H,P) (B,H) (B,N) (B,N)
        s = (jnp.exp(dt_t * A)[:, :, None, None] * s
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision="highest")

    s0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                   Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + w["D"][:, None] * xs                # (B,S,H,P)
    y = y.reshape(Bsz, S, di) * jax.nn.silu(z)
    y = _rmsnorm(y, w["norm_scale"], cfg["rms_norm_eps"])
    return _mm("bsk,kd->bsd", y, w["out_proj"], lower)


def _attention(x, w, cfg, lower):
    h_heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = _mm("bsd,dnh->bsnh", x, w["wq"], lower)
    k = jnp.repeat(_mm("bsd,dnh->bsnh", x, w["wk"], lower), h_heads // kv, 2)
    v = jnp.repeat(_mm("bsd,dnh->bsnh", x, w["wv"], lower), h_heads // kv, 2)
    s = _mm("bqnh,bknh->bnqk", q, k, lower) * cfg["attention_multiplier"]
    S = x.shape[1]
    s = jnp.where(np.tril(np.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = _mm("bnqk,bknh->bqnh", jax.nn.softmax(s, axis=-1), v, lower)
    return _mm("bqnh,nhd->bqd", a, w["wo"], lower)


def _swiglu(x, wg, wu, wd, lower):
    g = _mm("bsd,df->bsf", x, wg, lower)
    u = _mm("bsd,df->bsf", x, wu, lower)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, wd, lower)


def _moe(x, w, cfg, lower):
    """(y, the top-k experts of each token)."""
    k, first = cfg["num_experts_per_tok"], cfg["first_held_expert"]
    scores = _mm("bsd,de->bse", x, w["router"], lower)
    top, idx = jax.lax.top_k(scores, k)
    gates = jnp.sum(jax.nn.softmax(top, axis=-1)[..., None]
                    * jax.nn.one_hot(idx, scores.shape[-1]), axis=-2)
    y = _swiglu(x, w["shared"]["w_gate"], w["shared"]["w_up"],
                w["shared"]["w_down"], lower)
    for e in range(cfg["num_local_experts"]):
        y = y + gates[..., first + e, None] * _swiglu(
            x, w["w_gate"][e], w["w_up"][e], w["w_down"][e], lower)
    return y, idx


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "lower"))
def _layer(h, w, *, cfg_items, kind, lower):
    cfg = dict(cfg_items)
    w = _f32(w)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    h = h + r * mixer(_rmsnorm(h, w["norm"], eps), w["mixer"], cfg, lower)
    y, idx = _moe(_rmsnorm(h, w["ffn_norm"], eps), w["moe"], cfg, lower)
    return h + r * y, idx


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"))
def _head(h, outer, *, cfg_items, lower):
    cfg = dict(cfg_items)
    x = _rmsnorm(h, outer["final_norm"], cfg["rms_norm_eps"])
    head = outer.get("lm_head")
    head = outer["embed"].T if head is None else head
    return _mm("bsd,dv->bsv", x, head.astype(jnp.float32),
               lower) / cfg["logits_scaling"]


_ARCH_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size",
              "tie_word_embeddings", "rms_norm_eps", "router_outputs",
              "num_local_experts", "first_held_expert", "num_experts_per_tok",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_d_conv", "mamba_expand",
              "embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _ARCH_KEYS)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_weights(cfg, key, index, kind, dtype):
    return weights.layer(dict(cfg), key, index, kind, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _outer_weights(cfg, key, dtype):
    return weights.outer(dict(cfg), key, dtype)


def _forward(cfg: dict, seed: int, tokens, lower, dtype):
    items = _items(cfg)
    key = root_key(seed)
    outer = _outer_weights(items, key, dtype)
    h = (outer["embed"][jnp.asarray(tokens)].astype(jnp.float32)
         * cfg["embedding_multiplier"])
    routes = []
    for i, kind in enumerate(weights.kinds(cfg)):
        w = _layer_weights(items, key, i, kind, dtype)
        h, idx = _layer(h, w, cfg_items=items, kind=kind, lower=lower)
        routes.append(idx)
        del w
    return _head(h, outer, cfg_items=items, lower=lower), routes


def logits(cfg: dict, seed: int, tokens: np.ndarray, *, lower=False,
           dtype=jnp.bfloat16):
    """Float32 logits (B, S, V), on the device, of right-padded ``tokens``
    (B, S): causal, so the logits at real positions do not see the padding.
    The weights are drawn again from ``seed`` one layer at a time."""
    return _forward(cfg, seed, tokens, lower, dtype)[0]


def routes(cfg: dict, seed: int, tokens: np.ndarray, dtype=jnp.bfloat16):
    """The experts (of all the router's) each token of ``tokens`` (B, S) is
    routed to in each layer: (layers, B, S, top_k)."""
    return np.stack([np.asarray(r) for r in
                     _forward(cfg, seed, tokens, False, dtype)[1]])
