"""Top-k mixture-of-experts FFN with capacity-bounded scatter dispatch.

Dispatch uses cumsum slot assignment + scatter into an (E, C, d) buffer —
never materializing a (tokens × E × C) one-hot. Tokens over capacity are
dropped (scatter mode='drop'; gather mode='fill' returns zeros), matching
Switch/GShard semantics. Aux load-balance loss included.

Sharding: tokens shard over the data axes, expert hidden dim over "model"
(TP-in-expert). With ``expert_sharding='data'`` and E % |data| == 0 the expert
dim itself shards over data (EP) — GSPMD inserts the all-to-all.

``moe_held_apply`` is the serving layer of one chip's share of the experts:
told which contiguous block it holds, it routes over all of them, computes
only the (token, held expert) pairs by a grouped matmul and drops nothing, so
a padded batch gives what the exact-length batch gives.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import gelu, he_init, silu


def init_moe(key, d_model: int, d_ff: int, num_experts: int, dtype,
             shared_expert: bool, activation: str) -> dict:
    ks = jax.random.split(key, 5)
    E = num_experts
    p = {
        "router": he_init(ks[0], (d_model, E), jnp.float32, d_model),
        "w_gate": he_init(ks[1], (E, d_model, d_ff), dtype, d_model),
        "w_up": he_init(ks[2], (E, d_model, d_ff), dtype, d_model),
        "w_down": he_init(ks[3], (E, d_ff, d_model), dtype, d_ff),
    }
    if activation != "swiglu":
        del p["w_up"]
    if shared_expert:
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": he_init(ks2[0], (d_model, d_ff), dtype, d_model),
            "w_up": he_init(ks2[1], (d_model, d_ff), dtype, d_model),
            "w_down": he_init(ks2[2], (d_ff, d_model), dtype, d_ff),
        }
    return p


def _expert_ffn(p, buf, activation):
    """buf: (E, C, d) -> (E, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    if activation == "swiglu":
        u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        h = silu(g) * u
    else:
        h = gelu(g)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _dense_ffn(p, x, activation):
    g = jnp.einsum("td,df->tf", x, p["w_gate"])
    if activation == "swiglu":
        h = silu(g) * jnp.einsum("td,df->tf", x, p["w_up"])
    else:
        h = gelu(g)
    return jnp.einsum("tf,fd->td", h, p["w_down"])


def moe_apply(params, x, *, num_experts: int, top_k: int,
              capacity_factor: float = 1.25, activation: str = "swiglu",
              shard_fn=None):
    """x: (B, S, d) -> (y, aux_loss).

    Dispatch is PER BATCH ROW (capacity and slot cumsum within each
    sequence): sequences cannot displace each other's tokens (deterministic
    under continuous batching / changing co-batched requests) and slot order
    follows sequence order, so drops are causal within a row.
    """
    B, S, d = x.shape
    E, K = num_experts, top_k
    logits = (x.astype(jnp.float32) @ params["router"])            # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)                         # (B,S,K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch eq. 4): E * <f_e * p_e>
    assign = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(assign, axis=(0, 1))
                       * jnp.mean(probs, axis=(0, 1)))

    C = int(math.ceil(S * K / E * capacity_factor))
    C = max(C, K)
    flat_e = top_i.reshape(B, S * K)                               # expert ids
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (B,S*K,E)
    slot = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1       # (B,S*K)
    slot = jnp.where(slot < C, slot, C)                            # C = dropped
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    tok = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)[None]      # (1,S*K)
    xt = x  # (B,S,d)

    buf = jnp.zeros((E, B, C, d), x.dtype)
    src = jnp.take_along_axis(xt, jnp.broadcast_to(tok, (B, S * K))[..., None],
                              axis=1)                              # (B,S*K,d)
    buf = buf.at[flat_e, rows, slot].add(src, mode="drop")
    if shard_fn is not None:
        buf = shard_fn(buf, "moe_buf")
    out_buf = _expert_ffn(params, buf.reshape(E, B * C, d), activation)
    out_buf = out_buf.reshape(E, B, C, d)
    if shard_fn is not None:
        out_buf = shard_fn(out_buf, "moe_buf")

    gathered = out_buf.at[flat_e, rows, slot].get(
        mode="fill", fill_value=0)                                 # (B,S*K,d)
    weighted = gathered * top_p.reshape(B, S * K, 1).astype(gathered.dtype)
    y = jnp.zeros((B, S, d), x.dtype).at[
        rows, jnp.broadcast_to(tok, (B, S * K))].add(weighted)

    if "shared" in params:
        y = y + _dense_ffn(params["shared"], x.reshape(B * S, d),
                           activation).reshape(B, S, d)
    return y, aux


def moe_dense_oracle(params, x, *, num_experts: int, top_k: int,
                     activation: str = "swiglu"):
    """O(T·E) oracle: every expert on every token, combine with top-k gates.

    No capacity drops — used by tests with high capacity_factor.
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    # scatter normalized gates back to (T, E)
    gates_full = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], top_i].set(top_p)
    outs = _expert_ffn(params, jnp.broadcast_to(xt, (num_experts,) + xt.shape),
                       activation)                       # (E, T, d)
    y = jnp.einsum("te,etd->td", gates_full, outs.astype(jnp.float32))
    if "shared" in params:
        y = y + _dense_ffn(params["shared"], xt, activation).astype(jnp.float32)
    return y.reshape(B, S, d).astype(x.dtype)


def moe_held_apply(params, x, *, num_experts: int, top_k: int,
                   first: int = 0, activation: str = "swiglu",
                   rows: int = 0):
    """One chip's part of an MoE FFN. x: (T, d) -> (y (T, d), routed (T,
    held) int32).

    ``params["w_gate"]`` etc. hold experts ``first .. first + held - 1`` of
    ``num_experts``; the router (d, num_experts) routes over all of them,
    top ``top_k`` with the softmax taken over the chosen ones. Every (token,
    held expert) pair is computed and none is dropped: the pairs are sorted
    by expert and run through ``jax.lax.ragged_dot`` (a grouped matmul whose
    rows past a pass's pairs are selected away: the TPU leaves them
    unwritten, and a zero gate times a stale Inf is NaN), in passes of
    ``rows`` sorted pairs
    for as many passes as the pairs fill (0: one pass over ``T * min(top_k,
    held)`` rows, the most the tokens can bring; a fixed shape, so it also
    differentiates). A row's result does not depend on the other rows. A
    shared expert (``params["shared"]``) is added for every token.
    ``routed[t, e]`` is 1 where token t was routed to held expert e."""
    T, d = x.shape
    held = params["w_gate"].shape[0]
    K = top_k
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (T,E)
    top_p, top_i = jax.lax.top_k(probs, K)                         # (T,K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    local = top_i - first
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held).reshape(T * K)              # held: none
    most = T * min(K, held)
    C = min(rows or most, most)
    passes = -(-most // C)
    order = jnp.argsort(key, stable=True)
    order = jnp.concatenate([order, jnp.zeros(passes * C - T * K
                                              if passes * C > T * K else 0,
                                              order.dtype)])[:passes * C]
    gate_all = jnp.where(key[order] < held, top_p.reshape(T * K)[order], 0.0)
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def one_pass(c, out):
        lo = c * C
        idx = jax.lax.dynamic_slice_in_dim(order, lo, C)
        gate = jax.lax.dynamic_slice_in_dim(gate_all, lo, C)
        n = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        tok = idx // K
        xs = x[tok]
        g = jax.lax.ragged_dot(xs, params["w_gate"], n)
        if activation == "swiglu":
            h = silu(g) * jax.lax.ragged_dot(xs, params["w_up"], n)
        else:
            h = gelu(g)
        y = jax.lax.ragged_dot(h.astype(x.dtype), params["w_down"], n)
        y = jnp.where((jnp.arange(C) < jnp.sum(n))[:, None],
                      y.astype(jnp.float32) * gate[:, None], 0.0)
        return out.at[tok].add(y)

    out = jnp.zeros((T, d), jnp.float32)
    if passes == 1:
        out = one_pass(0, out)
    else:                              # only as many passes as pairs fill
        out = jax.lax.while_loop(
            lambda co: co[0] * C < ends[-1],
            lambda co: (co[0] + 1, one_pass(co[0], co[1])), (0, out))[1]
    if "shared" in params:
        out = out + _dense_ffn(params["shared"], x, activation).astype(
            jnp.float32)
    routed = jnp.sum(jax.nn.one_hot(jnp.where(mine, local, held), held,
                                    dtype=jnp.int32), axis=1)
    return out.astype(x.dtype), routed
