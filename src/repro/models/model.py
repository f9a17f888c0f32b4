"""Unified model facade: one API over all assigned architecture families.

    model = make_model(get_config("mistral-nemo-12b"), tp=16)
    params = model.init(key, dtype=jnp.bfloat16)
    loss, metrics = model.loss(params, batch)
    logits, state, pos = model.prefill(params, batch, cache_len=32768)
    logits, state = model.decode(params, state, tokens, pos)

``input_specs`` returns ShapeDtypeStruct stand-ins for every input of a
(shape-kind) cell — the multi-pod dry-run lowers against these without any
device allocation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import encdec, hybrid_moe, lm, ssm_lm
from repro.models.dims import PaddedDims, padded_dims
from repro.models.layers import cross_entropy


def _masked_ce_sum(logits, targets, mask, vocab_logical: int):
    """(sum of masked NLL, count). Padded vocab columns excluded."""
    v_phys = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if v_phys > vocab_logical:
        neg = jnp.full((v_phys - vocab_logical,), -1e9, jnp.float32)
        logits = logits.at[..., vocab_logical:].set(neg)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask.astype(jnp.float32)
    return jnp.sum(nll), jnp.sum(mask.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    dims: PaddedDims
    remat: str = "none"

    # ------------------------------------------------------------------ init
    def init(self, key, dtype=jnp.float32, sharding=None):
        """Random params from ``key``. Runs under ``jit``, so each float32
        draw fuses into its cast and no full-size float32 temporary is
        built; ``sharding`` places every leaf as it is created (e.g.
        replicated over a serving mesh), so no single-device copy exists."""
        return _init_params(self.cfg, self.dims, key, jnp.dtype(dtype),
                            sharding)

    # --------------------------------------------------------------- forward
    def forward(self, params, batch, shard_fn=None, return_features=False):
        c, d = self.cfg, self.dims
        kw = dict(remat=self.remat, shard_fn=shard_fn,
                  return_features=return_features)
        if c.family in ("dense", "moe", "vlm"):
            return lm.lm_forward(params, batch, c, d, **kw)
        if c.family in ("ssm", "hybrid"):
            return ssm_lm.ssm_forward(params, batch, c, d, **kw)
        if c.family == "hybrid_moe":
            return hybrid_moe.hmoe_forward(params, batch, c, d, **kw)
        if c.family == "audio":
            return encdec.encdec_forward(params, batch, c, d, **kw)
        raise ValueError(c.family)

    def _head(self, params):
        head = params.get("lm_head")
        return head if head is not None else params["embed"].T

    def loss(self, params, batch, shard_fn=None, loss_chunk: int = 2048):
        """Next-token CE via sequence-chunked head+softmax: the (T, V) logits
        tensor is never materialized (a ~V/d memory saving on the loss)."""
        c = self.cfg
        feats, aux = self.forward(params, batch, shard_fn=shard_fn,
                                  return_features=True)
        toks = batch["tokens"]
        if c.family == "vlm":
            P = c.num_patches
            pred_h = feats[:, P - 1:P + toks.shape[1] - 1]
            targets = toks
        else:
            pred_h = feats[:, :-1]
            targets = toks[:, 1:]
        ce = self._chunked_ce(params, pred_h, targets, loss_chunk, shard_fn)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    def _chunked_ce(self, params, pred_h, targets, loss_chunk, shard_fn):
        c = self.cfg
        head = self._head(params)
        B, S, dm = pred_h.shape
        loss_chunk = min(loss_chunk, S)
        n_chunks = -(-S // loss_chunk)
        S_pad = n_chunks * loss_chunk
        if S_pad != S:
            pred_h = jnp.pad(pred_h, ((0, 0), (0, S_pad - S), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, S_pad - S)))
        mask = (jnp.arange(S_pad) < S)[None, :]
        hc = pred_h.reshape(B, n_chunks, loss_chunk, dm).swapaxes(0, 1)
        tc = targets.reshape(B, n_chunks, loss_chunk).swapaxes(0, 1)
        mc = jnp.broadcast_to(mask.reshape(1, n_chunks, loss_chunk)
                              .swapaxes(0, 1), tc.shape)

        @jax.checkpoint
        def body(acc, xs):
            h, t, m = xs
            logits = h @ head
            if shard_fn is not None:
                logits = shard_fn(logits, "logits")
            nll_sum, n = _masked_ce_sum(logits, t, m, c.vocab_size)
            return (acc[0] + nll_sum, acc[1] + n), None

        (tot, n), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                   (hc, tc, mc))
        return tot / jnp.maximum(n, 1.0)

    # --------------------------------------------------------------- serving
    def init_serve_state(self, batch: int, cache_len: int,
                         cache_dtype=jnp.bfloat16):
        """``cache_dtype`` may be the string "int8" for dense/moe/vlm: the
        KV pool is stored int8 with per-(token, head) absmax scales (see
        ``repro.serving.kv_quant``) — ~3.6x slot capacity per byte vs f32."""
        c, d = self.cfg, self.dims
        if cache_dtype == "int8" and c.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"int8 cache needs an attention KV pool; family={c.family!r} "
                "keeps SSM/conv state in float")
        if c.family in ("dense", "moe", "vlm"):
            return lm.lm_init_cache(c, d, batch, cache_len, cache_dtype)
        if c.family in ("ssm", "hybrid"):
            return ssm_lm.ssm_init_state(c, d, batch, cache_len, cache_dtype)
        if c.family == "hybrid_moe":
            return hybrid_moe.hmoe_init_state(c, d, batch, cache_len,
                                              cache_dtype)
        if c.family == "audio":
            return encdec.encdec_init_state(c, d, batch, cache_len, cache_dtype)
        raise ValueError(c.family)

    def prefill(self, params, batch, cache_len: int,
                cache_dtype=jnp.bfloat16, shard_fn=None):
        c, d = self.cfg, self.dims
        if c.family in ("dense", "moe", "vlm"):
            # (vlm: _embed_inputs prepends the patch prefix; the cache covers
            # patches + text)
            return lm.lm_prefill(params, batch, c, d, cache_len=cache_len,
                                 cache_dtype=cache_dtype, shard_fn=shard_fn)
        if c.family in ("ssm", "hybrid"):
            return ssm_lm.ssm_prefill(params, batch, c, d, cache_len=cache_len,
                                      cache_dtype=cache_dtype,
                                      shard_fn=shard_fn)
        if c.family == "hybrid_moe":
            return hybrid_moe.hmoe_prefill(params, batch, c, d,
                                           cache_len=cache_len,
                                           cache_dtype=cache_dtype,
                                           shard_fn=shard_fn)
        if c.family == "audio":
            return encdec.encdec_prefill(params, batch, c, d,
                                         cache_len=cache_len,
                                         cache_dtype=cache_dtype,
                                         shard_fn=shard_fn)
        raise ValueError(c.family)

    def prefill_into(self, params, batch, slab, rows, slots, cache_len: int,
                     cache_dtype=jnp.bfloat16):
        """Prefill whose rows write their serve state straight into a fleet
        slab (every leaf with a leading fleet axis before the per-replica
        layout): row k's state goes to (fleet row ``rows[k]``, slot
        ``slots[k]``), an out-of-bounds row's is dropped. Returns (logits,
        slab, pos). ``hybrid_moe`` writes each layer's state as the layer
        scan reaches it; the others prefill, then scatter."""
        c, d = self.cfg, self.dims
        if c.family == "hybrid_moe":
            return hybrid_moe.hmoe_prefill_into(params, batch, slab, rows,
                                                slots, c, d)
        logits, small, pos = self.prefill(params, batch, cache_len=cache_len,
                                          cache_dtype=cache_dtype)

        def scatter(s, sm):
            return s.at[rows, :, slots].set(
                sm.swapaxes(0, 1).astype(s.dtype), mode="drop")

        return logits, jax.tree.map(scatter, slab, small), pos

    def prefill_chunk(self, params, state, tokens, offsets, lengths,
                      shard_fn=None):
        """Advance a chunked prefill: run ``tokens`` (B,C) at per-row cache
        ``offsets`` against the carried serve state (KV cache rows for
        dense/moe, SSM/conv/attn state for ssm/hybrid). Returns
        (last-real-token logits, state, pos). Chunk-by-chunk equals the
        single-shot ``prefill`` exactly; vlm/audio requests carry per-request
        extras and stay on the exact-length single-shot path, and moe is
        rejected because expert capacity would scale with the chunk rather
        than the full prompt (per-chunk routing drops differ from
        single-shot — the same reason the engine keeps moe on exact-length
        admission)."""
        c, d = self.cfg, self.dims
        if c.family == "dense":
            return lm.lm_prefill_chunk(params, state, tokens, offsets,
                                       lengths, c, d, shard_fn=shard_fn)
        if c.family in ("ssm", "hybrid"):
            return ssm_lm.ssm_prefill_chunk(params, state, tokens, offsets,
                                            lengths, c, d, shard_fn=shard_fn)
        raise ValueError(f"chunked prefill unsupported for {c.family!r}")

    def decode(self, params, state, tokens, pos, shard_fn=None,
               attn_backend=None, commit=None):
        """``attn_backend="pallas"`` (dense/moe/vlm only) decodes through
        the flash-decode kernel; None/"einsum" keeps the dense reference
        path. SSM/audio families carry no KV decode loop and ignore it.
        ``commit`` (scalar bool) False computes the step but returns
        ``state`` as it was: the KV families drop their token writes, the
        others select the old state back. ``hybrid_moe`` runs as a fleet of
        one (``decode_fleet``)."""
        c, d = self.cfg, self.dims
        if c.family in ("dense", "moe", "vlm"):
            return lm.lm_decode(params, state, tokens, pos, c, d,
                                shard_fn=shard_fn,
                                attn_backend=attn_backend, commit=commit)
        if c.family == "hybrid_moe":
            B = tokens.shape[0]
            rows = None if commit is None else jnp.reshape(commit, (1,))
            logits, new, _ = hybrid_moe.hmoe_decode(
                params, jax.tree.map(lambda t: t[None], state),
                tokens[None],
                jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))[None],
                c, d, rows=rows)
            return logits[0], jax.tree.map(lambda t: t[0], new)
        if c.family in ("ssm", "hybrid"):
            logits, new = ssm_lm.ssm_decode(params, state, tokens, pos, c, d,
                                            shard_fn=shard_fn)
        elif c.family == "audio":
            logits, new = encdec.encdec_decode(params, state, tokens, pos, c,
                                               d, shard_fn=shard_fn)
        else:
            raise ValueError(c.family)
        if commit is not None:
            new = jax.tree.map(lambda o, n: jnp.where(commit, n, o), state,
                               new)
        return logits, new

    def decode_fleet(self, params, slab, tokens, pos, rows=None,
                     attn_backend=None):
        """One decode step of a stacked fleet: every leaf of ``slab`` has a
        leading fleet axis F before the per-replica serve state; tokens
        (F, B, 1); pos (F, B); ``rows`` (F,) bool marks the rows whose step
        commits (None: all). Returns (logits (F, B, V), slab, counts), where
        ``counts`` maps a name to a per-slot (F, B, ...) integer count of
        what the step did (empty for families that count nothing).
        ``hybrid_moe`` steps the stacked state directly; the others map
        ``decode`` over the fleet axis."""
        if self.cfg.family == "hybrid_moe":
            return hybrid_moe.hmoe_decode(params, slab, tokens, pos,
                                          self.cfg, self.dims, rows=rows)
        logits, slab = jax.vmap(
            lambda c, t, q, r: self.decode(params, c, t, q,
                                           attn_backend=attn_backend,
                                           commit=r))(slab, tokens, pos, rows)
        return logits, slab, {}

    # ------------------------------------------------------------ dry-run IO
    def input_specs(self, shape: ShapeConfig, act_dtype=jnp.bfloat16,
                    cache_dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct stand-ins for every input of this (arch×shape)."""
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        if shape.kind in ("train", "prefill"):
            specs = {"tokens": sds((B, S), jnp.int32)}
            if c.family == "vlm":
                specs["patch_embeds"] = sds((B, c.num_patches, c.d_model),
                                            act_dtype)
            if c.family == "audio":
                specs["frame_embeds"] = sds((B, c.encoder_seq_len, c.d_model),
                                            act_dtype)
            return specs
        # decode: one new token against a cache of length S
        state = jax.eval_shape(
            lambda: self.init_serve_state(B, S, cache_dtype))
        return {
            "tokens": sds((B, 1), jnp.int32),
            "pos": sds((), jnp.int32),
            "state": state,
        }


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4))
def _init_params(cfg: ArchConfig, dims: PaddedDims, key, dtype, sharding):
    # keyed on (cfg, dims), not on a Model: a static Model would pin the
    # serve kernels cached on it for the life of the process
    if cfg.family in ("dense", "moe", "vlm"):
        params = lm.init_lm(key, cfg, dims, dtype)
    elif cfg.family in ("ssm", "hybrid"):
        params = ssm_lm.init_ssm_lm(key, cfg, dims, dtype)
    elif cfg.family == "hybrid_moe":
        params = hybrid_moe.init_hybrid_moe(key, cfg, dims, dtype)
    elif cfg.family == "audio":
        params = encdec.init_encdec(key, cfg, dims, dtype)
    else:
        raise ValueError(cfg.family)
    if sharding is not None:
        params = jax.lax.with_sharding_constraint(params, sharding)
    return params


def make_model(cfg: ArchConfig, tp: int = 1, remat: str = "none") -> Model:
    return Model(cfg, padded_dims(cfg, tp), remat)


def make_train_step(model: Model, optimizer, shard_fn=None, donate=True,
                    grad_accum: int = 1, loss_chunk: int = 2048,
                    accum_dtype=jnp.float32):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``grad_accum > 1`` scans over microbatches (global batch split on axis 0)
    accumulating gradients before one optimizer step — bounds the per-layer
    activation-checkpoint memory at L·(B/ga)·S·d.
    """
    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: model.loss(p, batch, shard_fn=shard_fn,
                                 loss_chunk=loss_chunk),
            has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if grad_accum <= 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)

            def body(acc, mb):
                (l, m), g = grads_of(params, mb)
                acc_g, acc_l = acc
                g = jax.tree.map(lambda a, b: a + b.astype(accum_dtype),
                                 acc_g, g)
                return (g, acc_l + l), m

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (grads, loss_sum), ms = jax.lax.scan(
                body, (zero, jnp.float32(0.0)), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum
            metrics = jax.tree.map(lambda x: x[-1], ms)
        params, opt_state, stats = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **stats)
        return params, opt_state, metrics
    return train_step


def make_decode_step(model: Model, shard_fn=None):
    """Returns serve_step(params, state, tokens, pos) -> (logits, state)."""
    def serve_step(params, state, tokens, pos):
        return model.decode(params, state, tokens, pos, shard_fn=shard_fn)
    return serve_step


def make_prefill_step(model: Model, cache_len: int, shard_fn=None):
    def prefill_step(params, batch):
        return model.prefill(params, batch, cache_len=cache_len,
                             shard_fn=shard_fn)
    return prefill_step
