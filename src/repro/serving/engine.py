"""Request-level serving engine: continuous batching over real model forwards.

``ReplicaEngine`` runs one model replica: slot-based KV/state pool, per-slot
positions (the vector-``pos`` decode path), admit-on-free-slot, greedy
sampling, retire-on-EOS/max-tokens. Prompts are right-padded to power-of-two
length buckets and admitted in batched prefill calls, so the jit'd prefill
compiles O(log max_seq · log max_batch) times total instead of once per
distinct prompt length (``prefill_traces`` counts actual retraces). Padded
prefill is exact for dense/ssm/hybrid: causal attention masks trailing pads
and the SSM path zeroes dt at pad positions (see
``models.ssd.mamba2_forward``). MoE replicas default to the exact-length
single-admit path instead: expert-capacity routing sees the pad tokens, so a
padded bucket is exact only when no capacity drops occur (capacity scales
with the padded length and the whole admit batch — a drop pattern the
per-prompt oracle never sees). Opt back into buckets with
``bucket_prompts=True`` when approximate routing is acceptable.
Prompts longer than ``max_seq - 1`` are truncated to their last
``max_seq - 1`` tokens at admission (the KV pool can never overflow).

**SLO tiers.** Each replica's pending queue is a ``TieredQueue``: one FIFO
per priority class (``workload.trace.TierSet``), drained in weighted-deficit
round-robin order — the top-weight tier admits first, lower-weight tiers are
guaranteed a bounded admission share so batch work never starves. Tiering
only reorders *which* requests enter the admission plans below; the dispatch
structure (one fleet prefill per distinct bucket shape, one fleet decode per
tick) is untouched, and the default single-tier configuration is
bit-identical to the untiered scheduler. Two guards keep long low-tier
prefills from degrading premium latency: a lower-tier chunk start yields the
last free slot while higher-priority work waits, and under pressure at most
one below-decoding-tier chunk cursor advances per tick (see
``plan_admission`` / ``_chunk_due``).

**Admission pipeline** (bucket → chunk → fleet slab). Each tick every
stepping replica *plans* admission from its queue without dispatching
(``plan_admission``): chunk-eligible prompts (longer than ``chunk_len``,
dense/ssm/hybrid, f32 cache) reserve a slot and a chunk cursor; requests
carrying per-request extras (vlm patches, audio frames) become exact-length
single admits; everything else groups into one pow2 ``(bucket_batch,
bucket_len)`` prefill per replica. Execution then depends on the mode:

  * **standalone** — the replica dispatches its own bucketed prefill and one
    batched chunk step (``prefill_dispatches`` counts jitted admission
    dispatches per replica);
  * **fleet-batched prefill** — a ``FleetGroup`` gathers every member's
    bucketed groups, flattens rows of the same pow2 length bucket across ALL
    members and runs ONE jitted ``fleet_prefill`` per *distinct bucket
    shape* per tick: the batched prefill writes each admit row's KV/state
    directly into the donated fleet slab on device (no host-side
    ``write_slot`` copies), and all members' due chunk rows advance in ONE
    ``fleet_chunk`` dispatch. Admission cost becomes O(distinct bucket
    shapes) per tick instead of O(replicas);
    ``FleetGroup.prefill_dispatches`` mirrors ``decode_dispatches``.

**Chunked prefill.** Prompts longer than ``chunk_len`` stream in fixed-size
chunks, one per engine step, interleaved with decode rounds: dense chunks
attend at a cache offset over the already-filled prefix
(``models.attention.chunk_prefill_attention``), ssm/hybrid chunks carry the
SSM state and raw conv window across chunks (``mamba2_forward`` with
``init_state``/``conv_state``). A mid-chunk slot is excluded from decode via
the ``hold`` mask fused into the decode kernels (its carried state must not
be advanced by garbage decode steps), so a long prompt admits over
ceil(len/chunk) ticks while decode TBT for the other slots stays one bounded
dispatch per tick. Chunk-by-chunk equals single-shot prefill exactly.

**Fleet-batched decode.** Slot bookkeeping (the ``Request`` objects, host
``pos``/``last_tok`` mirrors, queues, clocks) lives on the engine; the device
cache may live either on the engine (standalone) or stacked along a leading
fleet axis inside a ``FleetGroup`` shared by every replica of the same
``(model, params, max_batch, max_seq, cache_dtype)``. A fleet group advances
*all* member replicas with ONE jitted ``fleet_decode`` dispatch per tick:
greedy argmax and per-slot retire decisions (max-tokens / EOS / cache-full)
are fused into the jitted function and synced back as a single small
``(fleet, batch)`` int/bool array pair — instead of one dispatch plus
per-slot ``int()`` syncs per replica. Membership survives scale-up, drain
and failure by stacking/unstacking cache rows (capacity grows in power-of-two
steps so fleet-size churn retraces O(log F) times, and removed rows are
backfilled swap-style in one device op).

``ReplicaEngine.step()`` remains the standalone per-replica path (exact-length
vlm/audio admission, heterogeneous ``max_seq``) and is the parity oracle for
the fleet path.

``cache_dtype`` accepts the string ``"int8"`` for dense/moe/vlm replicas:
the KV pool is then stored int8 with per-(token, head) f32 absmax scales
(``repro.serving.kv_quant``), roughly 3.6x the slot capacity of an fp32 pool
for the same bytes. Non-f32 caches stay on single-shot prefill (the int8
codec quantizes whole prompts at prefill end, and a bf16 pool would round
the carried chunk state that single-shot keeps unrounded), so a
``chunk_len`` there is an error.

**Async tick contract (overlapped serving).** In async mode (the elastic
frontend's default) the fleet dispatch methods never block on the device:
``decode_round``/``admit_round`` push their work onto the accelerator queue
and record a ``_Pending`` entry — the small device outputs (next tokens,
fused retire mask, stepped mask, prefill first-tokens) plus the host context
captured at dispatch time (engines, slots, requests, clocks). The decode
*operands* (``toks``/``pos``/``rem``/``eos``/``active``) are persistent
device arrays living next to the slab and advanced inside the same jitted
dispatch (``FleetGroup.ops``), so consecutive ticks chain on device without
the host rebuilding or re-uploading operand arrays. All deferred host
bookkeeping is applied at ONE reconcile point per tick
(``FleetGroup.reconcile`` — a single ``jax.device_get`` over every pending
record, counted by ``syncs``): the host work for tick *t* (queues, tiers,
metrics, the control plane's forecast→balance→scale) therefore overlaps the
device computing tick *t*'s decode. What is pending when:

  * a request admitted at tick *t* is *reserved* in its slot immediately
    (occupancy, ``load`` and tier accounting are live) but its first token,
    TTFT stamp and possible finish-at-prefill apply at the reconcile that
    opens tick *t+1*;
  * decode tokens/retires dispatched at tick *t* commit at tick *t+1*'s
    reconcile, stamped with tick *t*'s clock — token streams and finish
    ticks are **bit-identical** to the eager oracle (``async_tick=False``),
    only the host-side observation is one tick late;
  * because retire/slot-free reconciles *before* admission planning, a slot
    freed by tick *t*'s decode is admittable at tick *t+1* — exactly like
    the eager path, so admission lags the device state by **at most one
    tick** under a full slab (and by zero ticks relative to the oracle);
  * membership churn (drain retire, failure, scale-up joins) force-flushes
    pending futures first, so host mirrors are current before rows unstack.

``decode_block=K`` fuses K decode micro-steps into one dispatch via
``lax.scan`` (one ``(K, F, B)`` sync per block — K× fewer dispatches *and*
syncs). A block only auto-engages on ticks with no admissions at all —
fleet prefill/chunk dispatches (``pending``) and eager single admits
(``_admitted``) both veto it — and no chunk cursors. Queued work behind a
*full* slab does not block engagement; the trade is that any admission
landing *inside* the fused window (a retire freeing a slot, or an arrival
finding one) only starts decoding at the window's end — admission-to-
first-decode may lag up to K-1 ticks (plain async K=1 keeps the <= 1-tick
bound). One block counts as K ticks of decode (finish clocks inside the
block are ``dispatch_clock + k``) and the reconcile is deferred until the
block's ticks are spent.

**Fleet-mesh sharding.** A ``FleetGroup`` built with ``mesh=`` (a mesh
carrying a ``fleet`` axis) lays its slab and async operands out
``P('fleet', ...)`` over the N devices while params replicate, so GSPMD
partitions the *same* jitted kernel families row-parallel: F replicas
decode on N devices under the identical one-dispatch/one-sync tick, with
bit-identical streams. Slab capacity stays a multiple of the shard count
(``shards * pow2_bucket(ceil(F/shards))``; pad rows are masked inactive
and invisible to dispatch/retire accounting) and the dense row packing
that churn already maintains doubles as the cross-shard re-balance. See
the ``FleetGroup`` class docstring for the full contract.

``ClusterFrontend`` stitches several replicas together behind a balancer
policy — the live counterpart of the fluid simulator. The node-structured
elastic frontend that plugs into the unified control plane lives in
``repro.serving.elastic``.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import fleet_slab_shardings
from repro.models.model import Model
from repro.workload.trace import DEFAULT_TIERS, TierSet

# families whose prefill accepts per-row ``lengths`` (bucketed prompts are
# exact). moe is deliberately absent: expert capacity scales with the padded
# bucket, so drops can differ from the exact-length oracle (see module
# docstring). audio prefill is driven by encoder frames and stays
# exact-length; vlm requests carry patch-embed extras, which take the
# single-admit path below (batching per-request extras is future work).
_BUCKET_FAMILIES = ("dense", "ssm", "hybrid")
# families with a chunked-prefill continuation kernel (cache-offset attention
# for dense, carried ssm/conv state for ssm/hybrid). moe is excluded by
# default for the same capacity reason as bucketing.
_CHUNK_FAMILIES = ("dense", "ssm", "hybrid")
# kernel variants whose compilations count as prefill retraces (the async
# admission twins included — same shapes, different sync contract)
_PREFILL_VARIANTS = ("prefill", "fleet_prefill", "chunk", "fleet_chunk",
                     "afleet_prefill", "afleet_chunk")


def pow2_bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= n (and >= lo)."""
    b = lo
    while b < n:
        b <<= 1
    return b


class _ServeKernels:
    """Shared jit'd prefill/decode for one (model, max_seq, cache_dtype):
    replicas of the same model reuse compiled code instead of re-jitting on
    every cold start (a scale-up would otherwise stall the tick loop on XLA
    compilation of identical shapes). ``trace_counts`` counts actual
    compilations per kernel variant across every replica that shares this
    object — one deduped accounting covering prefill, decode, the fleet
    decode variants and the fleet/chunk prefill variants. ``fleet`` /
    ``fleet_masked`` advance a whole stacked fleet of replicas in one
    dispatch with sampling and retire decisions fused on device (the masked
    variant leaves non-stepping rows' cache untouched, for heterogeneous
    replica speeds); ``fleet_prefill`` / ``fleet_chunk`` are the admission
    twins writing prefill state straight into the fleet slab. The ``afleet*``
    variants are the async twins: decode operands live on device and advance
    inside the dispatch, so the host syncs nothing until the next reconcile
    (``afleet_block`` fuses K micro-steps per dispatch via ``lax.scan``)."""
    __slots__ = ("prefill", "decode", "decode_hold", "fleet", "fleet_hold",
                 "fleet_masked", "fleet_masked_hold", "fleet_prefill",
                 "chunk", "fleet_chunk", "afleet", "afleet_hold",
                 "afleet_masked", "afleet_masked_hold", "afleet_prefill",
                 "afleet_chunk", "afleet_block", "_block_factory",
                 "trace_counts")

    def block_kernel(self, K: int):
        """The K-micro-step fused decode kernel (jitted on demand, cached
        per K)."""
        fn = self.afleet_block.get(K)
        if fn is None:
            fn = self.afleet_block[K] = jax.jit(self._block_factory(K),
                                                donate_argnums=(1, 2))
        return fn

    @property
    def prefill_traces(self) -> int:
        """Compilations of the prefill-side variants (bucketed, fleet,
        chunked) — the retrace-bound currency."""
        return sum(self.trace_counts.get(v, 0) for v in _PREFILL_VARIANTS)

    @property
    def total_traces(self) -> int:
        """Compilations across every serve-kernel variant."""
        return sum(self.trace_counts.values())


def _dtype_name(cache_dtype) -> str:
    return cache_dtype if isinstance(cache_dtype, str) else \
        np.dtype(cache_dtype).name


def _timed_get(owner, arrays):
    """Blocking fetch of device ``arrays``, accounted on ``owner``: bumps
    ``owner.syncs`` and adds the blocked wall time to ``owner.sync_wait``
    (the host-vs-device tick breakdown the serve bench reports)."""
    t0 = time.perf_counter()
    out = jax.device_get(arrays)
    owner.sync_wait += time.perf_counter() - t0
    owner.syncs += 1
    return out


def _init_ops(cap: int, batch: int) -> dict:
    """Fresh device-resident decode operands for an async fleet slab:
    per-slot next-token / cache-position / remaining-budget / eos-id /
    active-mask arrays, (cap, batch) each. Inactive rows are never read
    through (``active`` masks them), so zero init is fine."""
    return {
        "toks": jnp.zeros((cap, batch), jnp.int32),
        "pos": jnp.zeros((cap, batch), jnp.int32),
        "rem": jnp.ones((cap, batch), jnp.int32),
        "eos": jnp.full((cap, batch), -1, jnp.int32),
        "active": jnp.zeros((cap, batch), bool),
    }


@dataclasses.dataclass
class _Pending:
    """A dispatched device result not yet synced: ``arrays`` are the small
    device outputs to fetch at the next reconcile, ``meta`` the host
    bookkeeping context captured at dispatch time (engines, slots, requests
    and the dispatch-time clocks that stamp TTFT/finish)."""
    kind: str       # "decode" | "block" | "prefill" | "chunk"
    arrays: object
    meta: list


def get_serve_kernels(model: Model, max_seq: int, cache_dtype,
                      attn_backend: str = "einsum") -> _ServeKernels:
    # The cache lives on the Model instance (not a module global) so compiled
    # executables are reclaimed with the model instead of pinned forever.
    cache = getattr(model, "_serve_kernels", None)
    if cache is None:
        cache = {}
        object.__setattr__(model, "_serve_kernels", cache)  # frozen dataclass
    key = (max_seq, _dtype_name(cache_dtype), attn_backend)
    k = cache.get(key)
    if k is not None:
        return k
    k = _ServeKernels()
    k.trace_counts = {}

    def _count(name):
        # runs at trace time only (python side effect inside the traced fn)
        k.trace_counts[name] = k.trace_counts.get(name, 0) + 1

    def _prefill_fn(p, batch):
        _count("prefill")
        return model.prefill(p, batch, cache_len=max_seq,
                             cache_dtype=cache_dtype)

    def _decode(p, st, tok, pos):
        return model.decode(p, st, tok, pos, attn_backend=attn_backend)

    def _decode_fn(p, st, tok, pos):
        _count("decode")
        return _decode(p, st, tok, pos)

    def _decode_hold_fn(p, st, tok, pos, hslots):
        """Standalone decode that leaves the ``hslots`` slots' state
        untouched (mid-chunk-prefill slots must not be advanced by garbage
        tokens). The held slots are gathered before the step and scattered
        back after — touching K slot rows instead of select-copying the
        whole pool (pad entries are out-of-bounds: gather clips, scatter
        drops)."""
        _count("decode_hold")
        held = jax.tree.map(lambda t: jnp.take(t, hslots, axis=1), st)
        logits, new = _decode(p, st, tok, pos)
        new = jax.tree.map(
            lambda t, h: t.at[:, hslots].set(h, mode="drop"), new, held)
        return logits, new

    def _fleet_core(p, slab, toks, pos, rem, eos, active, rows=None,
                    held=None):
        """One dispatch for a stacked fleet. slab: cache pytree with a
        leading fleet axis; toks/pos/rem/eos/active: (F, B). Returns the
        next greedy token per slot, the fused retire mask, and the advanced
        slab. The retire rule is the exact device twin of the host rule in
        ``ReplicaEngine.finish_step``: after appending this token a slot is
        done when it reached max_new_tokens (rem <= 1), emitted EOS, or its
        next write index would hit the end of the cache. ``held``
        ((hrows, hslots) index vectors for mid-chunk-prefill slots) keeps
        those slots' state bit-for-bit by gather-before / scatter-after —
        touching K slot rows, NOT select-copying the whole slab; with
        ``rows`` (F,) only those fleet rows advance at all (hetero speeds).
        Each mask combination is its own kernel variant so the common
        all-decode path keeps the pure donated in-place update."""
        if held is not None:
            hrows, hslots = held
            kept = jax.tree.map(lambda s: s[hrows, :, hslots], slab)
        logits, new_slab = jax.vmap(
            lambda c, t, q: _decode(p, c, t, q))(slab, toks[..., None], pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        done = active & ((rem <= 1) | (nxt == eos)
                         | (pos + 1 >= max_seq - 1))

        if rows is not None:
            def sel(old, new):
                rm = rows.reshape((rows.shape[0],) + (1,) * (old.ndim - 1))
                return jnp.where(rm, new, old)

            new_slab = jax.tree.map(sel, slab, new_slab)
            done = done & rows[:, None]
        if held is not None:
            new_slab = jax.tree.map(
                lambda s, h: s.at[hrows, :, hslots].set(h, mode="drop"),
                new_slab, kept)
        return nxt, done, new_slab

    def _fleet_fn(p, slab, toks, pos, rem, eos, active):
        _count("fleet")
        return _fleet_core(p, slab, toks, pos, rem, eos, active)

    def _fleet_hold_fn(p, slab, toks, pos, rem, eos, active, hrows, hslots):
        _count("fleet_hold")
        return _fleet_core(p, slab, toks, pos, rem, eos, active,
                           held=(hrows, hslots))

    def _fleet_masked_fn(p, slab, toks, pos, rem, eos, active, rows):
        """Fleet dispatch where only ``rows`` (F,) advance — other rows keep
        their cache bit-for-bit (an SSM state must not step twice)."""
        _count("fleet_masked")
        return _fleet_core(p, slab, toks, pos, rem, eos, active, rows=rows)

    def _fleet_masked_hold_fn(p, slab, toks, pos, rem, eos, active, rows,
                              hrows, hslots):
        _count("fleet_masked_hold")
        return _fleet_core(p, slab, toks, pos, rem, eos, active, rows=rows,
                           held=(hrows, hslots))

    # ------------------------------------------------------ async variants
    def _afleet_core(p, slab, ops, rows=None, held=None):
        """One async decode micro-step: the operands live on device (``ops``)
        and advance inside the dispatch — the device twin of
        ``ReplicaEngine.apply_decode``. Returns the small sync payload
        (next token, fused retire mask, stepped mask) plus the advanced
        slab and operands; nothing blocks on the host."""
        nxt, done, slab = _fleet_core(p, slab, ops["toks"], ops["pos"],
                                      ops["rem"], ops["eos"], ops["active"],
                                      rows=rows, held=held)
        stepped = ops["active"] if rows is None else \
            ops["active"] & rows[:, None]
        inc = stepped.astype(jnp.int32)
        ops = {
            "toks": jnp.where(stepped, nxt, ops["toks"]),
            "pos": ops["pos"] + inc,
            "rem": ops["rem"] - inc,
            "eos": ops["eos"],
            "active": ops["active"] & ~done,
        }
        return nxt, done, stepped, slab, ops

    def _afleet_fn(p, slab, ops):
        _count("afleet")
        return _afleet_core(p, slab, ops)

    def _afleet_hold_fn(p, slab, ops, hrows, hslots):
        _count("afleet_hold")
        return _afleet_core(p, slab, ops, held=(hrows, hslots))

    def _afleet_masked_fn(p, slab, ops, rows):
        _count("afleet_masked")
        return _afleet_core(p, slab, ops, rows=rows)

    def _afleet_masked_hold_fn(p, slab, ops, rows, hrows, hslots):
        _count("afleet_masked_hold")
        return _afleet_core(p, slab, ops, rows=rows, held=(hrows, hslots))

    def _make_block_fn(K):
        def _afleet_block_fn(p, slab, ops):
            """K fused decode micro-steps in ONE dispatch: ``lax.scan`` over
            the async core (the retire rule is already the device twin of
            the host rule, so EOS/max-tokens/cache-full compose exactly —
            a slot retired at micro-step k is inactive for k+1..K-1). Syncs
            one (K, F, B) token/retire/stepped block."""
            _count("afleet_block")

            def micro(carry, _):
                slab, ops = carry
                nxt, done, stepped, slab, ops = _afleet_core(p, slab, ops)
                return (slab, ops), (nxt, done, stepped)

            (slab, ops), (nxt, done, stepped) = jax.lax.scan(
                micro, (slab, ops), None, length=K)
            return nxt, done, stepped, slab, ops
        return _afleet_block_fn

    def _ops_admit(ops, rows, slots, first, plen, rems, eoss):
        """Device twin of ``commit_admit``: register admitted rows in the
        persistent operands. A request that finishes at prefill time
        (``rem < 1`` i.e. max_new_tokens <= 1, or first token == EOS) never
        activates; the host learns the same outcome at reconcile."""
        return {
            "toks": ops["toks"].at[rows, slots].set(first, mode="drop"),
            "pos": ops["pos"].at[rows, slots].set(plen, mode="drop"),
            "rem": ops["rem"].at[rows, slots].set(rems, mode="drop"),
            "eos": ops["eos"].at[rows, slots].set(eoss, mode="drop"),
            "active": ops["active"].at[rows, slots].set(
                (rems >= 1) & (first != eoss), mode="drop"),
        }

    def _afleet_prefill_fn(p, slab, ops, toks, lens, rows, slots, rems,
                           eoss):
        """Async twin of ``_fleet_prefill_fn``: same slab scatter, plus the
        admitted rows activate in the device operands so the same tick's
        decode dispatch consumes their first token without a host sync."""
        _count("afleet_prefill")
        logits, small, plen = model.prefill(
            p, {"tokens": toks, "lengths": lens}, cache_len=max_seq,
            cache_dtype=cache_dtype)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def scatter(s, sm):
            return s.at[rows, :, slots].set(
                sm.swapaxes(0, 1).astype(s.dtype), mode="drop")

        slab = jax.tree.map(scatter, slab, small)
        ops = _ops_admit(ops, rows, slots, first, plen.astype(jnp.int32),
                         rems, eoss)
        return first, slab, ops

    def _afleet_chunk_fn(p, slab, ops, toks, offs, lens, fresh, rows, slots,
                         final, rems, eoss):
        """Async twin of ``_fleet_chunk_fn``: rows finishing their last
        chunk activate in the device operands (non-final rows' operand
        writes are parked out of bounds and drop)."""
        _count("afleet_chunk")
        sub = jax.tree.map(lambda s: s[rows, :, slots].swapaxes(0, 1), slab)
        first, pos, new_sub = _chunk_core(sub, toks, offs, lens, fresh, p)
        slab = jax.tree.map(
            lambda s, ns: s.at[rows, :, slots].set(
                ns.swapaxes(0, 1).astype(s.dtype), mode="drop"),
            slab, new_sub)
        wrows = jnp.where(final, rows, ops["toks"].shape[0])
        ops = _ops_admit(ops, wrows, slots, first, pos.astype(jnp.int32),
                         rems, eoss)
        return first, slab, ops

    def _fleet_prefill_fn(p, slab, toks, lens, rows, slots):
        """ONE admission dispatch for every same-bucket-length admit across
        the fleet: toks (K, sb) flattens every member's admit rows of the
        same pow2 length bucket into one batch (K itself pow2-padded), runs
        the exact same row-independent prefill as the standalone path, and
        scatters each row's KV/state straight into the donated slab at
        (fleet row ``rows[k]``, slot ``slots[k]``). Pad rows carry
        out-of-bounds indices so their writes drop. Keeping the batch flat
        (rather than vmapping per-member groups) keeps the retrace space at
        O(log(F·max_batch) · log max_seq) — a stacked (groups, kb, sb)
        signature would recompile for every fleet-size/group-count combo.
        Returns the greedy first token and per-row prompt length, (K,)
        each."""
        _count("fleet_prefill")
        logits, small, plen = model.prefill(
            p, {"tokens": toks, "lengths": lens}, cache_len=max_seq,
            cache_dtype=cache_dtype)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def scatter(s, sm):
            return s.at[rows, :, slots].set(
                sm.swapaxes(0, 1).astype(s.dtype), mode="drop")

        return first, plen.astype(jnp.int32), jax.tree.map(scatter, slab,
                                                           small)

    def _chunk_core(state, toks, offs, lens, fresh, p):
        """Shared chunk step on gathered per-slot state (leaves (L, K, ...)):
        zero fresh rows (a first chunk must not see the slot's previous
        occupant's SSM/conv state), advance one chunk, fuse the greedy
        argmax."""
        def zero(t):
            m = fresh.reshape((1, fresh.shape[0]) + (1,) * (t.ndim - 2))
            return jnp.where(m, jnp.zeros((), t.dtype), t)

        state = jax.tree.map(zero, state)
        logits, new_state, pos = model.prefill_chunk(p, state, toks, offs,
                                                     lens)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return first, pos, new_state

    def _chunk_fn(p, cache, toks, offs, lens, fresh, slots):
        """Standalone chunk step: gather ``slots`` rows from the engine
        cache (leaves (L, B, ...)), advance, scatter back. Pad rows carry an
        out-of-bounds slot so their writes drop."""
        _count("chunk")
        sub = jax.tree.map(lambda t: jnp.take(t, slots, axis=1), cache)
        first, pos, new_sub = _chunk_core(sub, toks, offs, lens, fresh, p)
        cache = jax.tree.map(
            lambda t, ns: t.at[:, slots].set(ns.astype(t.dtype), mode="drop"),
            cache, new_sub)
        return first, pos, cache

    def _fleet_chunk_fn(p, slab, toks, offs, lens, fresh, rows, slots):
        """ONE chunk dispatch for every due chunk row across the fleet:
        gather (fleet row, slot) state from the donated slab, advance one
        chunk, scatter back."""
        _count("fleet_chunk")
        sub = jax.tree.map(lambda s: s[rows, :, slots].swapaxes(0, 1), slab)
        first, pos, new_sub = _chunk_core(sub, toks, offs, lens, fresh, p)
        slab = jax.tree.map(
            lambda s, ns: s.at[rows, :, slots].set(
                ns.swapaxes(0, 1).astype(s.dtype), mode="drop"),
            slab, new_sub)
        return first, pos, slab

    k.prefill = jax.jit(_prefill_fn)
    k.decode = jax.jit(_decode_fn)
    k.decode_hold = jax.jit(_decode_hold_fn)
    # the fleet slab is owned exclusively by the FleetGroup (member engines
    # hold cache=None), so the input buffer can be donated: XLA updates the
    # KV slab in place instead of copying it every dispatch. The standalone
    # chunk kernel donates the engine cache the same way.
    k.fleet = jax.jit(_fleet_fn, donate_argnums=(1,))
    k.fleet_hold = jax.jit(_fleet_hold_fn, donate_argnums=(1,))
    k.fleet_masked = jax.jit(_fleet_masked_fn, donate_argnums=(1,))
    k.fleet_masked_hold = jax.jit(_fleet_masked_hold_fn, donate_argnums=(1,))
    k.fleet_prefill = jax.jit(_fleet_prefill_fn, donate_argnums=(1,))
    k.chunk = jax.jit(_chunk_fn, donate_argnums=(1,))
    k.fleet_chunk = jax.jit(_fleet_chunk_fn, donate_argnums=(1,))
    # async variants: slab AND operands are donated (both exclusively owned
    # by the FleetGroup), so consecutive ticks chain in place on device
    k.afleet = jax.jit(_afleet_fn, donate_argnums=(1, 2))
    k.afleet_hold = jax.jit(_afleet_hold_fn, donate_argnums=(1, 2))
    k.afleet_masked = jax.jit(_afleet_masked_fn, donate_argnums=(1, 2))
    k.afleet_masked_hold = jax.jit(_afleet_masked_hold_fn,
                                   donate_argnums=(1, 2))
    k.afleet_prefill = jax.jit(_afleet_prefill_fn, donate_argnums=(1, 2))
    k.afleet_chunk = jax.jit(_afleet_chunk_fn, donate_argnums=(1, 2))
    k.afleet_block = {}
    k._block_factory = _make_block_fn
    cache[key] = k
    return k


def _pack_chunk_rows(rows, chunk_len: int):
    """Pack per-slot chunk work items ``(toks, off, ln, fresh)`` into the
    pow2-padded host arrays both chunk kernels take (pad rows: length-1
    dummies whose index columns the caller points out of bounds)."""
    K = pow2_bucket(len(rows))
    toks = np.zeros((K, chunk_len), np.int32)
    offs = np.zeros(K, np.int32)
    lens = np.ones(K, np.int32)
    fresh = np.zeros(K, bool)
    for i, (t, off, ln, fr) in enumerate(rows):
        toks[i], offs[i], lens[i], fresh[i] = t, off, ln, fr
    return K, toks, offs, lens, fresh


@dataclasses.dataclass
class _ChunkCursor:
    """Per-slot chunked-prefill progress: the (truncated) prompt streaming
    into the slot and how many tokens earlier chunks consumed."""
    req: "Request"
    prompt: list
    consumed: int = 0


@dataclasses.dataclass
class _AdmitPlans:
    """Host-side admission decisions for one engine step (no dispatches):
    ``bucketed`` groups share one pow2-bucket prefill each, ``singles`` are
    exact-length admits (vlm/audio extras, moe exactness). Chunk starts are
    recorded directly on the engine's cursor table."""
    bucketed: list          # [(slots, reqs)]
    singles: list           # [(slot, req)]
    expired: list = dataclasses.field(default_factory=list)
    # queue heads whose deadline already passed — popped without consuming
    # a slot (admitting them would waste a prefill on a request that could
    # emit at most one truncated token); retired directly into ``finished``


class TieredQueue:
    """Per-tier FIFO queues drained in weighted-deficit round-robin order.

    Each tier owns a FIFO deque and a deficit counter. ``peek``/``pop``
    implement classic DRR with a unit request cost: when no backlogged tier
    holds a full credit, every backlogged tier earns its quantum
    (``weight / max_weight``), then the highest-priority tier with credit
    supplies the next request. The top-weight tier therefore admits first
    (its quantum is exactly 1.0), while a weight-w tier is still guaranteed
    ~w/w_max of admissions under sustained higher-tier load — weighted
    fairness with a hard no-starvation bound. Deficits persist across ticks
    so short admission windows can't bias the long-run shares; an empty
    tier's banked credit resets (no burst debt).

    With a single tier the discipline degenerates to the plain FIFO deque
    this class replaced: same pops, same order, bit-identical streams.
    ``popleft``/``__iter__`` expose global arrival order for the drain and
    failure hand-back paths, which must not apply scheduling priority."""

    def __init__(self, tiers: TierSet):
        self.tiers = tiers
        self._qs = [deque() for _ in tiers.specs]
        self._deficit = [0.0] * len(tiers)
        wmax = max(float(w) for w in tiers.weights)
        self._quantum = [float(w) / wmax for w in tiers.weights]

    def __len__(self) -> int:
        return sum(len(q) for q in self._qs)

    def __bool__(self) -> bool:
        return any(self._qs)

    def __iter__(self):
        """All queued requests in global arrival order (rid tiebreak)."""
        return iter(sorted((r for q in self._qs for r in q),
                           key=lambda r: (r.arrival, r.rid)))

    def append(self, req):
        self._qs[self.tiers.index(getattr(req, "tier", "standard"))] \
            .append(req)

    def clear(self):
        for q in self._qs:
            q.clear()

    def popleft(self):
        """Earliest-arrival request across all tiers (hand-back order for
        drain/evacuate — deliberately NOT the scheduling order)."""
        cands = [q for q in self._qs if q]
        if not cands:
            raise IndexError("pop from an empty TieredQueue")
        best = min(cands, key=lambda q: (q[0].arrival, q[0].rid))
        return best.popleft()

    def depths(self) -> list:
        """Per-tier queue lengths (declaration order)."""
        return [len(q) for q in self._qs]

    def higher_waiting(self, tier_idx: int) -> bool:
        """Any queued work in a strictly higher-priority tier?"""
        rank = self.tiers._rank[tier_idx]
        return any(self._qs[t] for t in self.tiers.priority[:rank])

    def _head_tier(self, exclude) -> Optional[int]:
        live = [t for t in self.tiers.priority
                if self._qs[t] and t not in exclude]
        if not live:
            return None
        for t, q in enumerate(self._qs):     # empty tiers bank no credit
            if not q:
                self._deficit[t] = 0.0
        while True:
            for t in live:                   # priority order within a round
                if self._deficit[t] >= 1.0 - 1e-9:
                    return t
            for t in live:
                self._deficit[t] += self._quantum[t]

    def peek(self, exclude=()) -> Optional[tuple]:
        """(tier_idx, request) the next ``pop`` would return, or None.
        Idempotent: repeated peeks without a pop return the same head."""
        t = self._head_tier(exclude)
        return None if t is None else (t, self._qs[t][0])

    def pop(self, exclude=()):
        t = self._head_tier(exclude)
        if t is None:
            raise IndexError("pop from an empty TieredQueue")
        self._deficit[t] -= 1.0
        return self._qs[t].popleft()


class FleetGroup:
    """Stacks the device state of same-shape replicas along a leading fleet
    axis and advances every member with one jitted dispatch per tick.

    The slab capacity grows in power-of-two steps (O(log F) retraces as the
    fleet scales 1 -> F); spare rows decode throwaway state and are fully
    overwritten when a replica joins, so they need no masking. Removing a
    member (drain retire / failure) backfills its row with the last member's
    row in a single device op, so live rows stay dense.

    ``admit_round`` is the admission twin of ``decode_round``: members'
    bucketed admit rows of the same pow2 length bucket flatten into ONE
    ``fleet_prefill`` per distinct bucket, and all due chunk rows into ONE
    ``fleet_chunk`` — each writing KV/state straight into the donated slab.
    ``prefill_dispatches`` mirrors ``dispatches``.

    With ``async_mode`` the dispatch methods never block: device results
    queue on ``pending`` and the deferred host bookkeeping applies at the
    next ``reconcile()`` — one blocking sync per tick (``syncs``), with the
    decode operands persistent on device (``ops``). See the module
    docstring's async tick contract.

    **Shard contract** (``mesh`` with a ``fleet`` axis, N = shard count).
    The slab's leading fleet axis (and the async operands') is laid out
    ``NamedSharding(mesh, P('fleet'))`` — device d owns the contiguous row
    block [d·cap/N, (d+1)·cap/N) — while ``params`` replicate across the
    fleet axis, so GSPMD partitions the *existing* jitted kernel families
    row-parallel: still ONE logical dispatch per kernel variant per tick and
    ONE reconcile sync, now fanned out over N devices. Invariants:

      * **divisibility** — slab capacity is always a multiple of N:
        ``cap = N * pow2_bucket(ceil(F / N))`` (per-shard sub-capacity grows
        in pow2 steps, O(log ceil(F/N)) retraces). The extra rows are pad
        rows exactly like the unsharded spares: masked inactive (never in
        ``movers``/``active``) and excluded from dispatch and retire
        accounting, they only burn bounded throwaway compute;
      * **row re-balance on churn** — live rows stay DENSE in [0, F) (joins
        append, removals swap-backfill with the last row), so with block
        layout the F live rows spread across shards as evenly as contiguous
        blocks allow; membership changes force-flush pending futures first,
        exactly like the unsharded async path;
      * **bit-identical streams** — the kernels are mesh-agnostic (sharding
        only partitions them), so token streams and finish clocks equal the
        unsharded oracle across churn/async/chunk/tier (tests/
        test_fleet_shard.py)."""

    def __init__(self, model: Model, params, *, max_batch: int, max_seq: int,
                 cache_dtype=jnp.float32, async_mode: bool = False,
                 decode_block: int = 1, attn_backend: str = "einsum",
                 mesh=None):
        self.model = model
        self.mesh = mesh
        if mesh is not None and "fleet" not in mesh.axis_names:
            raise ValueError(
                f"FleetGroup mesh needs a 'fleet' axis, got "
                f"{mesh.axis_names}")
        self.shards = int(mesh.shape["fleet"]) if mesh is not None else 1
        if mesh is not None:
            # replicate the weights across the fleet axis once: every shard
            # decodes its own slab rows against the full params (serve-mode
            # rule — see distributed.sharding), and a device-0-committed
            # params array mixed with a sharded slab is a placement error
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        self.attn_backend = attn_backend
        self.members: list = []     # ReplicaEngine; fleet row == list index
        self.cap = 0                # allocated fleet rows (power of two)
        self.slab = None            # cache pytree, leaves (cap, *per_replica)
        self.dispatches = 0         # jitted fleet decode dispatches issued
        self.prefill_dispatches = 0  # jitted fleet admission dispatches
        self.async_mode = bool(async_mode)
        self.decode_block = max(1, int(decode_block))
        self.ops = None             # device decode operands (async mode)
        self.pending: list = []     # _Pending device results, un-synced
        self._stash: list = []      # finishes from forced flushes (churn)
        self._admitted = False      # eager single-admit landed this tick
        self.syncs = 0              # blocking host syncs performed
        self.sync_wait = 0.0        # seconds spent blocked on device results
        self._block_credit = 0      # ticks already covered by a decode block
        self._kernels = get_serve_kernels(model, max_seq, cache_dtype,
                                          attn_backend)

    def __len__(self) -> int:
        return len(self.members)

    # ------------------------------------------------------------- sharding
    def _cap_for(self, rows: int) -> int:
        """Slab capacity for ``rows`` members. Unsharded: the next power of
        two. Sharded: the per-shard sub-capacity grows in pow2 steps instead
        (cap = shards * pow2_bucket(ceil(rows / shards))), keeping the fleet
        axis divisible by the shard count (a non-dividing axis would silently
        fall back to replication) at the same O(log) retrace bound."""
        if self.shards == 1:
            return pow2_bucket(rows)
        return self.shards * pow2_bucket(-(-rows // self.shards))

    def _replicated(self, x):
        """Replicate a host/device-0 value over the mesh so eager mixed ops
        against the sharded slab are placement-legal (eager updates with one
        mesh-sharded and one device-0-committed operand are an error)."""
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def _zero_slab(self, cap: int):
        """A zero slab of ``cap`` rows, allocated where it will live (the
        fleet sharding under a mesh): no full-size staging copy lands on
        the default device."""
        per_replica = jax.eval_shape(lambda: self.model.init_serve_state(
            self.max_batch, self.max_seq, self.cache_dtype))
        shapes = jax.tree.map(
            lambda c: jax.ShapeDtypeStruct((cap,) + c.shape, c.dtype),
            per_replica)
        if self.mesh is None:
            return jax.tree.map(lambda c: jnp.zeros(c.shape, c.dtype), shapes)
        return jax.tree.map(
            lambda c, sh: jnp.zeros(c.shape, c.dtype, device=sh),
            shapes, fleet_slab_shardings(self.mesh, shapes))

    def _place_slab(self, slab):
        """Pin the slab's sharding: fleet axis over the mesh's fleet axis,
        per-replica cache dims under the serve-mode rules."""
        return jax.device_put(slab, fleet_slab_shardings(self.mesh, slab))

    def _place_ops(self, ops):
        return jax.device_put(ops, NamedSharding(self.mesh, P("fleet")))

    # -------------------------------------------------------------- members
    def add(self, eng: "ReplicaEngine"):
        """Stack ``eng``'s device cache into the slab (any in-flight slot
        state rides along, so replicas can join mid-generation). Pending
        futures flush first so the operand seed sees current host state."""
        assert eng._fleet is None, "engine already belongs to a fleet"
        if self.pending:
            self._stash += self.reconcile(force=True)
        row = len(self.members)
        if row >= self.cap:
            new_cap = self._cap_for(row + 1)
            slab = self._zero_slab(new_cap)
            if self.slab is None:
                self.slab = slab
                if self.async_mode:
                    self.ops = _init_ops(new_cap, self.max_batch)
            else:
                self.slab = jax.tree.map(lambda n, s: n.at[:self.cap].set(s),
                                         slab, self.slab)
                if self.async_mode:
                    grow = lambda s: jnp.concatenate(
                        [s, jnp.zeros((new_cap - self.cap,) + s.shape[1:],
                                      s.dtype)])
                    self.ops = jax.tree.map(grow, self.ops)
            self.cap = new_cap
            if self.mesh is not None:
                # re-pin after (re)allocation: the eager copy above and the
                # operands' concatenate need not keep the fleet sharding,
                # and GSPMD must row-partition every subsequent dispatch
                self.slab = self._place_slab(self.slab)
                if self.async_mode:
                    self.ops = self._place_ops(self.ops)
        if eng.cache is None:
            # a fresh replica: its slots are empty, so its row only has to
            # be cleared of a former member's state (never built whole
            # elsewhere first)
            self.slab = jax.tree.map(
                lambda s: s.at[row].set(jnp.zeros((), s.dtype)), self.slab)
        else:
            cache = eng.cache if self.mesh is None \
                else self._replicated(eng.cache)
            self.slab = jax.tree.map(lambda s, c: s.at[row].set(c),
                                     self.slab, cache)
        if self.async_mode:
            self._seed_ops_row(row, eng)
        eng.cache = None
        eng._fleet, eng._fleet_row = self, row
        self.members.append(eng)

    def _seed_ops_row(self, row: int, eng: "ReplicaEngine"):
        """Initialize the device operands for a joining member from its
        host mirrors (it may carry in-flight slots mid-generation)."""
        B = self.max_batch
        rem = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        act = np.zeros(B, bool)
        for s, req in enumerate(eng.slots):
            if req is not None and s not in eng._chunks:
                act[s] = True
                rem[s] = req.rem_tokens(eng.clock)
                eos[s] = req.eos_id
        vals = {"toks": np.asarray(eng.last_tok, np.int32),
                "pos": np.asarray(eng.pos, np.int32),
                "rem": rem, "eos": eos, "active": act}
        self.ops = {kk: self.ops[kk].at[row].set(vals[kk])
                    for kk in self.ops}

    def remove(self, eng: "ReplicaEngine", restore: bool = True):
        """Detach ``eng``; with ``restore`` its cache row is unstacked back
        onto the engine (drain hand-back), otherwise dropped (failure).
        Pending futures flush first (host mirrors must be current before a
        row unstacks or backfills — the churn half of the async contract)."""
        if self.pending:
            self._stash += self.reconcile(force=True)
        row = eng._fleet_row
        assert eng._fleet is self and self.members[row] is eng
        if restore:
            eng.cache = jax.tree.map(lambda s: s[row], self.slab)
            if self.mesh is not None:
                # the detached engine steps standalone against its own
                # params, so its cache goes where they live (the eager
                # slice above is committed to the whole mesh)
                eng.cache = jax.device_put(
                    eng.cache, jax.tree.leaves(eng.params)[0].sharding)
        last = self.members.pop()
        if last is not eng:          # backfill the hole with the last row
            backfill = lambda s: s.at[row].set(s[len(self.members)])
            self.slab = jax.tree.map(backfill, self.slab)
            if self.async_mode:
                self.ops = jax.tree.map(backfill, self.ops)
            last._fleet_row = row
            self.members[row] = last
        eng._fleet, eng._fleet_row = None, -1

    # -------------------------------------------------------------- slots
    def write_slot(self, f: int, slot: int, small_state, row: int,
                   req: Optional["Request"] = None, prompt_len: int = 0):
        """Copy prefill output row ``row`` into member ``f``'s slot (the
        exact-length single-admit path; bucketed admits scatter on device
        inside ``fleet_prefill`` instead). In async mode the slot also
        registers in the device operands (``req``'s first token was already
        synced by the eager single-admit path)."""
        if self.mesh is not None:
            small_state = self._replicated(small_state)
        self.slab = jax.tree.map(
            lambda s, sm: s.at[f, :, slot].set(sm[:, row]),
            self.slab, small_state)
        if self.async_mode and req is not None:
            o = self.ops
            self.ops = {
                "toks": o["toks"].at[f, slot].set(int(req.output[-1])),
                "pos": o["pos"].at[f, slot].set(int(prompt_len)),
                "rem": o["rem"].at[f, slot].set(
                    req.rem_tokens(self.members[f].clock)),
                "eos": o["eos"].at[f, slot].set(int(req.eos_id)),
                "active": o["active"].at[f, slot].set(True),
            }
            # single admits bypass ``pending`` (their sync was eager), so
            # they must veto fused-block engagement separately — a tick
            # that admitted anything never fuses
            self._admitted = True

    # -------------------------------------------------------------- admit
    def admit_round(self, stepping_ids=None) -> list:
        """One fused admission step for every member (or the ``id(engine)``
        subset in ``stepping_ids``): plan each member's admissions on the
        host, then flatten same-length-bucket admit rows into one
        ``fleet_prefill`` per distinct bucket and all due chunk rows into
        one ``fleet_chunk``. Exact-length single admits (extras / moe) keep
        the per-request path. Returns requests finished at prefill time."""
        movers = [e for e in self.members
                  if stepping_ids is None or id(e) in stepping_ids]
        finished: list = []
        buckets: dict = {}       # sb -> [(engine, slot, req, prompt)] rows
        chunk_rows: list = []    # (engine, slot, toks, off, ln, fresh, final)
        for e in movers:
            plans = e.plan_admission()
            finished.extend(plans.expired)
            for slot, req in plans.singles:
                e._admit_batch([slot], [req], finished, bucketed=False)
            for slots, reqs in plans.bucketed:
                prompts = [r.prompt[-(self.max_seq - 1):] for r in reqs]
                # the length bucket is chosen per member group exactly like
                # the standalone path; rows of the same bucket then flatten
                # into one fleet-wide batch
                sb = min(pow2_bucket(max(len(p) for p in prompts),
                                     e.min_bucket), self.max_seq)
                buckets.setdefault(sb, []).extend(
                    (e, s, r, p) for s, r, p in zip(slots, reqs, prompts))
            for row in e._chunk_rows():
                chunk_rows.append((e,) + row)
        for sb, entries in sorted(buckets.items()):
            self._dispatch_fleet_prefill(sb, entries, finished)
        if chunk_rows:
            self._dispatch_fleet_chunk(chunk_rows, finished)
        return finished

    def _dispatch_fleet_prefill(self, sb: int, entries: list,
                                finished: list):
        K = pow2_bucket(len(entries))
        toks = np.zeros((K, sb), np.int32)
        lens = np.ones(K, np.int32)             # pad rows: length-1 dummies
        rows = np.full(K, self.cap, np.int32)   # OOB pad rows -> dropped
        slots = np.full(K, self.max_batch, np.int32)
        rems = np.zeros(K, np.int32)
        eoss = np.full(K, -1, np.int32)
        for i, (e, slot, req, p) in enumerate(entries):
            toks[i, :len(p)] = p
            lens[i] = len(p)
            rows[i], slots[i] = e._fleet_row, slot
            rems[i] = req.rem_tokens(e.clock) - 1
            eoss[i] = req.eos_id
        if self.async_mode:
            first, self.slab, self.ops = self._kernels.afleet_prefill(
                self.params, self.slab, self.ops, jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(rows), jnp.asarray(slots),
                jnp.asarray(rems), jnp.asarray(eoss))
            self.prefill_dispatches += 1
            meta = []
            for i, (e, slot, req, p) in enumerate(entries):
                e.slots[slot] = req      # reserve now; commit at reconcile
                meta.append((i, e, slot, req, len(p), e.clock))
            self.pending.append(_Pending("prefill", first, meta))
            return
        first, plen, self.slab = self._kernels.fleet_prefill(
            self.params, self.slab, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(rows), jnp.asarray(slots))
        self.prefill_dispatches += 1
        first, plen = _timed_get(self, (first, plen))
        first, plen = np.asarray(first), np.asarray(plen)
        for i, (e, slot, req, p) in enumerate(entries):
            e.commit_admit([slot], [req], first[i:i + 1], plen[i:i + 1],
                           finished)

    def _dispatch_fleet_chunk(self, chunk_rows: list, finished: list):
        # members may carry different chunk_len settings; each width is its
        # own fixed kernel shape
        by_width: dict = {}
        for item in chunk_rows:
            by_width.setdefault(item[0].chunk_len, []).append(item)
        for C, items in sorted(by_width.items()):
            K, toks, offs, lens, fresh = _pack_chunk_rows(
                [(t, off, ln, fr) for _, _, t, off, ln, fr, _ in items], C)
            rows = np.full(K, self.cap, np.int32)       # OOB pads -> dropped
            slots = np.full(K, self.max_batch, np.int32)
            for i, (e, slot, *_rest) in enumerate(items):
                rows[i], slots[i] = e._fleet_row, slot
            if self.async_mode:
                final = np.zeros(K, bool)
                rems = np.zeros(K, np.int32)
                eoss = np.full(K, -1, np.int32)
                for i, (e, slot, t, off, ln, fr, fin) in enumerate(items):
                    req = e._chunks[slot].req
                    final[i] = fin
                    rems[i] = req.rem_tokens(e.clock) - 1
                    eoss[i] = req.eos_id
                first, self.slab, self.ops = self._kernels.afleet_chunk(
                    self.params, self.slab, self.ops, jnp.asarray(toks),
                    jnp.asarray(offs), jnp.asarray(lens), jnp.asarray(fresh),
                    jnp.asarray(rows), jnp.asarray(slots),
                    jnp.asarray(final), jnp.asarray(rems), jnp.asarray(eoss))
                self.prefill_dispatches += 1
                meta = []
                for i, (e, slot, t, off, ln, fr, fin) in enumerate(items):
                    cur = e._chunks[slot]
                    if not fin:          # cursor advance is host-computable
                        cur.consumed += e.chunk_len
                        continue
                    del e._chunks[slot]  # slot stays reserved (slots[slot])
                    meta.append((i, e, slot, cur.req, off + ln, e.clock))
                if meta:
                    self.pending.append(_Pending("chunk", first, meta))
                continue
            first, pos, self.slab = self._kernels.fleet_chunk(
                self.params, self.slab, jnp.asarray(toks), jnp.asarray(offs),
                jnp.asarray(lens), jnp.asarray(fresh), jnp.asarray(rows),
                jnp.asarray(slots))
            self.prefill_dispatches += 1
            first, pos = _timed_get(self, (first, pos))
            first, pos = np.asarray(first), np.asarray(pos)
            for i, (e, slot, t, off, ln, fr, fin) in enumerate(items):
                e.commit_chunk(slot, first[i], pos[i], fin, finished)

    # -------------------------------------------------------------- decode
    def decode_round(self, stepping_ids=None, allow_block: bool = False
                     ) -> list:
        """One fused decode step for every member (or the ``id(engine)``
        subset in ``stepping_ids``). Returns finished requests. Eager: one
        jitted dispatch plus one small (F, B) host sync. Async: one jitted
        dispatch, NO sync (results commit at the next ``reconcile``), and
        with ``allow_block`` a K-micro-step fused block may engage on a
        tick that admitted nothing — covering the next K-1 ticks' decode
        in this single dispatch."""
        movers = [e for e in self.members
                  if stepping_ids is None or id(e) in stepping_ids]
        if self.async_mode:
            return self._decode_round_async(movers, allow_block)
        if not movers or not any(e.n_decoding for e in movers):
            return []
        cap, B = self.cap, self.max_batch
        toks = np.zeros((cap, B), np.int32)
        pos = np.zeros((cap, B), np.int32)
        rem = np.ones((cap, B), np.int32)
        eos = np.full((cap, B), -1, np.int32)
        active = np.zeros((cap, B), bool)
        rows = np.zeros((cap,), bool)
        held: list = []              # mid-chunk (row, slot): state must not
        for e in movers:             # move this round
            f = e._fleet_row
            rows[f] = True
            toks[f] = e.last_tok
            pos[f] = e.pos
            held.extend((f, s) for s in e._chunks)
            for s, req in enumerate(e.slots):
                if req is not None and s not in e._chunks:
                    active[f, s] = True
                    rem[f, s] = req.rem_tokens(e.clock)
                    eos[f, s] = req.eos_id
        if held:                     # pow2-padded OOB -> dropped on scatter
            hk = pow2_bucket(len(held))
            hrows = np.full(hk, cap, np.int32)
            hslots = np.full(hk, B, np.int32)
            for i, (f, s) in enumerate(held):
                hrows[i], hslots[i] = f, s
        if len(movers) == len(self.members):
            if held:
                nxt, done, self.slab = self._kernels.fleet_hold(
                    self.params, self.slab, toks, pos, rem, eos, active,
                    hrows, hslots)
            else:
                nxt, done, self.slab = self._kernels.fleet(
                    self.params, self.slab, toks, pos, rem, eos, active)
        elif held:
            nxt, done, self.slab = self._kernels.fleet_masked_hold(
                self.params, self.slab, toks, pos, rem, eos, active, rows,
                hrows, hslots)
        else:
            nxt, done, self.slab = self._kernels.fleet_masked(
                self.params, self.slab, toks, pos, rem, eos, active, rows)
        self.dispatches += 1
        nxt, done = _timed_get(self, (nxt, done))   # ONE small host sync
        nxt, done = np.asarray(nxt), np.asarray(done)
        finished: list = []
        for e in movers:
            f = e._fleet_row
            finished.extend(e.commit_decode(nxt[f], done[f]))
        return finished

    def _decode_round_async(self, movers: list, allow_block: bool) -> list:
        """Sync-free decode round: operands already live on device, so the
        dispatch takes only the cheap host-known masks (held chunk slots,
        stepping rows). Results queue on ``pending``."""
        if self._block_credit > 0:      # a fused block covers this tick
            self._block_credit -= 1
            return []
        if not movers or not any(e.n_decoding for e in movers):
            return []
        cap, B = self.cap, self.max_batch
        held = [(e._fleet_row, s) for e in movers for s in e._chunks]
        full = len(movers) == len(self.members)
        K = self.decode_block
        meta = [(e, e._fleet_row, e.clock) for e in movers]
        # fused-block engagement: only on ticks with no admissions at all —
        # ``pending`` catches this tick's fleet prefill/chunk dispatches
        # (the tick-start reconcile cleared the previous window) and
        # ``_admitted`` the eager single-admit path — and no chunk cursors
        # anywhere. Queued work behind a FULL slab does not block
        # engagement: any admission landing inside the fused window (a
        # retire freeing a slot, or an arrival finding one) only starts
        # decoding at the window's end, i.e. admission-to-first-decode may
        # lag up to K-1 ticks (the documented decode_block trade; async
        # with K=1 keeps the <= 1-tick bound)
        admitted, self._admitted = self._admitted, False
        if (allow_block and K > 1 and full and not held and not self.pending
                and not admitted
                and all(not e._chunks for e in self.members)):
            nxt, done, stepped, self.slab, self.ops = \
                self._kernels.block_kernel(K)(self.params, self.slab,
                                              self.ops)
            self.dispatches += 1
            self._block_credit = K - 1
            self.pending.append(_Pending("block", (nxt, done, stepped),
                                         meta))
            return []
        if held:                     # pow2-padded OOB -> dropped on scatter
            hk = pow2_bucket(len(held))
            hrows = np.full(hk, cap, np.int32)
            hslots = np.full(hk, B, np.int32)
            for i, (f, s) in enumerate(held):
                hrows[i], hslots[i] = f, s
        if full:
            if held:
                out = self._kernels.afleet_hold(self.params, self.slab,
                                                self.ops, hrows, hslots)
            else:
                out = self._kernels.afleet(self.params, self.slab, self.ops)
        else:
            rows = np.zeros((cap,), bool)
            for e in movers:
                rows[e._fleet_row] = True
            if held:
                out = self._kernels.afleet_masked_hold(
                    self.params, self.slab, self.ops, rows, hrows, hslots)
            else:
                out = self._kernels.afleet_masked(self.params, self.slab,
                                                  self.ops, rows)
        nxt, done, stepped, self.slab, self.ops = out
        self.dispatches += 1
        self.pending.append(_Pending("decode", (nxt, done, stepped), meta))
        return []

    # ----------------------------------------------------------- reconcile
    def take_stash(self) -> list:
        """Drain finishes produced by forced mid-tick flushes (membership
        churn) without touching still-pending futures."""
        out = list(self._stash)
        self._stash.clear()
        return out

    def reconcile(self, force: bool = False) -> list:
        """The ONE blocking host sync per tick: fetch every pending device
        result together and apply the deferred host bookkeeping in dispatch
        order (prefill first-tokens before the same tick's decode tokens —
        the exact replay of the eager host effects, one tick late). Returns
        newly finished requests, stamped with their dispatch-time clocks.
        While a decode block still covers upcoming ticks the fetch is
        deferred (that is the < 1 sync/tick regime) unless ``force``d by
        membership churn."""
        # mutate the stash in place: callers flush via
        # ``self._stash += self.reconcile(...)`` and a reassignment here
        # would strand their appends on the orphaned old list (the bound
        # method/in-place target resolves BEFORE this call runs)
        finished: list = list(self._stash)
        self._stash.clear()
        if not self.pending or (self._block_credit > 0 and not force):
            return finished
        pend, self.pending = self.pending, []
        fetched = _timed_get(self, [p.arrays for p in pend])
        for p, vals in zip(pend, fetched):
            if p.kind == "decode":
                self._apply_decode(vals, p.meta, finished)
            elif p.kind == "block":
                self._apply_block(vals, p.meta, finished)
            else:                    # "prefill" and final-"chunk" commits
                self._apply_admit(vals, p.meta, finished)
        return finished

    def _apply_decode(self, arrays, meta: list, finished: list):
        nxt, done, stepped = (np.asarray(a) for a in arrays)
        for e, row, clock in meta:
            finished.extend(e.apply_decode(nxt[row], done[row], stepped[row],
                                           clock))

    def _apply_block(self, arrays, meta: list, finished: list):
        nxt, done, stepped = (np.asarray(a) for a in arrays)  # (K, F, B)
        for k in range(nxt.shape[0]):        # micro-step k == tick clock+k
            for e, row, clock in meta:
                finished.extend(e.apply_decode(nxt[k, row], done[k, row],
                                               stepped[k, row], clock + k))

    def _apply_admit(self, first, meta: list, finished: list):
        """Deferred ``commit_admit``/final-chunk ``commit_chunk``: the slot
        was reserved at dispatch (and non-final chunk cursor advances were
        committed host-side there); now the first generated token, the TTFT
        stamp and the finish-at-prefill rule apply. ``pos`` in the meta is
        the host-computed cache frontier (prompt length, or chunk offset +
        length)."""
        first = np.asarray(first)
        for i, e, slot, req, pos, clock in meta:
            tok = int(first[i])
            req.output.append(tok)
            req.first_token_time = clock
            if len(req.output) >= req.max_new_tokens or tok == req.eos_id \
                    or req.out_of_time(clock):
                req.finish_time = clock
                finished.append(req)
                e.slots[slot] = None
                continue
            e.pos[slot] = pos
            e.last_tok[slot] = tok


def total_prefill_traces(engines) -> int:
    """Global prefill-side compile count (bucketed + fleet + chunk kernel
    variants), deduped across replicas that share kernels (each replica
    reports its shared counter)."""
    seen = {id(e._kernels): e._kernels.prefill_traces for e in engines}
    return sum(seen.values())


def total_serve_traces(engines) -> int:
    """Global compile count across *every* serve-kernel variant (prefill,
    decode, decode_hold, fleet, fleet_masked, fleet_prefill, chunk,
    fleet_chunk), deduped across replicas sharing kernels."""
    seen = {id(e._kernels): e._kernels.total_traces for e in engines}
    return sum(seen.values())


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new_tokens: int = 16
    eos_id: int = -1               # -1: never stop early
    arrival: float = 0.0
    tier: str = "standard"         # SLO tier name (see workload.trace)
    # deadline (absolute tick, None = no deadline): past it the request is
    # worthless to its client — in-flight slots retire through the existing
    # fleet/afleet ``rem <= 1`` rule (the host clamps the remaining-token
    # budget, see ``rem_tokens``; no new kernels, no extra dispatches) and
    # queued copies are culled at admission time. Deadlines are denominated
    # in ticks and enforced at one decode step per tick; a speed>1 replica's
    # extra sub-steps only ever retire it conservatively *earlier*.
    deadline_tick: Optional[float] = None
    # filled by the engine
    output: list = dataclasses.field(default_factory=list)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.finish_time is not None

    @property
    def expired(self) -> bool:
        """Finished by deadline expiry rather than on its own terms: the
        output was truncated — neither the token budget nor EOS ended it —
        and only the deadline clamp / queue cull truncates. The finish
        stamp can land *before* the deadline (a request admitted at tick t
        also decodes at tick t, outrunning the 1-token/tick clamp budget),
        so truncation, not ``finish_time``, is the signal. Never true
        without a deadline, so deadline-free workloads classify exactly
        as before."""
        return (self.deadline_tick is not None
                and self.finish_time is not None
                and len(self.output) < self.max_new_tokens
                and (not self.output or self.output[-1] != self.eos_id))

    def rem_tokens(self, clock: float) -> int:
        """Remaining-token budget at ``clock`` — the value the fleet/afleet
        retire rule consumes as ``rem``. Without a deadline this is exactly
        the historical ``max_new_tokens - len(output)``; with one, it is
        additionally clamped so the slot retires (``rem <= 1``) no later
        than the deadline tick. Both budgets decrement one per decode step,
        so a value seeded once into the async device operands stays the
        exact min at every later micro-step."""
        rem = self.max_new_tokens - len(self.output)
        if self.deadline_tick is not None:
            rem = min(rem, int(self.deadline_tick - clock) + 1)
        return rem

    def out_of_time(self, clock: float) -> bool:
        """Host twin of the deadline half of the device retire rule: at
        ``clock >= deadline_tick`` the deadline-clamped ``rem`` is <= 1, so
        the token appended at ``clock`` is the slot's last."""
        return self.deadline_tick is not None and clock >= self.deadline_tick

    def reset_progress(self):
        """Forget generation progress (replica failure -> re-queue)."""
        self.output = []
        self.first_token_time = None
        self.finish_time = None


class ReplicaEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 4,
                 max_seq: int = 256, cache_dtype=jnp.float32, rid: int = 0,
                 speed: float = 1.0, min_bucket: int = 8,
                 bucket_prompts: Optional[bool] = None, chunk_len: int = 0,
                 tiers: Optional[TierSet] = None,
                 attn_backend: str = "einsum"):
        if attn_backend not in ("einsum", "pallas"):
            raise ValueError(f"unknown attn_backend {attn_backend!r}")
        if attn_backend == "pallas" and \
                model.cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                "attn_backend='pallas' needs the attention-KV decode path; "
                f"family={model.cfg.family!r} decodes through ssm/encdec "
                "state")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        self.attn_backend = attn_backend
        self.rid = rid
        self.speed = speed            # relative decode speed (hetero hardware)
        self.min_bucket = min_bucket
        self.draining = False         # drained replicas admit nothing new
        # device KV/state pool, built on first standalone use: a replica
        # that joins a fleet keeps its state in the fleet slab instead
        self.cache = None
        self.pos = np.zeros(max_batch, np.int32)       # next cache index
        self.last_tok = np.zeros(max_batch, np.int32)
        self.slots: list = [None] * max_batch
        self.tiers = tiers or DEFAULT_TIERS
        self.queue: TieredQueue = TieredQueue(self.tiers)
        self.clock = 0.0
        self.steps = 0
        self.syncs = 0                # blocking host syncs performed
        self.sync_wait = 0.0          # seconds spent blocked on the device
        self.prefill_dispatches = 0   # jitted admission dispatches issued
        self._fleet: Optional[FleetGroup] = None   # device state owner when
        self._fleet_row = -1                       # fleet-batched
        self._chunks: dict = {}       # slot -> _ChunkCursor (mid-chunk-prefill)
        if bucket_prompts is None:
            bucket_prompts = model.cfg.family in _BUCKET_FAMILIES
        self.bucket_prompts = bucket_prompts
        # chunked admission needs a continuation kernel and an f32 cache:
        # the int8 codec quantizes whole prompts at prefill end, and a
        # reduced-precision (bf16) cache would make chunked attention read
        # back rounded K/V (and re-round carried ssm/conv state per chunk)
        # where single-shot prefill attends the unrounded values — breaking
        # the chunk-vs-single-shot exactness the parity oracle relies on.
        if chunk_len and (model.cfg.family not in _CHUNK_FAMILIES
                          or _dtype_name(cache_dtype) != "float32"):
            raise ValueError(
                f"chunked prefill (chunk_len={chunk_len}) needs a "
                f"{'/'.join(_CHUNK_FAMILIES)} model with a float32 cache; "
                f"got family={model.cfg.family!r}, "
                f"cache_dtype={_dtype_name(cache_dtype)!r}")
        self.chunk_len = int(chunk_len)
        self._kernels = get_serve_kernels(model, max_seq, cache_dtype,
                                          attn_backend)
        self._prefill = self._kernels.prefill
        self._decode = self._kernels.decode

    @property
    def fleet_key(self) -> tuple:
        """Replicas with equal keys can share one stacked fleet slab."""
        return (id(self.model), id(self.params), self.max_batch,
                self.max_seq, _dtype_name(self.cache_dtype),
                self.attn_backend)

    @property
    def prefill_traces(self) -> int:
        """Prefill-side compilations of this replica's (shared) kernels —
        counts the bucketed, fleet-batched and chunked variants in one
        accounting."""
        return self._kernels.prefill_traces

    # ----------------------------------------------------------------- load
    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def n_decoding(self) -> int:
        """Slots in the decode phase (occupied and not mid-chunk-prefill)."""
        return sum(s is not None and i not in self._chunks
                   for i, s in enumerate(self.slots))

    @property
    def load(self) -> int:
        return self.n_active + len(self.queue)

    def tier_load(self) -> list:
        """Per-tier unfinished count on this replica (declaration order):
        queued + in-flight slots (mid-chunk included)."""
        counts = self.queue.depths()
        for req in self.slots:
            if req is not None:
                counts[self.tiers.index(req.tier)] += 1
        return counts

    def submit(self, req: Request):
        self.queue.append(req)

    def evacuate(self) -> list:
        """Failure path: pull every in-flight + queued request off this
        replica (generation progress is lost) so the caller can re-queue."""
        lost = [r for r in self.slots if r is not None] + list(self.queue)
        self.slots = [None] * self.max_batch
        self.queue.clear()
        self._chunks.clear()
        for r in lost:
            r.reset_progress()
        return lost

    # ------------------------------------------------------------- plumbing
    def _own_cache(self):
        """The standalone device pool, allocated on first use."""
        if self.cache is None:
            self.cache = self.model.init_serve_state(
                self.max_batch, self.max_seq, self.cache_dtype)
        return self.cache

    def _insert_slot(self, slot: int, small_state, row: int, prompt_len: int,
                     first_tok: int, req: Request):
        if self._fleet is not None:
            self._fleet.write_slot(self._fleet_row, slot, small_state, row,
                                   req=req, prompt_len=prompt_len)
        else:
            def put(big, small):
                return big.at[:, slot].set(small[:, row])
            self.cache = jax.tree.map(put, self._own_cache(), small_state)
        self.pos[slot] = prompt_len
        self.last_tok[slot] = first_tok
        self.slots[slot] = req

    def _admit_batch(self, slots: list, reqs: list, finished: list,
                     bucketed: bool):
        if bucketed:
            # a prompt longer than the KV pool keeps only its last
            # max_seq - 1 tokens (one slot must remain for generation);
            # copying the raw prompt would overflow the token buffer.
            prompts = [r.prompt[-(self.max_seq - 1):] for r in reqs]
            lens = [len(p) for p in prompts]
            sb = min(pow2_bucket(max(lens), self.min_bucket), self.max_seq)
            kb = pow2_bucket(len(reqs))
            toks = np.zeros((kb, sb), np.int32)
            lengths = np.ones(kb, np.int32)    # pad rows: length-1 dummies
            for i, p in enumerate(prompts):
                toks[i, :len(p)] = p
                lengths[i] = len(p)
            batch = {"tokens": jnp.asarray(toks),
                     "lengths": jnp.asarray(lengths)}
            logits, small, plen = self._prefill(self.params, batch)
        else:
            req = reqs[0]
            # same overflow guard as the bucketed path: the KV pool holds
            # max_seq entries and one must remain for generation
            prompt = req.prompt[-(self.max_seq - 1):]
            batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
            extras = getattr(req, "extras", None)
            if extras:
                batch.update({k: jnp.asarray(v) for k, v in extras.items()})
            logits, small, plen = self._prefill(self.params, batch)
        self.prefill_dispatches += 1
        first, plen = _timed_get(self, (jnp.argmax(logits, axis=-1), plen))
        first = np.asarray(first)
        plen = np.atleast_1d(np.asarray(plen)).astype(np.int32)
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            tok = int(first[i])
            req.output.append(tok)
            req.first_token_time = self.clock
            if len(req.output) >= req.max_new_tokens or tok == req.eos_id \
                    or req.out_of_time(self.clock):
                req.finish_time = self.clock
                finished.append(req)
                continue
            self._insert_slot(slot, small, i, int(plen[i]), tok, req)

    def commit_admit(self, slots: list, reqs: list, first, plen,
                     finished: list):
        """Apply a fleet-prefill result: the slab rows were already written
        on device, so only the host bookkeeping (first token, TTFT, retire
        or register) remains. A request that finishes at prefill time leaves
        stale state in the slab — harmless, exactly like slot reuse."""
        for i, (slot, req) in enumerate(zip(slots, reqs)):
            tok = int(first[i])
            req.output.append(tok)
            req.first_token_time = self.clock
            if len(req.output) >= req.max_new_tokens or tok == req.eos_id \
                    or req.out_of_time(self.clock):
                req.finish_time = self.clock
                finished.append(req)
                continue
            self.pos[slot] = int(plen[i])
            self.last_tok[slot] = tok
            self.slots[slot] = req

    # ------------------------------------------------------------ admission
    def _chunkable(self, req: Request) -> bool:
        return (self.chunk_len > 0
                and getattr(req, "extras", None) is None
                and min(len(req.prompt), self.max_seq - 1) > self.chunk_len)

    def plan_admission(self) -> _AdmitPlans:
        """Pop admittable queue heads into reserved slots WITHOUT
        dispatching — the shared host half of both the standalone and the
        fleet-batched admission paths (identical plans keep the two modes in
        lockstep). Queue heads come out in the tiered weighted-deficit order
        (see ``TieredQueue``): high-weight tiers admit first, low-weight
        tiers keep a bounded share. Chunk-eligible prompts just reserve a
        slot + cursor; their first chunk runs in this step's chunk round —
        but a lower-tier chunk start *yields* the last free slot while
        higher-priority work is waiting (a long batch-tier prefill would
        otherwise hold the slot for ceil(len/chunk) ticks and lock premium
        traffic out)."""
        plans = _AdmitPlans([], [])
        if self.draining:
            return plans
        free = [i for i in range(self.max_batch) if self.slots[i] is None]
        deferred: set = set()         # tiers whose chunk start yielded
        while free:
            picked = self.queue.peek(deferred)
            if picked is None:
                break
            tier_idx, head = picked
            if head.out_of_time(self.clock):
                req = self.queue.pop(deferred)
                req.finish_time = self.clock
                plans.expired.append(req)
                continue
            if self._chunkable(head):
                if len(free) == 1 and self.queue.higher_waiting(tier_idx):
                    deferred.add(tier_idx)    # leave the slot for premium
                    continue
                req = self.queue.pop(deferred)
                slot = free.pop(0)
                self.slots[slot] = req
                self._chunks[slot] = _ChunkCursor(
                    req, req.prompt[-(self.max_seq - 1):])
                continue
            if not self.bucket_prompts or getattr(head, "extras", None):
                # exact-length single admit (audio / extras-carrying
                # requests, and moe replicas by default)
                plans.singles.append((free.pop(0), self.queue.pop(deferred)))
                continue
            group = []
            while len(group) < len(free):
                nxt = self.queue.peek(deferred)
                if nxt is None or getattr(nxt[1], "extras", None) \
                        or self._chunkable(nxt[1]) \
                        or nxt[1].out_of_time(self.clock):
                    break
                group.append(self.queue.pop(deferred))
            plans.bucketed.append(([free.pop(0) for _ in group], group))
        return plans

    def _admit(self, finished: list):
        """Standalone admission: plan, then dispatch this engine's own
        bucketed / exact-length prefill calls."""
        plans = self.plan_admission()
        finished.extend(plans.expired)
        for slot, req in plans.singles:
            self._admit_batch([slot], [req], finished, bucketed=False)
        for slots, reqs in plans.bucketed:
            self._admit_batch(slots, reqs, finished, bucketed=True)

    # --------------------------------------------------------------- chunks
    def _chunk_due(self) -> list:
        """Mid-chunk slots due to advance this step, tier-throttled: a
        cursor whose tier is strictly below some *decoding* slot's tier is
        "pressured" — its chunk compute would stretch the tick every one of
        those higher-tier slots' next token waits on. Under pressure at most
        ONE such low-tier cursor advances per step (the highest-priority,
        lowest-slot one), so a long batch-tier prefill streams through
        without inflating premium TBT by more than one chunk row. Cursors at
        or above every decoding tier (and everything in single-tier mode)
        advance unthrottled."""
        slots = sorted(self._chunks)
        if len(self.tiers) <= 1 or not slots:
            return slots
        decoding = [self.tiers.rank(req.tier)
                    for s, req in enumerate(self.slots)
                    if req is not None and s not in self._chunks]
        if not decoding:
            return slots
        best = min(decoding)                  # rank 0 = highest priority
        rank = lambda s: self.tiers.rank(self._chunks[s].req.tier)
        calm = [s for s in slots if rank(s) <= best]
        pressured = sorted((s for s in slots if rank(s) > best),
                           key=lambda s: (rank(s), s))
        return sorted(calm + pressured[:1])

    def _chunk_rows(self):
        """This step's chunk work items:
        (slot, toks (chunk_len,), offset, true_len, fresh, final)."""
        rows = []
        for slot in self._chunk_due():
            cur = self._chunks[slot]
            off = cur.consumed
            ln = min(self.chunk_len, len(cur.prompt) - off)
            toks = np.zeros(self.chunk_len, np.int32)
            toks[:ln] = cur.prompt[off:off + ln]
            rows.append((slot, toks, off, ln, off == 0,
                         off + ln >= len(cur.prompt)))
        return rows

    def commit_chunk(self, slot: int, first_tok, pos, final: bool,
                     finished: list):
        """Apply one chunk result: advance the cursor, or — on the final
        chunk — record the first generated token and hand the slot to the
        decode phase (or retire it immediately)."""
        cur = self._chunks[slot]
        if not final:
            cur.consumed += self.chunk_len
            return
        del self._chunks[slot]
        req = cur.req
        tok = int(first_tok)
        req.output.append(tok)
        req.first_token_time = self.clock
        if len(req.output) >= req.max_new_tokens or tok == req.eos_id \
                or req.out_of_time(self.clock):
            req.finish_time = self.clock
            finished.append(req)
            self.slots[slot] = None
            return
        self.pos[slot] = int(pos)
        self.last_tok[slot] = tok

    def _chunk_step(self, finished: list):
        """Advance every mid-chunk slot by one chunk in ONE batched
        dispatch (fleet members route through the fleet slab kernel)."""
        rows = self._chunk_rows()
        if not rows:
            return
        if self._fleet is not None:
            self._fleet._dispatch_fleet_chunk(
                [(self,) + row for row in rows], finished)
            return
        K, toks, offs, lens, fresh = _pack_chunk_rows(
            [(t, off, ln, fr) for _, t, off, ln, fr, _ in rows],
            self.chunk_len)
        slots = np.full(K, self.max_batch, np.int32)   # OOB pads -> dropped
        for i, (slot, *_rest) in enumerate(rows):
            slots[i] = slot
        first, pos, self.cache = self._kernels.chunk(
            self.params, self._own_cache(), jnp.asarray(toks),
            jnp.asarray(offs),
            jnp.asarray(lens), jnp.asarray(fresh), jnp.asarray(slots))
        self.prefill_dispatches += 1
        first, pos = _timed_get(self, (first, pos))
        first, pos = np.asarray(first), np.asarray(pos)
        for i, (slot, t, off, ln, fr, fin) in enumerate(rows):
            self.commit_chunk(slot, first[i], pos[i], fin, finished)

    # ------------------------------------------------------------- stepping
    def begin_step(self, dt: float = 1.0, admit: bool = True) -> list:
        """Tick phase 1: advance the clock and admit from the queue. Returns
        requests that completed at prefill time. With ``admit=False`` only
        the clock moves — the caller batches admission across the fleet via
        ``FleetGroup.admit_round``. The decode phase follows via
        ``finish_step`` (standalone) or one ``FleetGroup.decode_round``."""
        self.clock += dt
        finished: list = []
        if admit:
            self._admit(finished)
            self._chunk_step(finished)
        return finished

    def finish_step(self) -> list:
        """Tick phase 2: one decode step for all active (non-chunking)
        slots."""
        if self._fleet is not None:    # device state lives in the fleet slab
            return self._fleet.decode_round({id(self)})
        if self.n_decoding == 0:
            return []
        toks = jnp.asarray(self.last_tok[:, None])
        pos = jnp.asarray(self.pos)
        if self._chunks:
            # mid-chunk slots must keep their carried state bit-for-bit
            hk = pow2_bucket(len(self._chunks))
            hslots = np.full(hk, self.max_batch, np.int32)  # OOB pads
            hslots[:len(self._chunks)] = sorted(self._chunks)
            logits, self.cache = self._kernels.decode_hold(
                self.params, self.cache, toks, pos, jnp.asarray(hslots))
        else:
            logits, self.cache = self._decode(self.params, self.cache, toks,
                                              pos)
        self.steps += 1
        finished: list = []
        next_toks = np.asarray(_timed_get(self, jnp.argmax(logits, axis=-1)))
        for slot, req in enumerate(self.slots):
            if req is None or slot in self._chunks:
                continue
            tok = int(next_toks[slot])
            req.output.append(tok)
            self.pos[slot] += 1
            self.last_tok[slot] = tok
            if (len(req.output) >= req.max_new_tokens or tok == req.eos_id
                    or self.pos[slot] >= self.max_seq - 1
                    or req.out_of_time(self.clock)):
                req.finish_time = self.clock
                finished.append(req)
                self.slots[slot] = None
        return finished

    def commit_decode(self, next_toks: np.ndarray, done: np.ndarray) -> list:
        """Apply one fleet decode result to the host-side slot bookkeeping.
        ``next_toks``/``done`` are this engine's (B,) rows of the batched
        sync; the retire mask was already computed on device. Mid-chunk
        slots were held on device and are skipped here."""
        finished: list = []
        stepped = False
        for slot, req in enumerate(self.slots):
            if req is None or slot in self._chunks:
                continue
            stepped = True
            tok = int(next_toks[slot])
            req.output.append(tok)
            self.pos[slot] += 1
            self.last_tok[slot] = tok
            if done[slot]:
                req.finish_time = self.clock
                finished.append(req)
                self.slots[slot] = None
        if stepped:
            self.steps += 1
        return finished

    def apply_decode(self, nxt: np.ndarray, done: np.ndarray,
                     stepped: np.ndarray, clock: float) -> list:
        """Apply one *async* fleet decode result at reconcile time: the
        device's ``stepped`` mask (not the possibly-stale host view) says
        which slots advanced, and ``clock`` is the dispatch-time clock that
        stamps finishes. Host mirrors update vectorized (numpy
        struct-of-arrays), python touches only the stepped slots."""
        idx = np.flatnonzero(stepped)
        if idx.size == 0:
            return []
        self.pos[idx] += 1
        self.last_tok[idx] = nxt[idx]
        self.steps += 1
        finished: list = []
        for s in idx:
            req = self.slots[s]
            req.output.append(int(nxt[s]))
            if done[s]:
                req.finish_time = clock
                finished.append(req)
                self.slots[s] = None
        return finished

    def step(self, dt: float = 1.0) -> list:
        """Admit + one decode step for all active slots. Returns finished
        (including requests that completed at prefill time)."""
        finished = self.begin_step(dt)
        finished.extend(self.finish_step())
        return finished


def normalize_fractions(fr: np.ndarray, mask: Optional[np.ndarray] = None
                        ) -> np.ndarray:
    """Simplex-normalize routing fractions with a uniform fallback — the
    numpy twin of ``core.balancer._mask_normalize``. Non-finite or negative
    entries are zeroed; a zero/NaN sum falls back to uniform over the mask.
    An all-false mask (every node/cell down — a full blackout tick) returns
    uniform-over-none, i.e. all zeros: callers must treat a zero-sum result
    as "nothing can serve" and park arrivals (retry pool / pending) rather
    than divide by the mask count — the old fallback silently routed
    uniform over DEAD nodes."""
    fr = np.asarray(fr, np.float64)
    fr = np.where(np.isfinite(fr) & (fr > 0.0), fr, 0.0)
    if mask is not None:
        m = np.asarray(mask, np.float64) > 0.0
        if not m.any():
            return np.zeros(fr.shape[0], np.float64)
        fr = fr * m
    s = fr.sum()
    if s <= 1e-12:
        if mask is not None:
            m = (np.asarray(mask) > 0).astype(np.float64)
            return m / m.sum()
        return np.full(fr.shape[0], 1.0 / fr.shape[0])
    return fr / s


class ClusterFrontend:
    """Routes requests to replicas via balancer fractions (or queue depth).

    ``fleet_batch=True`` stacks same-shape replicas into ``FleetGroup``s so a
    ``step`` issues one decode dispatch per group instead of one per replica
    (replicas that can't stack — different shapes — keep stepping solo).
    ``fleet_prefill`` (default: follows ``fleet_batch``) batches admission
    the same way: one prefill dispatch per distinct bucket shape per group;
    set it False to keep per-replica admission as the parity oracle."""

    def __init__(self, replicas: list, policy: str = "lc",
                 fractions_fn=None, seed: int = 0, fleet_batch: bool = False,
                 fleet_prefill: Optional[bool] = None, mesh=None):
        self.replicas = replicas
        self.policy = policy
        self.fractions_fn = fractions_fn
        self.rng = np.random.default_rng(seed)
        self.pending: deque = deque()
        self.finished: list = []
        self._rr = itertools.cycle(range(len(replicas)))
        self.fleets: dict = {}
        self.mesh = mesh
        self.fleet_prefill = fleet_batch if fleet_prefill is None \
            else (fleet_prefill and fleet_batch)
        if fleet_batch:
            for eng in replicas:
                g = self.fleets.get(eng.fleet_key)
                if g is None:
                    g = self.fleets[eng.fleet_key] = FleetGroup(
                        eng.model, eng.params, max_batch=eng.max_batch,
                        max_seq=eng.max_seq, cache_dtype=eng.cache_dtype,
                        attn_backend=eng.attn_backend, mesh=mesh)
                g.add(eng)

    def submit(self, req: Request):
        self.pending.append(req)

    def _route(self):
        while self.pending:
            req = self.pending.popleft()
            if self.policy == "rr":
                idx = next(self._rr)
            elif self.policy == "lc":
                loads = [r.load for r in self.replicas]
                idx = int(np.argmin(loads))
            elif self.policy == "fractions":
                fr = normalize_fractions(self.fractions_fn(self))
                idx = int(self.rng.choice(len(self.replicas), p=fr))
            else:
                raise ValueError(self.policy)
            self.replicas[idx].submit(req)

    def step(self, dt: float = 1.0):
        self._route()
        if not self.fleets:
            for r in self.replicas:
                self.finished.extend(r.step(dt))
            return
        for r in self.replicas:
            self.finished.extend(r.begin_step(
                dt, admit=r._fleet is None or not self.fleet_prefill))
        if self.fleet_prefill:
            for g in self.fleets.values():
                self.finished.extend(g.admit_round())
        for g in self.fleets.values():
            self.finished.extend(g.decode_round())
        for r in self.replicas:          # replicas outside any fleet
            if r._fleet is None:
                self.finished.extend(r.finish_step())

    def run_until_drained(self, max_steps: int = 10_000):
        for _ in range(max_steps):
            self.step()
            if not self.pending and all(r.load == 0 for r in self.replicas):
                return
        raise RuntimeError("engine did not drain")
