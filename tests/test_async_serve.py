"""Overlapped fleet serving tick: late single sync, fused decode blocks,
device-resident operands, and the Pallas decode-attention backend.

Acceptance coverage for the fleet tick contract (see ``serving.engine``
module docstring), against the per-replica loop (``fleet_batch=False``,
each replica stepped by ``ReplicaEngine.step()``) as the reference:

  * **bit-parity** — the fleet tick produces identical token streams and
    finish ticks to the per-replica loop, including continuous arrivals
    with provisioning and scale churn, chunked admission, SLO tiers and
    moe single admits; under ``decode_block=4`` the dense, ssm and hybrid
    families keep identical streams through failure evacuation, graceful
    drain and scale-up joins — all with device futures pending when
    membership changes;
  * **admission-lag bound** — a slot freed by tick t's decode is re-admitted
    at tick t+1 under a full slab, exactly like the per-replica loop (the
    host observes device state at most one tick late, admission never lags
    the reference);
  * **sync bound** — steady-state ticks cost ONE blocking host sync
    (``metrics()['syncs']``), admissions included;
  * **decode_block** — K fused micro-steps per dispatch are bit-exact vs K
    single steps, drop syncs/tick below 1, and never engage while work is
    waiting (so admission latency is untouched);
  * **moe single-admit path** — exact-length admits keep parity (their
    immediate prefill fetch + device-operand registration);
  * **pallas backend** — ``attn_backend="pallas"`` decodes through the
    flash-decode kernel (CPU interpret mode) with per-row cache positions,
    matching the dense einsum path stream-for-stream.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.models import make_model
from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request

MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    return c, m, params


def _make_reqs(n, n_new=6, seed=3, vocab=400):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, vocab, rng.integers(3, 9)).tolist(),
                    max_new_tokens=n_new) for i in range(n)]


def _snap(reqs):
    return {r.rid: (tuple(r.output), r.finish_time, r.first_token_time)
            for r in reqs}


def _snap_fe(fe):
    return sorted((r.rid, tuple(r.output), r.finish_time)
                  for r in fe.finished)


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("arch", ["granite-3-8b", "mamba2-1.3b",
                                  "zamba2-2.7b"])
def test_async_matches_eager_across_churn(arch):
    """Fused decode blocks (``decode_block=4``) through the full churn
    matrix — failure (progress reset with a block in flight), drain,
    scale-up — against the per-replica loop: identical token streams for
    dense/ssm/hybrid, first tokens and finishes at most K-1 ticks late, and
    fewer blocking syncs than ticks."""
    K = 4
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    fleet_batch=fleet, decode_block=K)
        reqs = _make_reqs(10, n_new=12)
        for r in reqs:
            fe.submit(r)
        fe.tick(0.0)
        fe.fail_replica(0, 0)        # evacuate with decode futures pending
        fe.tick(0.0)
        fe.scale_to(np.array([1, 1]))
        fe.tick(0.0)
        fe.scale_to(np.array([2, 2]))
        fe.run_until_drained()
        return reqs, fe

    ref, _ = run(False)
    blocked, fe_b = run(True)
    for rr, rb in zip(ref, blocked):
        assert rb.output == rr.output
        assert 0 <= rb.first_token_time - rr.first_token_time <= K - 1
        assert 0 <= rb.finish_time - rr.finish_time <= K - 1
    # the fused windows engaged across the churn
    assert fe_b.sync_count() < fe_b.t


def _arrivals_and_scaling(m, params, fleet):
    """Continuous arrivals, cold-start provisioning and scale-down/up
    churn; returns the finished streams and finish ticks."""
    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def rf(rid, tick):
        return Request(rid, [1 + rid % 50, 2, 3, 4], max_new_tokens=4)

    fe = ElasticClusterFrontend(factory, 2, initial_replicas=1,
                                provisioning_delay=2,
                                request_factory=rf, seed=0,
                                est_tokens=4, fleet_batch=fleet)
    for t in range(24):
        fe.tick(1.6)
        if t == 5:
            fe.scale_to(np.array([2, 1]))
        if t == 12:
            fe.scale_to(np.array([2, 2]))
        if t == 18:
            fe.scale_to(np.array([1, 2]))
    fe.run_until_drained()
    return _snap_fe(fe)


def test_async_matches_eager_with_arrivals_and_scaling(setup):
    """Continuous arrivals + cold-start provisioning + scale-down/up churn,
    fleet tick vs the per-replica loop: the regression scenario where a
    mid-tick force-flush (drained-replica retirement) must not lose or
    reorder finishes."""
    c, m, params = setup
    assert _arrivals_and_scaling(m, params, True) == \
        _arrivals_and_scaling(m, params, False)


def test_streams_unchanged_under_profiler(setup, tmp_path):
    """With the profiler recording the frontend's spans, token streams and
    finish ticks are bit-identical to a run without it, and the trace holds
    every ``repro.fe.*`` span and the blocking fetches."""
    import glob
    import os

    from jax.profiler import ProfileData

    c, m, params = setup
    plain = _arrivals_and_scaling(m, params, True)
    with jax.profiler.trace(str(tmp_path)):
        traced = _arrivals_and_scaling(m, params, True)
    assert traced == plain
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"repro.fe.tick", "repro.fe.reconcile", "repro.fe.route",
            "repro.fe.admit", "repro.fe.decode", "repro.fetch"} <= names


def test_moe_single_admit_async_parity():
    """moe replicas use exact-length single admits (an immediate
    per-request prefill fetch + device-operand registration via
    ``write_slot``) — the fleet decode around them must still match the
    per-replica loop."""
    c = get_config("grok-1-314b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        reqs = _make_reqs(5, n_new=4, seed=11)
        for r in reqs:
            fe.submit(r)
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)


# -------------------------------------------------------- admission timing
def test_admission_lag_bound_under_full_slab(setup):
    """A queued request waiting on a full slab admits on the tick right
    after a slot retires — identical to the per-replica loop (retire/slot-
    free reconciles BEFORE admission planning, so the host's
    one-tick-stale view never delays an admission)."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=1, seed=0,
                                    max_replicas_per_node=1,
                                    fleet_batch=fleet)
        # 2 slots; first two requests fill the slab, the third waits
        short = [Request(0, [5, 6, 7], max_new_tokens=3),
                 Request(1, [8, 9, 10], max_new_tokens=5),
                 Request(2, [11, 12, 13], max_new_tokens=3)]
        for r in short:
            fe.submit(r)
        for _ in range(20):
            fe.tick(0.0)
            if all(r.done for r in short):
                break
        return [(r.first_token_time, r.finish_time) for r in short]

    ref = run(False)
    assert run(True) == ref
    # the waiting request admitted exactly one tick after the first retire
    finish0 = ref[0][1]
    assert ref[2][0] == finish0 + 1


# ----------------------------------------------------------- sync accounting
def test_syncs_per_tick_bound(setup):
    """Steady-state ticks cost exactly ONE blocking sync (the reconcile)
    while keeping one decode dispatch per group; no tick, admissions
    included, pays more than one."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0)
    for r in _make_reqs(4, n_new=10):
        fe.submit(r)
    ticks = []
    for _ in range(30):
        mtr = fe.tick(0.0)
        ticks.append((mtr["syncs"], mtr["decode_dispatches"]))
        if not fe.pending and all(n.unfinished() == 0 for n in fe.nodes):
            break
    assert all(s <= 1 for s, _ in ticks)
    steady = [t for t in ticks if t[1] == 1]
    assert steady and all(s == 1 for s, _ in steady[1:])
    assert all(d <= 1 for _, d in ticks)


# -------------------------------------------------------------- decode block
def test_decode_block_exact_vs_single_steps(setup):
    """decode_block=4 (one fused dispatch + one (K,F,B) sync per 4 ticks)
    must be bit-exact vs single-step fleet ticks AND the per-replica loop,
    with strictly fewer syncs and dispatches than single steps, which pay
    at most one sync per tick."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(fleet, block=1):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    fleet_batch=fleet, decode_block=block)
        reqs = _make_reqs(4, n_new=12, seed=5)   # fills 2x2 slots, no queue
        for r in reqs:
            fe.submit(r)
        ticks = 0
        for _ in range(60):
            fe.tick(0.0)
            ticks += 1
            if not fe.pending and all(n.unfinished() == 0
                                      for n in fe.nodes):
                break
        return _snap(reqs), fe, ticks

    s_ref, _, _ = run(False)
    s_async, fe_a, ticks_a = run(True)
    s_block, fe_b, ticks_b = run(True, block=4)
    assert s_ref == s_async == s_block
    assert fe_b.sync_count() < fe_a.sync_count() <= ticks_a
    assert fe_b.decode_dispatches() < fe_a.decode_dispatches()
    # block mode averages under one sync AND one dispatch per tick
    assert fe_b.sync_count() / ticks_b < 1.0


def test_decode_block_admission_lag_bounded(setup):
    """A block never engages on a tick that admitted anything (pending
    admissions veto it), and queued work behind a full slab re-admits at
    the block-end reconcile — token CONTENT is identical to decode_block=1
    and the TTFT/finish lag is bounded by K-1 ticks (the documented
    latency-for-throughput trade)."""
    c, m, params = setup
    K = 4

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(block):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=1, seed=0,
                                    max_replicas_per_node=1,
                                    decode_block=block)
        reqs = _make_reqs(6, n_new=6, seed=7)    # 2 slots, 4 queued behind
        for r in reqs:
            fe.submit(r)
        fe.run_until_drained()
        return reqs, fe

    base, fe1 = run(1)
    blocked, feK = run(K)
    for rb, rk in zip(base, blocked):
        assert rb.output == rk.output            # greedy streams unchanged
        assert 0 <= rk.first_token_time - rb.first_token_time <= K - 1
        assert 0 <= rk.finish_time - rb.finish_time <= K - 1
    # the fused window really engaged: fewer syncs for the same work
    assert feK.sync_count() < fe1.sync_count()


def test_decode_block_vetoed_by_single_admits():
    """Exact-length single admits (the moe path) bypass ``pending``; the
    ``_admitted`` flag must still veto fused-block engagement on the tick
    that admitted, keeping streams identical to decode_block=1 for a
    workload whose every admission tick also decodes."""
    c = get_config("grok-1-314b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(block):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    decode_block=block)
        reqs = _make_reqs(4, n_new=10, seed=17)   # fills 2x2, singles-only
        for r in reqs:
            fe.submit(r)
        fe.run_until_drained()
        return reqs, fe

    base, _ = run(1)
    blocked, feK = run(4)
    for rb, rk in zip(base, blocked):
        assert rb.output == rk.output
        assert rk.first_token_time == rb.first_token_time  # admit tick
        assert 0 <= rk.finish_time - rb.finish_time <= 3   # fused windows


# ------------------------------------------------------------ chunked + tiers
def test_chunked_prefill_async_parity(setup):
    """Chunked admission (cursor advance at dispatch, final-chunk commit at
    reconcile) keeps the fleet tick in parity with the per-replica loop."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, chunk_len=8)

    def run(fleet):
        rng = np.random.default_rng(2)
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        reqs = [Request(i, rng.integers(1, 400, ln).tolist(),
                        max_new_tokens=4)
                for i, ln in enumerate([30, 5, 45, 6, 20, 7])]
        for r in reqs:
            fe.submit(r)
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)


def test_tiered_async_parity(setup):
    """Weighted-deficit tiered admission reorders identically under the
    fleet tick and the per-replica loop (queue work is host-state, never
    deferred)."""
    from repro.workload import TierSet, TierSpec

    c, m, params = setup
    tiers = TierSet([TierSpec("premium", share=0.34, weight=5.0,
                              ttft_target=3.0),
                     TierSpec("standard", share=0.33, weight=2.0),
                     TierSpec("batch", share=0.33, weight=1.0)])

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, tiers=tiers)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    fleet_batch=fleet, tiers=tiers)
        reqs = _make_reqs(9, n_new=4, seed=13)
        for i, r in enumerate(reqs):
            r.tier = tiers.names[i % 3]
            fe.submit(r)
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)


# ------------------------------------------------------------ pallas backend
@pytest.mark.parametrize("B,Hq,Hkv,S,d", [(2, 4, 2, 128, 32),
                                          (3, 4, 1, 256, 64)])
def test_flash_decode_per_row_pos(B, Hq, Hkv, S, d):
    """flash_decode now takes per-row cache lengths (the serving slot-pool
    layout): each row must match the scalar-pos reference run row by row."""
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, Hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Hkv, S, d), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Hkv, S, d), jnp.float32)
    pos = jnp.asarray([7 + 13 * b for b in range(B)], jnp.int32)
    out = flash_decode(q, kc, vc, pos, block_kv=128, interpret=True)
    for b in range(B):
        want = ref.decode_attention_ref(q[b:b + 1], kc[b:b + 1],
                                        vc[b:b + 1], int(pos[b]))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(want[0]), atol=2e-5,
                                   rtol=2e-5)


def test_pallas_backend_stream_parity(setup):
    """ReplicaEngine(attn_backend="pallas") serves the same greedy streams
    as the dense einsum reference (CPU interpret mode), at mixed per-slot
    cache depths."""
    c, m, params = setup

    def run(backend):
        eng = ReplicaEngine(m, params, max_batch=2, max_seq=32,
                            attn_backend=backend)
        rng = np.random.default_rng(5)
        reqs = [Request(i, rng.integers(1, 400, 4 + 3 * i).tolist(),
                        max_new_tokens=4) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        for _ in range(20):
            eng.step()
            if eng.load == 0:
                break
        return _snap(reqs)

    assert run("pallas") == run("einsum")


def test_pallas_backend_rejected_for_ssm():
    c = get_config("mamba2-1.3b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="pallas"):
        ReplicaEngine(m, params, max_batch=2, max_seq=32,
                      attn_backend="pallas")
