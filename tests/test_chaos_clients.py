"""Failure matrix: closed-loop clients, deadlines, spot preemption and
exactly-once request accounting.

Covers the robustness layer end to end: chaos entry-point validation
(ValueError, not IndexError), deadline retirement inside the fleet retire
rule, duplicate suppression + retry (the exactly-once guarantee of the
``RequestLedger``), whole-node preemption notices (drain-under-deadline,
hard drop, re-queue, scripted ``ChaosSchedule``), conservation across the
full churn x chaos matrix, the fluid-sim mirror, the GPSO preemption-risk
cost term, and chaos-off streams bit-identical whether the robustness
layer is present but inert or absent.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.paper_cluster import ClusterConfig
from repro.core.autoscaler import (GPSOAutoscaler, eq9_fitness,
                                   eq9_risk_fitness)
from repro.models import make_model
from repro.serving import (ChaosSchedule, ElasticClusterFrontend,
                           ReplicaEngine, Request)
from repro.sim.cluster import ClusterSim
from repro.workload import ClientPool, parse_tiers

MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    return c, m, params


def _factory(m, params, max_batch=2, tiers=None):
    def make_replica(rid):
        return ReplicaEngine(m, params, max_batch=max_batch, max_seq=MAX_SEQ,
                             rid=rid, tiers=tiers)
    return make_replica


def _req(i, plen=4, n_new=4, deadline=None):
    r = Request(i, [1 + (i + j) % 97 for j in range(plen)],
                max_new_tokens=n_new)
    r.deadline_tick = deadline
    return r


# ------------------------------------------------------------- validation
def test_chaos_entry_points_validate(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 2, initial_replicas=1)
    with pytest.raises(ValueError, match="out of range"):
        fe.fail_replica(5)
    with pytest.raises(ValueError, match="out of range"):
        fe.fail_replica(-1)          # negative must not wrap
    with pytest.raises(ValueError, match="replica index"):
        fe.fail_replica(0, replica_idx=3)
    with pytest.raises(ValueError, match="must be an int"):
        fe.fail_replica("n0")
    with pytest.raises(ValueError, match="out of range"):
        fe.preempt_node(9)
    with pytest.raises(ValueError, match="not down"):
        fe.recover_node(0)
    fe.preempt_node(0, notice=2)
    with pytest.raises(ValueError, match="already has a preemption"):
        fe.preempt_node(0)
    with pytest.raises(ValueError, match="no live replicas"):
        fe.fail_replica(0)           # live drained away by the notice
    for _ in range(4):
        fe.tick(0.0)
    assert fe.nodes[0].down and fe.preempted_nodes == 1
    with pytest.raises(ValueError, match="already down"):
        fe.preempt_node(0)
    fe.recover_node(0)
    assert not fe.nodes[0].down


def test_chaos_schedule_parse_errors():
    s = ChaosSchedule.parse("preempt@12:n0:k3, fail@8:n1:r1 ,recover@40:n0")
    assert s.pop(12) == [("preempt", 0, 3)]
    assert s.pop(8) == [("fail", 1, 1)]
    assert s.pop(40) == [("recover", 0, None)]
    assert s.pop(13) == []
    for bad in ("explode@3:n0", "preempt@3", "fail@3:n0:k2",
                "preempt@3:n0:r2", "preempt@x:n0"):
        with pytest.raises(ValueError):
            ChaosSchedule.parse(bad)


# --------------------------------------------------------------- deadlines
def test_deadline_retires_in_fleet_and_queue_cull(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1)
    fe.submit(_req(0, n_new=12, deadline=3.0))    # expires mid-decode
    fe.submit(_req(1, n_new=12, deadline=50.0))   # comfortable
    fe.submit(_req(2, n_new=12, deadline=1.0))    # expires while queued
    for _ in range(30):
        fe.tick(0.0)
        assert fe.metrics()["syncs"] <= 1         # bounds hold under expiry
    fe.run_until_drained()
    done = {r.rid: r for r in fe.finished}
    assert done[0].expired and len(done[0].output) < 12
    assert done[0].finish_time <= done[0].deadline_tick + 1
    assert not done[1].expired and len(done[1].output) == 12
    # rid 2 never got a slot past its deadline: culled, zero tokens
    assert done[2].expired and done[2].output == []
    b = fe.ledger.balance()
    assert b["finished"] == 1 and b["timed_out"] == 2 and b["live"] == 0
    assert fe.ledger.balanced()


# ----------------------------------------------- exactly-once + retry path
def test_duplicate_suppression_and_retry(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1)
    assert fe.submit(_req(7, n_new=4)) is True
    assert fe.submit(_req(7, n_new=4)) is False      # live -> suppressed
    assert fe.ledger.duplicates == 1
    fe.run_until_drained()
    assert [r.rid for r in fe.finished] == [7]       # served exactly once
    assert fe.submit(_req(7)) is False               # finished -> suppressed
    assert fe.ledger.double_served == 0

    # timeout -> retry accepted, fresh attempt served
    fe2 = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1)
    fe2.submit(_req(0, n_new=12, deadline=2.0))
    for _ in range(8):
        fe2.tick(0.0)
    assert fe2.ledger.state[0] == "timed_out"
    assert fe2.submit(_req(0, n_new=4, deadline=100.0)) is True
    assert fe2.ledger.retries == 1
    fe2.run_until_drained()
    assert fe2.ledger.state[0] == "finished"
    served = [r for r in fe2.finished if r.rid == 0 and not r.expired]
    assert len(served) == 1                          # exactly one good serve
    assert fe2.ledger.balanced()

    # abandoned rid: late completion counts wasted, not served
    fe3 = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1)
    fe3.submit(_req(5, n_new=6))
    fe3.tick(0.0)                                    # in flight
    assert fe3.abandon(5) is True
    assert fe3.submit(_req(5)) is False              # abandoned -> suppressed
    fe3.run_until_drained()
    assert fe3.ledger.wasted == 1 and fe3.ledger.double_served == 0
    assert fe3.ledger.balanced()


def test_rejection_under_queue_cap(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1,
                                max_queue=2)
    assert fe.submit(_req(0)) and fe.submit(_req(1))
    assert fe.submit(_req(2)) is False               # cap hit -> rejected
    assert fe.ledger.state[2] == "rejected"
    fe.run_until_drained()
    assert fe.submit(_req(2)) is True                # retry after rejection
    fe.run_until_drained()
    b = fe.ledger.balance()
    assert b["finished"] == 3 and b["rejected"] == 0 and fe.ledger.balanced()


# -------------------------------------------------------------- preemption
def test_preempt_notice_drains_then_drops(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 2, initial_replicas=1,
                                seed=1)
    for i in range(6):
        fe.submit(_req(i, n_new=10))
    fe.tick(0.0)
    fe.preempt_node(0, notice=2)
    assert not fe.nodes[0].live and fe.nodes[0].draining
    assert fe.up_mask().tolist() == [0.0, 1.0]
    assert fe.preempt_risk().tolist() == [1.0, 0.0]
    assert not fe.nodes[0].spawning
    fe.scale_to(np.array([3, 1]))                    # refused on noticed node
    assert not fe.nodes[0].spawning
    for _ in range(4):
        fe.tick(0.0)
    assert fe.nodes[0].down and not fe.nodes[0].draining
    assert fe.preempted_nodes == 1
    fe.run_until_drained()
    assert sorted(r.rid for r in fe.finished) == list(range(6))  # none lost
    assert all(len(r.output) == 10 for r in fe.finished)
    assert fe.ledger.balanced()
    # scripted schedule drives the same machinery
    fe2 = ElasticClusterFrontend(
        _factory(m, params), 2, initial_replicas=1, seed=1,
        chaos=ChaosSchedule.parse("preempt@2:n0:k1,recover@6:n0"))
    for i in range(4):
        fe2.submit(_req(i, n_new=8))
    for t in range(7):
        fe2.tick(0.0)
    assert fe2.preempted_nodes == 1 and not fe2.nodes[0].down  # recovered
    fe2.run_until_drained()
    assert sorted(r.rid for r in fe2.finished) == list(range(4))
    assert fe2.ledger.balanced()


# ------------------------------------------------------- edge ordering
def test_same_tick_preempt_recover_applies_in_order(setup):
    """Same-tick events apply in spec order: an immediate (k0) preemption
    hard-drops and downs the node, then the recover in the SAME tick
    brings it back — net effect one preemption, node schedulable again,
    every evacuated request re-served exactly once."""
    c, m, params = setup
    fe = ElasticClusterFrontend(
        _factory(m, params), 2, initial_replicas=1, seed=1,
        chaos=ChaosSchedule.parse("preempt@3:n0:k0,recover@3:n0"))
    for i in range(6):
        fe.submit(_req(i, n_new=8))
    for _ in range(4):
        fe.tick(0.0)
    assert fe.preempted_nodes == 1
    assert not fe.nodes[0].down              # recovered within the tick
    fe.scale_to(np.array([1, 1]))            # schedulable again (empty)
    assert fe.nodes[0].spawning
    fe.run_until_drained()
    assert sorted(r.rid for r in fe.finished) == list(range(6))
    assert fe.ledger.balanced() and fe.ledger.double_served == 0


def test_cell_down_races_inflight_drain(setup):
    """A blackout landing while a node is mid-drain under a preemption
    notice must supersede the notice and push everything through the same
    ledger-safe evacuation path — balanced accounting, nothing lost or
    double-served across the re-route to the sibling cell."""
    from repro.control import MultiCellBackend

    c, m, params = setup
    cell0 = ElasticClusterFrontend(
        _factory(m, params), 2, initial_replicas=1, seed=1,
        chaos=ChaosSchedule.parse("preempt@2:n0:k4"))
    cell1 = ElasticClusterFrontend(_factory(m, params), 2,
                                   initial_replicas=1, seed=2)
    mc = MultiCellBackend(
        [cell0, cell1],
        chaos=ChaosSchedule.parse("cell_down@3:c0,cell_up@8:c0"), seed=0)
    for i in range(8):
        mc.submit(_req(i, n_new=8))
    for t in range(4):
        mc.tick(0.0)
        if t == 1:
            # notice active on cell 0's node 0, drain in flight
            assert cell0.nodes[0].draining or cell0.preempt_risk()[0] == 1.0
    assert mc.cell_downs == 1 and mc.evacuated_total > 0
    mc.run_until_drained()
    assert sorted(r.rid for r in mc.finished) == list(range(8))
    assert mc.ledger.balanced() and mc.ledger.double_served == 0


# ------------------------------------------------------ conservation matrix
def test_conservation_full_churn_matrix(setup):
    """Drain + stochastic failure + preemption mid-drain + retry storm, all
    at once: every rid lands in exactly one terminal state, nothing is lost
    or double-served, and the per-tick dispatch/sync bounds hold."""
    c, m, params = setup
    tiers = parse_tiers("premium:0.3:w5:4,batch:0.7:w1")
    rng = np.random.default_rng(0)

    def request_factory(rid, tick):
        plen = int(rng.integers(2, 8))
        req = Request(rid, rng.integers(1, c.vocab_size, plen).tolist(),
                      max_new_tokens=int(rng.integers(3, 8)))
        req.tier = tiers.sample(rng)
        return req

    fe = ElasticClusterFrontend(
        _factory(m, params, tiers=tiers), 2, initial_replicas=2,
        provisioning_delay=1, failure_rate=0.05, seed=7, tiers=tiers,
        preempt_notice=2,
        chaos=ChaosSchedule.parse("preempt@8:n0:k2,recover@16:n0"))
    pool = ClientPool(
        fe, 24, request_factory=request_factory, think_time=1.0,
        timeout={"premium": 6.0, "batch": 12.0}, max_retries=2,
        backoff_base=1.0, spawn_rate=8.0, seed=5)
    for t in range(24):
        pool.tick()
        fe.tick(0.0)
        g = fe.metrics()["fleet_groups"]
        assert fe.metrics()["syncs"] <= max(g, 1)
        if t == 6:
            fe.scale_to(np.array([1, 2]))            # drain mid-chaos
    pool.quiesce()
    fe.run_until_drained()
    pool.finalize()
    b = fe.ledger.balance()
    assert b["live"] == 0 and b["double_served"] == 0
    assert fe.ledger.balanced()
    assert b["submitted"] == sum(
        b[k] for k in ("finished", "timed_out", "abandoned", "rejected"))
    assert pool.stats["ok"] > 0
    # goodput metric never counted an expired or wasted completion
    assert b["finished"] >= pool.stats["ok"]


# ---------------------------------------------------------- chaos-off parity
def _stream_digest(c, m, params, tiers_spec, robust: bool):
    """Token-stream digest of a chaos-off scenario (stochastic failures,
    scale-down, scale-up). ``robust`` arms the robustness layer inert: an
    empty chaos schedule, a preemption notice, an admission cap nothing
    reaches and explicit no-deadline requests."""
    tiers = parse_tiers(tiers_spec)
    rng = np.random.default_rng(3)

    def make_replica(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, tiers=tiers)

    def request_factory(rid, tick):
        plen = int(rng.integers(2, 10))
        req = Request(rid, rng.integers(1, c.vocab_size, plen).tolist(),
                      max_new_tokens=int(rng.integers(3, 9)))
        if robust:
            req.deadline_tick = None
        if len(tiers) > 1:
            req.tier = tiers.sample(rng)
        return req

    robustness = dict(chaos=ChaosSchedule(), preempt_notice=3,
                      max_queue=10**6) if robust else {}
    fe = ElasticClusterFrontend(
        make_replica, 2, initial_replicas=2, provisioning_delay=2,
        failure_rate=0.08, request_factory=request_factory, seed=3,
        decode_block=1, tiers=tiers, **robustness)
    for t in range(24):
        fe.tick(1.5)
        if t == 10:
            fe.scale_to(np.array([1, 2]))
        if t == 16:
            fe.scale_to(np.array([2, 2]))
    fe.run_until_drained()
    assert fe.ledger.balanced()          # conservation even without chaos
    h = hashlib.sha256()
    for r in sorted(fe.finished, key=lambda r: r.rid):
        h.update(repr((r.rid, r.tier, tuple(r.output), r.arrival,
                       r.first_token_time, r.finish_time)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("tiers_spec", ["", "premium:0.3:w5:4,batch:0.7:w1"])
def test_chaos_off_streams_bit_identical_to_pr6(setup, tiers_spec):
    """The robustness layer, present but inert, must not perturb chaos-off
    streams by a single token: same digest as the plain path, in-process
    (a digest pinned on another machine or JAX build is not portable)."""
    c, m, params = setup
    assert _stream_digest(c, m, params, tiers_spec, robust=True) == \
        _stream_digest(c, m, params, tiers_spec, robust=False)


# ------------------------------------------------------------- fluid mirror
def _sim_cfg(**kw):
    kw.setdefault("num_nodes", 4)
    kw.setdefault("provisioning_delay", 2)
    kw.setdefault("node_mtbf", 1e12)
    kw.setdefault("straggler_prob", 0.0)
    return ClusterConfig(**kw)


def test_sim_preemption_mirror():
    sim = ClusterSim(_sim_cfg(), unit_capacity=10.0, seed=0,
                     heterogeneous=False,
                     chaos=ChaosSchedule.parse("preempt@3:n0:k2,recover@9:n0"))
    fr = np.full(4, 0.25)
    for t in range(1, 3):
        sim.tick(100.0, fr)
    assert sim.preempt_risk().tolist() == [0.0] * 4
    sim.tick(100.0, fr)                       # t=3: notice lands
    assert sim.preempt_risk()[0] == 1.0
    assert sim.state.pending[0].sum() == 0    # spawns cancelled
    sim.scale_to(np.array([6, 6, 6, 6]))      # refused on the noticed node
    assert sim.state.pending[0].sum() == 0
    assert sim.state.pending[1].sum() > 0
    q_before = float(sim.state.queue.sum() + sim.state.retry_pool)
    for t in range(4, 7):
        sim.tick(0.0, fr)
    # expired: node 0 down, replicas gone, queue conserved via retry pool
    assert sim.state.up[0] == 0.0 and sim.state.active[0] == 0
    assert float(sim.state.queue.sum() + sim.state.retry_pool) <= q_before
    assert sim._preempt_down[0]
    for t in range(7, 10):
        sim.tick(0.0, fr)                     # t=9: scripted recovery
    assert sim.state.up[0] == 1.0 and not sim._preempt_down[0]
    assert sim.preempt_risk().tolist() == [0.0] * 4
    with pytest.raises(ValueError):
        sim.preempt_node(99)
    with pytest.raises(ValueError):
        sim.recover_node(1)


# ------------------------------------------------------------- planner risk
def test_gpso_preemption_risk_term():
    cfg = _sim_cfg(num_nodes=2)
    demand = jnp.asarray([5.0, 5.0])
    base_ctx = (demand, jnp.asarray(10.0), jnp.float32(1.0),
                jnp.float32(cfg.lam), jnp.float32(cfg.target_load))
    risk = jnp.asarray([1.0, 0.0])
    ctx = base_ctx + (jnp.float32(cfg.risk_lam), risk)
    risky = jnp.asarray([[4.0, 1.0]])
    safe = jnp.asarray([[1.0, 4.0]])
    # same base cost by symmetry; the risk term must separate them
    assert float(eq9_fitness(risky, base_ctx)[0]) == pytest.approx(
        float(eq9_fitness(safe, base_ctx)[0]))
    assert float(eq9_risk_fitness(risky, ctx)[0]) > \
        float(eq9_risk_fitness(safe, ctx)[0])
    # end to end: the planner shifts capacity off the at-risk node
    scaler = GPSOAutoscaler(cfg, unit_capacity=10.0, seed=0)
    cur = np.array([2, 2], np.int32)
    tgt = scaler.plan(np.array([5.0, 5.0], np.float32), 40, cur,
                      preempt_risk=np.array([1.0, 0.0], np.float32))
    assert tgt[0] <= tgt[1]
    # all-zero risk keeps the base objective: identical plan to omitting it
    s1 = GPSOAutoscaler(cfg, unit_capacity=10.0, seed=0)
    s2 = GPSOAutoscaler(cfg, unit_capacity=10.0, seed=0)
    t1 = s1.plan(np.array([5.0, 3.0], np.float32), 40, cur)
    t2 = s2.plan(np.array([5.0, 3.0], np.float32), 40, cur,
                 preempt_risk=np.zeros(2, np.float32))
    assert (t1 == t2).all()


# ------------------------------------------------------- closed-loop clients
def test_client_pool_flash_ramp_and_stats(setup):
    c, m, params = setup

    def request_factory(rid, tick):
        return _req(rid, plen=3, n_new=4)

    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=2)
    pool = ClientPool(fe, 10, request_factory=request_factory,
                      think_time=1.0, timeout=20.0, max_retries=1,
                      spawn_rate=4.0, seed=2)
    ramp = []
    for _ in range(20):
        pool.tick()
        ramp.append(pool.active_clients)
        fe.tick(0.0)
    assert ramp[0] == 4 and ramp[1] == 8 and ramp[2] == 10  # spawn ramp
    pool.quiesce()
    fe.run_until_drained()
    pool.finalize()
    s = pool.summary()
    assert s["ok"] > 0 and s["latency_mean"] is not None
    assert fe.ledger.balanced()
    # every rid the pool ever created ends ok or abandoned client-side
    # (the pool is the frontend's only traffic source here)
    assert fe.ledger.submitted == s["ok"] + s["abandoned"]
    # attempts >= distinct rids (retries re-use the rid)
    assert s["issued"] >= fe.ledger.submitted
