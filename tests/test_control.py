"""Unified control plane + elastic request-level backend.

Covers the ClusterBackend contract both ways: operational semantics of the
elastic engine (cold-start provisioning, drain-before-remove, failure
re-queue, heterogeneous replicas), the bucketed-prefill retrace bound, the
routing-fraction guard, straggler persistence in the fluid sim, ranking
parity between the fluid and request-level backends under the same plane,
and the plane's placement on the host device.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.paper_cluster import ClusterConfig
from repro.control import ControlPlane, SimBackend
from repro.models import make_model
from repro.serving import (ClusterFrontend, ElasticClusterFrontend,
                           ReplicaEngine, Request, normalize_fractions)
from repro.sim.cluster import ClusterSim
from repro.sim.experiment import collect_episode

MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    return c, m, params


def _factory(m, params, max_batch=2, speed=1.0):
    def make_replica(rid):
        return ReplicaEngine(m, params, max_batch=max_batch, max_seq=MAX_SEQ,
                             rid=rid, speed=speed)
    return make_replica


def _req(i, plen=4, n_new=4):
    return Request(i, [1 + (i + j) % 97 for j in range(plen)],
                   max_new_tokens=n_new)


# ---------------------------------------------------------------- elastic
def test_scale_up_respects_provisioning_delay(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=1,
                                provisioning_delay=3)
    fe.scale_to(np.array([3]))
    assert fe.in_flight().tolist() == [3]
    live = []
    for _ in range(4):
        fe.tick(0.0)
        live.append(len(fe.nodes[0].live))
    # cold start: nothing serves before the delay elapses, then all arrive
    assert live == [1, 1, 3, 3]


def test_drain_before_remove_finishes_in_flight(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=2)
    reqs = [_req(i, n_new=6) for i in range(4)]
    for r in reqs:
        fe.submit(r)
    fe.tick(0.0)                          # route + admit across both replicas
    assert all(e.n_active > 0 for e in fe.nodes[0].live)
    fe.scale_to(np.array([1]))            # remove one replica
    node = fe.nodes[0]
    assert len(node.live) == 1 and len(node.draining) == 1
    drained = node.draining[0]
    assert drained.draining and drained.n_active > 0
    fe.run_until_drained()
    # no dropped in-flight work: every request finished with full output
    assert all(r.done and len(r.output) == 6 for r in reqs)
    assert node.draining == [] and len(node.live) == 1
    # a draining replica admits nothing new
    fe.submit(_req(99, n_new=2))
    fe.run_until_drained()
    assert drained.steps <= 6 + 1         # only its original slot work


def test_replica_failure_requeues_lost_work(setup):
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 1, initial_replicas=2)
    reqs = [_req(i, n_new=5) for i in range(4)]
    for r in reqs:
        fe.submit(r)
    fe.tick(0.0)
    victim = fe.nodes[0].live[0]
    carried = [r for r in victim.slots if r is not None] + list(victim.queue)
    assert carried, "victim replica should hold work"
    fe.fail_replica(0, 0)
    assert fe.failed_replicas == 1
    assert len(fe.nodes[0].live) == 1
    # lost requests had their progress reset and sit back in the node queue
    assert all(not r.done and r.output == [] for r in carried)
    fe.run_until_drained()
    assert all(r.done and len(r.output) == 5 for r in reqs)


def test_dead_node_work_reroutes_to_healthy_node(setup):
    """When every replica on a node dies, its queued work must migrate to
    healthy nodes (the elastic twin of the sim's retry pool) instead of
    stranding forever."""
    c, m, params = setup
    fe = ElasticClusterFrontend(_factory(m, params), 2, initial_replicas=1)
    fe.route(np.array([1.0, 0.0]))        # pin everything to node 0
    reqs = [_req(i, n_new=3) for i in range(4)]
    for r in reqs:
        fe.submit(r)
    fe.tick(0.0)
    fe.fail_replica(0, 0)                 # node 0 now has no replicas
    assert fe.up_mask().tolist() == [0.0, 1.0]
    fe.route(np.array([0.5, 0.5]))        # routing guard masks dead node
    fe.run_until_drained()
    assert all(r.done and len(r.output) == 3 for r in reqs)


def test_heterogeneous_speed_drains_faster(setup):
    c, m, params = setup

    def drain_ticks(speed):
        fe = ElasticClusterFrontend(_factory(m, params, max_batch=2,
                                             speed=speed), 1,
                                    initial_replicas=1)
        for i in range(6):
            fe.submit(_req(i, n_new=6))
        for t in range(1, 200):
            fe.tick(0.0)
            if fe.nodes[0].unfinished() == 0 and not fe.pending:
                return t
        raise AssertionError("did not drain")

    # a 2x-speed replica runs two decode sub-steps per tick via the credit
    # scheduler -> roughly half the wall-clock ticks
    assert drain_ticks(2.0) < 0.7 * drain_ticks(1.0)


# ---------------------------------------------------- prefill retrace bound
def test_prefill_retraces_bounded_by_buckets(setup):
    """Acceptance: prefill compiles O(log max_seq) bucketed variants, not
    once per distinct prompt length."""
    c, m, params = setup
    eng = ReplicaEngine(m, params, max_batch=4, max_seq=MAX_SEQ)
    t0 = eng.prefill_traces        # kernels are shared across replicas of
    lens = list(range(2, 31))      # the same model; count this run's delta
    for i, L in enumerate(lens):
        eng.submit(_req(i, plen=L, n_new=2))
    for _ in range(400):
        eng.step()
        if eng.load == 0:
            break
    assert eng.load == 0
    compiles = eng.prefill_traces - t0
    len_buckets = int(np.log2(MAX_SEQ // eng.min_bucket)) + 1
    batch_buckets = int(np.log2(eng.max_batch)) + 1
    assert compiles <= len_buckets * batch_buckets
    assert compiles < len(set(lens))   # beats once-per-prompt-length


def test_replicas_share_compiled_kernels(setup):
    """A cold-started replica of the same model reuses compiled serve
    kernels instead of re-jitting (scale-ups must not stall on XLA)."""
    c, m, params = setup
    e1 = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    e1.submit(_req(0, plen=4, n_new=2))
    e1.step()
    before = e1.prefill_traces
    e2 = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    assert e2._prefill is e1._prefill
    e2.submit(_req(1, plen=4, n_new=2))
    e2.step()
    assert e2.prefill_traces == before    # same shape -> zero new compiles


# ------------------------------------------------------- fraction guard
def test_normalize_fractions_guards_zero_and_nan():
    n = 4
    uniform = np.full(n, 0.25)
    assert np.allclose(normalize_fractions(np.zeros(n)), uniform)
    assert np.allclose(normalize_fractions(np.full(n, np.nan)), uniform)
    assert np.allclose(normalize_fractions(np.array([-1.0, 0, 0, 0])),
                       uniform)
    masked = normalize_fractions(np.zeros(n), mask=np.array([1, 1, 0, 0]))
    assert np.allclose(masked, [0.5, 0.5, 0, 0])
    fr = normalize_fractions(np.array([np.inf, 1.0, 0, 0]))
    assert np.isfinite(fr).all() and fr.sum() == pytest.approx(1.0)
    # all-false mask: uniform-over-NONE (zeros), never a uniform split over
    # dead nodes — callers park arrivals instead of routing them (PR 8)
    dead = normalize_fractions(np.ones(n), mask=np.zeros(n))
    assert dead.tolist() == [0.0] * n
    assert normalize_fractions(np.full(n, np.nan),
                               mask=np.zeros(n)).tolist() == [0.0] * n


def test_frontend_fractions_policy_survives_bad_fn(setup):
    c, m, params = setup
    engines = [ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=i)
               for i in range(2)]
    fe = ClusterFrontend(engines, policy="fractions",
                         fractions_fn=lambda fe: np.zeros(2))
    for i in range(4):
        fe.submit(_req(i, n_new=2))
    fe.run_until_drained()
    assert len(fe.finished) == 4


# ------------------------------------------------- straggler persistence
def test_straggler_slowdown_persists_across_ticks():
    cfg = ClusterConfig(num_nodes=4, straggler_prob=0.0, node_mtbf=1e12)
    sim = ClusterSim(cfg, 30.0, seed=0, failures=True)
    sim.state.slow_left[:] = 3
    uniform = np.full(4, 0.25, np.float32)
    slows = []
    for _ in range(4):
        sim.tick(1.0, uniform)
        slows.append(float(sim.state.slow[0]))
    # degraded for the sampled duration, then recovers (the old code reset
    # the multiplier from a fresh Bernoulli draw every tick)
    assert slows[:2] == pytest.approx([cfg.straggler_slowdown] * 2)
    assert slows[-1] == 1.0


# ------------------------------------------------------- backend parity
def _parity_cfg():
    return ClusterConfig(
        num_nodes=2, horizon=4, forecast_window=8, provisioning_delay=2,
        max_replicas_per_node=2, min_replicas_per_node=1, scale_interval=3,
        cooldown=6, straggler_prob=0.0, node_mtbf=1e12)


N_NEW = 4          # fixed decode length -> replica rate = max_batch / N_NEW

_PARITY_TIERS = None     # module default: untiered


def _run_elastic(m, params, cfg, arrivals, scaler, tiers=None):
    def request_factory(rid, tick):
        req = Request(rid, [1 + rid % 50, 2, 3, 4], max_new_tokens=N_NEW)
        if tiers is not None:
            req.tier = tiers.names[rid % len(tiers)]
        return req

    # synchronous ticks: the fluid sim observes its own tick synchronously,
    # so the apples-to-apples ranking comparison runs the engine's
    # per-replica loop too — the fleet tick intentionally delays metric
    # observation by one tick, which shifts WHICH node the scaler grows
    # first (legit controller divergence, not a serving difference)
    fe = ElasticClusterFrontend(
        _factory(m, params, max_batch=2), cfg.num_nodes, initial_replicas=1,
        provisioning_delay=cfg.provisioning_delay,
        max_replicas_per_node=cfg.max_replicas_per_node,
        request_factory=request_factory, seed=0, est_tokens=N_NEW,
        fleet_batch=False, tiers=tiers)
    plane = ControlPlane(cfg, fe, balancer="rr", scaler=scaler,
                         unit_capacity=2.0 / N_NEW, seed=0,
                         init_arrival=float(arrivals[:5].mean()))
    return collect_episode(plane, arrivals, scaler, cfg,
                           unit_capacity=2.0 / N_NEW)


def _run_sim(cfg, arrivals, scaler, tiers=None):
    sim = ClusterSim(cfg, 2.0 / N_NEW, seed=0, failures=False,
                     heterogeneous=False, tiers=tiers)
    plane = ControlPlane(cfg, SimBackend(sim), balancer="rr", scaler=scaler,
                         unit_capacity=2.0 / N_NEW, seed=0,
                         init_arrival=float(arrivals[:5].mean()))
    return collect_episode(plane, arrivals, scaler, cfg,
                           unit_capacity=2.0 / N_NEW)


def _ranking_parity(m, params, tiers=None):
    """Shared body: static vs rbas ranking must agree sim <-> elastic."""
    # 1.6 req/tick vs static capacity of 2 nodes x 1 replica x 0.5 req/tick:
    # static saturates, the autoscaler can double capacity.
    arrivals = np.full(36, 1.6, np.float32)
    cfg = _parity_cfg()
    rankings = {}
    for backend in ("sim", "engine"):
        res = {}
        for scaler in ("static", "rbas"):
            if backend == "sim":
                r = _run_sim(cfg, arrivals, scaler, tiers=tiers)
            else:
                r = _run_elastic(m, params, cfg, arrivals, scaler,
                                 tiers=tiers)
            res[scaler] = r.summary(warmup=8)["mean_resp"]
        rankings[backend] = sorted(res, key=res.get)
    assert rankings["sim"] == rankings["engine"]
    assert rankings["sim"][0] == "rbas"   # autoscaling wins under saturation


def test_method_ranking_matches_across_backends(setup):
    """The same ControlPlane over the fluid sim and the request-level engine
    must rank scaling policies identically: under a saturating trace, the
    rule-based autoscaler beats the static allocation on response time on
    BOTH backends (the paper's qualitative claim, ported to real forwards)."""
    c, m, params = setup
    _ranking_parity(m, params)


def test_method_ranking_matches_across_backends_3tier(setup):
    """Backend-ranking parity holds under SLO-tiered traffic too: both
    backends run the tiered queues/metrics path (premium-first fluid drain
    vs weighted-deficit request admission) and still rank the scaling
    policies identically."""
    from repro.workload import TierSet, TierSpec

    c, m, params = setup
    tiers = TierSet([TierSpec("premium", share=0.34, weight=5.0,
                              ttft_target=4.0),
                     TierSpec("standard", share=0.33, weight=2.0),
                     TierSpec("batch", share=0.33, weight=1.0)])
    _ranking_parity(m, params, tiers=tiers)


def test_async_observation_shifts_decisions_at_most_one_tick(setup):
    """Stale-observation contract: the fleet tick's metrics describe the
    device state one tick earlier, so on a fixed trace every rule-based
    ``scale_to`` decision of the fleet backend must appear among the
    per-replica loop's (synchronous) decisions within one plan interval
    (rbas plans every tick, window t-1..t+1) — staleness may DELAY a
    decision, never diverge it.
    Pinned on a single-node backend: with several nodes the lag legally
    shifts WHICH node grows first (see ``_run_elastic``), which compounds
    into different per-node trajectories; the total-capacity decision is
    the contract."""
    c, m, params = setup
    arrivals = np.full(28, 1.6, np.float32)
    cfg = ClusterConfig(
        num_nodes=1, horizon=4, forecast_window=8, provisioning_delay=2,
        max_replicas_per_node=4, min_replicas_per_node=1, scale_interval=3,
        cooldown=6, straggler_prob=0.0, node_mtbf=1e12)

    def decisions(fleet):
        def request_factory(rid, tick):
            return Request(rid, [1 + rid % 50, 2, 3, 4],
                           max_new_tokens=N_NEW)

        fe = ElasticClusterFrontend(
            _factory(m, params, max_batch=2), 1, initial_replicas=1,
            provisioning_delay=cfg.provisioning_delay,
            max_replicas_per_node=cfg.max_replicas_per_node,
            request_factory=request_factory, seed=0, est_tokens=N_NEW,
            fleet_batch=fleet)
        plane = ControlPlane(cfg, fe, balancer="rr", scaler="rbas",
                             unit_capacity=2.0 / N_NEW, seed=0,
                             init_arrival=float(arrivals[:5].mean()))
        out = []
        orig = fe.scale_to

        def spy(target):
            out.append(int(np.asarray(target).sum()))
            orig(target)

        fe.scale_to = spy
        for a in arrivals:
            plane.step(float(a))
        return out

    sync = decisions(False)
    lagged = decisions(True)
    assert len(sync) == len(lagged) == len(arrivals)
    for t, d in enumerate(lagged):
        lo, hi = max(t - 1, 0), min(t + 1, len(sync) - 1)
        assert d in sync[lo:hi + 1], (t, d, sync[lo:hi + 1])
    assert sync[-1] == lagged[-1]        # same steady-state capacity


def test_ours_stack_runs_on_elastic_backend(setup):
    """Full OURS wiring (RL balancer + GPSO autoscaler) drives the elastic
    backend end-to-end and produces finite metrics + scaling actions."""
    from repro.core import balancer as bal

    c, m, params = setup
    cfg = _parity_cfg()
    rl = bal.RLBalancer(cfg, 4 + cfg.horizon, seed=0)

    def request_factory(rid, tick):
        return Request(rid, [1, 2, 3, 4], max_new_tokens=N_NEW)

    fe = ElasticClusterFrontend(
        _factory(m, params, max_batch=2), cfg.num_nodes, initial_replicas=1,
        provisioning_delay=1,
        max_replicas_per_node=cfg.max_replicas_per_node,
        request_factory=request_factory, seed=0, est_tokens=N_NEW)
    plane = ControlPlane(cfg, fe, balancer="rl", scaler="gpso",
                         unit_capacity=2.0 / N_NEW, rl=rl, seed=0,
                         init_arrival=1.5)
    for _ in range(8):
        m_ = plane.step(1.5)
    assert np.isfinite(m_["response_time"])
    assert np.isfinite(m_["mean_utilization"])
    assert (fe.in_flight() >= 1).all()
    fe.run_until_drained()
    assert all(r.done for r in fe.finished)


# ------------------------------------------------------ host placement
# Two virtual CPU devices need ``--xla_force_host_platform_device_count``
# before jax initializes, so this runs in a subprocess. ``cpu:1`` is made
# the default device and stands in for the accelerator; ``cpu:0`` is what
# ``repro.core.host_device()`` returns.
_PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
import numpy as np

from repro.configs.paper_cluster import ClusterConfig
from repro.control import ControlPlane, SimBackend
from repro.control import plane as plane_mod
from repro.core import autoscaler, host_device
from repro.core import balancer as bal
from repro.core.forecaster import init_forecaster
from repro.sim.cluster import ClusterSim

host, accel = jax.devices("cpu")
fetched = {"plane": [], "scale": []}


def on_host(tree):
    return all(x.committed and x.devices() == {host}
               for x in jax.tree.leaves(tree))


def recording(kind, get):
    def timed_get(owner, arrays):
        fetched[kind].append(on_host(arrays))
        return get(owner, arrays)
    return timed_get


plane_mod.timed_get = recording("plane", plane_mod.timed_get)
autoscaler.timed_get = recording("scale", autoscaler.timed_get)


def run(default, gru):
    jax.config.update("jax_default_device", default)
    cfg = ClusterConfig(
        num_nodes=2, horizon=4, forecast_window=8, provisioning_delay=2,
        max_replicas_per_node=3, min_replicas_per_node=1, scale_interval=3,
        cooldown=6, straggler_prob=0.0, node_mtbf=1e12, batch_size=4,
        ga_pop=16, ga_elite=4, ga_generations=4, pso_iters=4)
    sim = ClusterSim(cfg, 0.5, seed=0, failures=False, heterogeneous=False)
    backend = SimBackend(sim)
    targets = []
    scale_to = backend.scale_to
    backend.scale_to = lambda t: (targets.append(np.asarray(t).tolist()),
                                  scale_to(t))[1]
    rl = bal.RLBalancer(cfg, 4 + cfg.horizon, seed=0)
    fp = init_forecaster(jax.random.PRNGKey(1), 1, 8, cfg.horizon) \
        if gru else None
    plane = ControlPlane(cfg, backend, balancer="rl", scaler="gpso",
                         unit_capacity=0.5, rl=rl, forecaster_params=fp,
                         train_rl=True, explore=True, seed=0,
                         init_arrival=1.5)
    steps = []
    for a in 1.0 + 1.5 * np.random.default_rng(0).random(14):
        m = plane.step(float(a))
        steps.append([plane._prev_fc1, plane.fractions.tobytes().hex(),
                      float(m["response_time"])])
    state = [np.asarray(x).tobytes().hex() for x in jax.tree.leaves(rl.state)]
    return {
        "steps": steps, "targets": targets, "state": state,
        "trained": rl.buffer.size >= cfg.batch_size,
        "state_on_host": on_host((rl.state, rl.a_hat, plane.scaler._key,
                                  plane.forecaster_params)),
        "syncs": plane.syncs,
    }


out = {"host_device": str(host_device())}
for gru in (False, True):
    name = "gru" if gru else "last_value"
    runs = {str(d): run(d, gru) for d in (accel, host)}
    a, h = runs[str(accel)], runs[str(host)]
    out[name] = {
        "same_decisions": all(a[k] == h[k]
                              for k in ("steps", "targets", "state")),
        "state_on_host": a["state_on_host"], "trained": a["trained"],
        "syncs": a["syncs"],
    }
out["forecast_and_fractions_on_host"] = all(fetched["plane"])
out["plans_on_host"] = all(fetched["scale"])
out["fetches"] = {k: len(v) for k, v in fetched.items()}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def placement(tmp_path_factory):
    script = tmp_path_factory.mktemp("placement") / "run.py"
    script.write_text(_PLACEMENT_SCRIPT)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_plane_decisions_run_on_host_device(placement):
    """With another device as the default (the accelerator's stand-in), the
    forecast, the balancer's fractions and every GPSO target are computed
    on ``host_device()`` and fetched from there."""
    assert placement["host_device"] == "TFRT_CPU_0"
    # 2 forecasters x 2 default devices x 14 ticks x (forecast + balance)
    assert placement["fetches"] == {"plane": 112, "scale": 16}
    assert placement["forecast_and_fractions_on_host"]
    assert placement["plans_on_host"]


@pytest.mark.parametrize("forecaster", ["last_value", "gru"])
def test_plane_state_committed_to_host_device(placement, forecaster):
    """The actor's state (after DDPG updates too), its adjacency, the GPSO
    key and the forecaster's parameters stay committed to the host."""
    run = placement[forecaster]
    assert run["trained"]
    assert run["state_on_host"]
    # one fetch each for the forecast and the fractions, 4 GPSO plans
    assert run["syncs"] == 14 * 2 + 4


@pytest.mark.parametrize("forecaster", ["last_value", "gru"])
def test_plane_decisions_independent_of_default_device(placement,
                                                       forecaster):
    """Every forecast, fraction, scale target and the trained actor are
    bit-identical to the same plane run with the host as the default."""
    assert placement[forecaster]["same_decisions"]
