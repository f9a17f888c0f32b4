"""Spans, wait counters and wall-clock stamps (``repro.obs``).

  * every admission path (fleet bucketed, fleet singles, chunked prefill,
    standalone engine) stamps ``admitted_s`` no earlier than the frontend's
    ``submitted_s``, and ``reset_progress`` forgets the admission;
  * the control plane accounts its own blocking fetches: one per forecast,
    one per balancer action, one more on each GPSO plan tick;
  * the plane's phases appear as ``repro.plane.*`` spans in a profiler
    trace, nested in ``repro.plane.step``.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.paper_cluster import ClusterConfig
from repro.control import ControlPlane, SimBackend
from repro.core import balancer as bal
from repro.models import make_model
from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request
from repro.sim.cluster import ClusterSim

MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    return c, m, params


# (engine options, frontend options): each admission path
PATHS = {
    "fleet_bucketed": ({}, {}),
    "fleet_singles": ({"bucket_prompts": False}, {}),
    "chunked": ({"chunk_len": 8}, {}),
    "standalone": ({}, {"fleet_batch": False}),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_admission_stamped_after_submission(setup, path):
    """Every request is admitted after it was submitted, also after a
    replica failure sends it back through admission."""
    _, m, params = setup
    eng_kw, fe_kw = PATHS[path]

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, **eng_kw)

    fe = ElasticClusterFrontend(factory, 2, initial_replicas=1, seed=0,
                                **fe_kw)
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(1, 400, ln).tolist(), max_new_tokens=4)
            for i, ln in enumerate([5, 20, 3, 30, 9, 12])]
    for r in reqs:
        assert fe.submit(r)
        assert r.submitted_s is not None and r.admitted_s is None
    fe.tick(0.0)
    admitted = [r for r in reqs if r.admitted_s is not None]
    assert admitted
    fe.fail_replica(0, 0)        # evacuates: those requests admit again
    fe.run_until_drained()
    assert all(r.done for r in reqs)
    for r in reqs:
        assert r.submitted_s <= r.admitted_s, (path, r.rid)
    if path == "chunked":
        assert fe.prefill_dispatches() > 0


def test_reset_progress_forgets_admission():
    r = Request(0, [1, 2, 3], max_new_tokens=2)
    r.submitted_s, r.admitted_s = 1.0, 2.0
    r.output = [7]
    r.reset_progress()
    assert r.admitted_s is None and r.submitted_s == 1.0 and r.output == []


def _plane_over_sim():
    cfg = ClusterConfig(
        num_nodes=2, horizon=4, forecast_window=8, provisioning_delay=2,
        max_replicas_per_node=2, min_replicas_per_node=1, scale_interval=3,
        cooldown=6, straggler_prob=0.0, node_mtbf=1e12)
    sim = ClusterSim(cfg, 0.5, seed=0, failures=False)
    rl = bal.RLBalancer(cfg, 4 + cfg.horizon, seed=0)
    return cfg, ControlPlane(cfg, SimBackend(sim), balancer="rl",
                             scaler="gpso", unit_capacity=0.5, rl=rl,
                             seed=0, init_arrival=1.0)


def test_plane_counts_its_fetches():
    """One fetch per forecast and per actor call, and one more on each
    tick the GPSO autoscaler plans."""
    cfg, plane = _plane_over_sim()
    for _ in range(8):
        t, syncs, wait = plane.t, plane.syncs, plane.sync_wait
        plane.step(1.0)
        plans = t % cfg.scale_interval == 0 and t > 0
        assert plane.syncs - syncs == 2 + plans, t
        assert plane.sync_wait > wait


def test_plane_spans_in_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    _, plane = _plane_over_sim()
    plane.step(1.0)                # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            plane.step(1.0)
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("repro.")]
    names = {n for n, _, _ in spans}
    assert {"repro.plane.step", "repro.plane.forecast", "repro.plane.observe",
            "repro.plane.balance", "repro.plane.learn", "repro.plane.scale",
            "repro.fetch"} <= names
    steps = [(s, e) for n, s, e in spans if n == "repro.plane.step"]
    assert len(steps) == 4
    for n, s, e in spans:
        assert any(s0 <= s and e <= e1 for s0, e1 in steps), n
