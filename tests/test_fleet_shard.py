"""Sharded fleet slab: F replicas decode on N devices, bit-identically.

Acceptance coverage for the fleet-mesh serving path (ISSUE 6):

  * sharded == unsharded parity — identical token streams and finish clocks
    through a 4-device ``('fleet',)`` mesh for the dense, ssm and hybrid
    families, across the churn matrix (mid-run failure evacuation, graceful
    drain, scale-up) and for the async ``decode_block=4``, chunked-prefill
    and SLO-tier modes;
  * the dispatch/sync contract survives sharding: still ONE logical decode
    dispatch per fleet group per tick and at most ONE blocking reconcile
    sync per tick (GSPMD partitions the dispatch; it must not multiply it);
  * pow2 growth keeps the fleet axis divisible by the shard count
    (3 -> 4 -> 8 members under 4 devices allocates caps 4, 4, 8) with pad
    rows masked inactive and excluded from dispatch/retire accounting
    (dispatch counts match the unsharded oracle exactly);
  * slab + operand shardings stay pinned to the fleet axis through donated
    dispatches, churn backfills and slab growth (no silent re-gather).

Multi-device CPU needs ``--xla_force_host_platform_device_count`` set
before jax's backend initializes, so the whole matrix runs in ONE
subprocess (jax is already single-device in the pytest process) that
prints a JSON summary; the host-side tests assert on slices of it.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_fleet_mesh
from repro.models import make_model
from repro.serving import (ElasticClusterFrontend, FleetGroup,
                           ReplicaEngine, Request)
from repro.workload.trace import DEFAULT_TIERS

MAX_SEQ = 64
mesh = make_fleet_mesh()
out = {"n_dev": jax.local_device_count()}


def make_reqs(n, n_new=6, seed=3, vocab=400, long=False, tiers=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = rng.integers(20, 40) if long else rng.integers(3, 9)
        kw = {}
        if tiers:
            kw["tier"] = tiers.names[rng.integers(0, len(tiers.names))]
        reqs.append(Request(i, rng.integers(1, vocab, plen).tolist(),
                            max_new_tokens=n_new, **kw))
    return reqs


def snap(reqs):
    return {r.rid: (tuple(r.output), r.finish_time) for r in reqs}


def model_for(arch):
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    return m, m.init(jax.random.PRNGKey(0), jnp.float32)


# ---- churn-matrix parity per family (failure, drain, scale-up mid-run)
out["parity"] = {}
out["dispatch_match"] = {}
for arch in ("granite-3-8b", "mamba2-1.3b", "zamba2-2.7b"):
    m, params = model_for(arch)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(use_mesh):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    mesh=mesh if use_mesh else None)
        reqs = make_reqs(10)
        for r in reqs:
            fe.submit(r)
        fe.tick(0.0)
        fe.fail_replica(0, 0)          # row drop + swap-backfill mid-run
        fe.tick(0.0)
        fe.scale_to(np.array([1, 1]))  # graceful drain
        fe.tick(0.0)
        fe.scale_to(np.array([2, 2]))  # scale-up: slab grows
        fe.run_until_drained()
        return snap(reqs), fe

    base, fe0 = run(False)
    shard, fe1 = run(True)
    out["parity"][arch] = base == shard
    # pad rows must not inflate the dispatch/sync accounting
    out["dispatch_match"][arch] = (
        fe0.decode_dispatches() == fe1.decode_dispatches()
        and fe0.sync_count() == fe1.sync_count())

# ---- mode parity on the dense family: block4 / chunked / tiers
m, params = model_for("granite-3-8b")
out["modes"] = {}
for label, kw in (("block4", dict(decode_block=4, n=12)),
                  ("chunk", dict(chunk_len=8, long=True, n=8)),
                  ("tiers", dict(tiers=DEFAULT_TIERS, n=12))):
    chunk_len = kw.pop("chunk_len", 0)
    tiers = kw.pop("tiers", None)
    n = kw.pop("n")
    long = kw.pop("long", False)
    decode_block = kw.pop("decode_block", 1)

    def factory(rid):
        ekw = {}
        if chunk_len:
            ekw["chunk_len"] = chunk_len
        if tiers:
            ekw["tiers"] = tiers
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, **ekw)

    def run(use_mesh):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    decode_block=decode_block, tiers=tiers,
                                    mesh=mesh if use_mesh else None)
        reqs = make_reqs(n, tiers=tiers, long=long)
        for r in reqs:
            fe.submit(r)
        fe.tick(0.0)
        fe.scale_to(np.array([2, 2]))
        fe.run_until_drained()
        return snap(reqs)

    out["modes"][label] = run(False) == run(True)

# ---- dispatch/sync bound per tick under sharding (saturated slab)
def factory(rid):
    return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)

fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                            mesh=mesh)
for r in make_reqs(16, n_new=8):
    fe.submit(r)
ticks = []
for _ in range(4):
    mtr = fe.tick(0.0)
    ticks.append({"groups": mtr["fleet_groups"],
                  "dispatches": mtr["decode_dispatches"],
                  "syncs": mtr["syncs"]})
out["ticks"] = ticks
fe.run_until_drained()

# ---- growth divisibility: 3 -> 4 -> 8 members under 4 shards
g = FleetGroup(m, params, max_batch=2, max_seq=MAX_SEQ, mesh=mesh)
caps = []
engs = [ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=i)
        for i in range(8)]
for i, e in enumerate(engs):
    g.add(e)
    if i + 1 in (3, 4, 5, 8):
        caps.append([i + 1, g.cap])
out["growth_caps"] = caps
out["growth_divisible"] = all(c % 4 == 0 for _, c in caps)

# a 3-member fleet (1 pad row on the 4-wide slab) must match the
# unsharded 3-member fleet stream-for-stream and dispatch-for-dispatch
def run3(use_mesh):
    fe = ElasticClusterFrontend(factory, 1, initial_replicas=3, seed=0,
                                mesh=mesh if use_mesh else None)
    reqs = make_reqs(9, n_new=5, seed=11)
    for r in reqs:
        fe.submit(r)
    fe.run_until_drained()
    return snap(reqs), fe.decode_dispatches()

(s0, d0), (s1, d1) = run3(False), run3(True)
out["growth_parity"] = s0 == s1 and d0 == d1

# ---- sharding stays pinned after the dispatches above
stable = True
for leaf in jax.tree.leaves(g.slab):
    spec = leaf.sharding.spec
    stable &= bool(spec) and spec[0] == "fleet"
out["sharding_stable"] = stable

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    script = tmp_path_factory.mktemp("shard") / "run.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_mesh_visible(result):
    assert result["n_dev"] == 4


@pytest.mark.parametrize("arch", ["granite-3-8b", "mamba2-1.3b",
                                  "zamba2-2.7b"])
def test_sharded_parity_across_churn(result, arch):
    """Token streams + finish clocks identical to the unsharded fleet
    through failure / drain / scale-up, per model family."""
    assert result["parity"][arch]
    assert result["dispatch_match"][arch]


@pytest.mark.parametrize("mode", ["block4", "chunk", "tiers"])
def test_sharded_parity_modes(result, mode):
    """decode_block fusion, chunked prefill and SLO tiers all hold parity
    under the fleet mesh."""
    assert result["modes"][mode]


def test_one_dispatch_one_sync_per_tick_sharded(result):
    """Sharding partitions the dispatch, it must not multiply it: one
    logical decode dispatch per group per tick, <= 1 blocking sync."""
    for i, t in enumerate(result["ticks"]):
        assert t["groups"] == 1, result["ticks"]
        assert t["syncs"] <= 1, result["ticks"]
        if i > 0:                       # first tick only admits
            assert t["dispatches"] == 1, result["ticks"]


def test_growth_keeps_fleet_axis_divisible(result):
    """3 -> 4 -> 5 -> 8 members under 4 shards allocates caps 4, 4, 8, 8:
    per-shard sub-capacity grows pow2, fleet axis stays divisible."""
    assert result["growth_caps"] == [[3, 4], [4, 4], [5, 8], [8, 8]]
    assert result["growth_divisible"]
    assert result["growth_parity"]      # pad row inert: streams + dispatches


def test_slab_sharding_stable(result):
    """Donated dispatches and churn must leave the slab pinned to the
    fleet axis (a silent re-gather would serialize the fleet again)."""
    assert result["sharding_stable"]
