"""Fleet-batched decode: one device dispatch per tick for the whole cluster.

Acceptance coverage for the fleet serving path:

  * fleet-vs-single parity — the same requests and seeds through per-replica
    ``step()`` and fleet-batched stepping produce identical token streams and
    finish ticks for the dense and ssm/hybrid families, including across a
    mid-run scale-up, a graceful drain, and a failure evacuation;
  * one jitted decode dispatch per fleet group per tick (4 same-model
    replicas spanning 2 nodes form ONE group);
  * the frontend refuses ``async_tick=False`` (the fleet tick has one mode;
    the per-replica loop is ``fleet_batch=False``);
  * slab membership churn (join mid-generation, unstack on leave);
  * the ``_admit_batch`` overflow fix (over-long prompts truncate instead of
    crashing the token-buffer copy);
  * the int8 KV-cache ``cache_dtype="int8"`` option (greedy parity with
    fp32, smaller pool bytes, rejected for stateful SSM families);
  * the measured service-rate EMA feeding the GPSO planner.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.serving import (ElasticClusterFrontend, FleetGroup,
                           ReplicaEngine, Request)

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
    return {r.rid: (tuple(r.output), r.finish_time) for r in reqs}


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("arch", ["granite-3-8b", "mamba2-1.3b",
                                  "zamba2-2.7b"])
def test_fleet_matches_per_replica_across_churn(arch):
    """Same workload + seeds through fleet-batched and per-replica stepping,
    with a mid-run failure evacuation, a graceful drain (scale-down), and a
    scale-up: token streams and finish ticks must be identical."""
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        reqs = _make_reqs(10)
        for r in reqs:
            fe.submit(r)
        fe.tick(0.0)
        fe.fail_replica(0, 0)            # failure: row dropped, work re-queued
        fe.tick(0.0)
        fe.scale_to(np.array([1, 1]))    # drain: member decodes until empty
        fe.tick(0.0)
        fe.scale_to(np.array([2, 2]))    # scale-up: slab rows grow
        fe.run_until_drained()
        return _snap(reqs), fe

    base, fe_off = run(False)
    fleet, fe_on = run(True)
    assert base == fleet
    assert fe_off.decode_dispatches() == 0
    assert fe_on.decode_dispatches() > 0


def test_one_dispatch_per_group_per_tick(setup):
    """4 same-model replicas across 2 nodes = ONE fleet group = ONE jitted
    decode dispatch per tick — and at most ONE blocking host sync per tick
    (the reconcile of the previous tick's futures), even on ticks that also
    admit."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)

    fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0)
    for r in _make_reqs(16, n_new=8):
        fe.submit(r)
    mtr = fe.tick(0.0)                   # admit everywhere
    assert mtr["syncs"] <= 1             # admissions defer their sync too
    for _ in range(3):                   # saturated steady-state ticks
        mtr = fe.tick(0.0)
        assert mtr["fleet_groups"] == 1
        assert mtr["decode_dispatches"] == 1
        assert mtr["syncs"] == 1         # exactly the one reconcile
    assert len(fe.replicas) == 4


def test_elastic_refuses_the_eager_tick(setup):
    """The fleet tick has one mode: ``async_tick=False`` is refused with a
    pointer to the per-replica loop instead of silently changing the
    tick."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)

    with pytest.raises(ValueError, match="fleet_batch=False"):
        ElasticClusterFrontend(factory, 1, async_tick=False)
    fe = ElasticClusterFrontend(factory, 1, async_tick=True)
    assert len(fe._fleets) == 1


def test_fleet_join_and_leave_mid_generation(setup):
    """A standalone replica with in-flight slots joins a fleet (its cache
    rides into the slab) and later leaves (cache unstacks) without
    perturbing its greedy stream."""
    c, m, params = setup
    oracle = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    other = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    reqs_o = _make_reqs(2, n_new=9)
    reqs_e = _make_reqs(2, n_new=9)
    for a, b in zip(reqs_o, reqs_e):
        oracle.submit(a)
        eng.submit(b)
    for _ in range(3):                       # standalone start
        oracle.step()
        eng.step()
    g = FleetGroup(m, params, max_batch=2, max_seq=MAX_SEQ,
                   cache_dtype=jnp.float32)
    g.add(eng)
    g.add(other)
    assert eng.cache is None and g.cap == 2
    for _ in range(3):                       # fleet middle
        oracle.step()
        eng.begin_step()
        g.decode_round()
        g.reconcile()
    g.remove(eng)                            # unstack and finish standalone
    assert eng.cache is not None and eng._fleet is None
    for _ in range(30):
        oracle.step()
        eng.step()
        if eng.load == 0 and oracle.load == 0:
            break
    # identical prompts + seeds: the churned engine's streams and finish
    # clocks must match the untouched oracle's
    assert [r.output for r in reqs_e] == [r.output for r in reqs_o]
    assert [r.finish_time for r in reqs_e] == [r.finish_time for r in reqs_o]


# ------------------------------------------------- admit overflow truncation
def test_admit_truncates_overlong_prompt(setup):
    """A prompt longer than max_seq used to overflow the prefill token
    buffer; it must now keep its last max_seq-1 tokens and finish."""
    c, m, params = setup
    eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    rng = np.random.default_rng(7)
    long_prompt = rng.integers(1, 400, MAX_SEQ + 37).tolist()
    req = Request(0, long_prompt, max_new_tokens=4)
    eng.submit(req)
    for _ in range(40):
        eng.step()
        if eng.load == 0:
            break
    # finishes (the old code crashed copying into the token buffer); the
    # near-full cache legitimately retires it early via the cache-full rule
    assert req.done and 1 <= len(req.output) <= 4
    # matches running the truncated prompt explicitly
    ref = Request(1, long_prompt[-(MAX_SEQ - 1):], max_new_tokens=4)
    eng2 = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    eng2.submit(ref)
    for _ in range(40):
        eng2.step()
        if eng2.load == 0:
            break
    assert ref.output == req.output


# ------------------------------------------------------------- int8 KV pool
def test_int8_cache_matches_fp32_greedy(setup):
    c, m, params = setup
    prompts = [p.prompt for p in _make_reqs(4, seed=11)]

    def run(dtype):
        eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                            cache_dtype=dtype)
        reqs = [Request(i, list(p), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        for _ in range(60):
            eng.step()
            if eng.load == 0:
                break
        return [r.output for r in reqs]

    assert run("int8") == run(jnp.float32)


def test_int8_cache_capacity_gain(setup):
    """Same byte budget holds ~3.6x the decode slots (int8 payload + f32
    per-(token, head) scales vs f32 payload)."""
    c, m, params = setup

    def nbytes(dtype):
        st = jax.eval_shape(lambda: m.init_serve_state(4, MAX_SEQ, dtype))
        return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(st))

    gain = nbytes(jnp.float32) / nbytes("int8")
    assert gain > 3.0


def test_int8_cache_rejected_for_ssm():
    c = get_config("mamba2-1.3b").reduced()
    m = make_model(c, tp=1)
    with pytest.raises(ValueError, match="int8"):
        m.init_serve_state(2, MAX_SEQ, "int8")


def test_int8_fleet_parity(setup):
    """int8 replicas fleet-batch too (the slab is just a bigger pytree)."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, cache_dtype="int8")

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        reqs = _make_reqs(6, n_new=5)
        for r in reqs:
            fe.submit(r)
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)


# ------------------------------------------------------ decode token writes
def _dequant(slab):
    """Float K/V leaves of a float or int8 slab."""
    if "k_q" in slab:
        return {n: np.asarray(slab[n + "_q"], np.float32)
                * np.asarray(slab[n + "_s"])[..., None] for n in ("k", "v")}
    return {n: np.asarray(slab[n], np.float32) for n in ("k", "v")}


def _changed(before, after):
    """(F, B, S) mask of the cache cells any layer or leaf changed."""
    ch = None
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        d = np.asarray(b) != np.asarray(a)
        d = d.reshape(d.shape[:4] + (-1,)).any(axis=(1, 4))    # (F, B, S)
        ch = d if ch is None else ch | d
    return ch


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_afleet_writes_only_each_steps_token(setup, cache_dtype):
    """Async fleet decode against independent oracles: each step changes
    the slab only at every stepping slot's write position; after the steps
    a slot's K/V at [0, pos) equals a prefill of the same tokens; held
    slots (``afleet_hold``) and rows that do not step (``afleet_masked``)
    stay bit-for-bit as they were."""
    from repro.serving.engine import _init_ops, get_serve_kernels
    c, m, params = setup
    cd = "int8" if cache_dtype == "int8" else jnp.dtype(cache_dtype)
    F, B, n_steps = 2, 2, 4
    kern = get_serve_kernels(m, MAX_SEQ, cd)
    rng = np.random.default_rng(5)
    # the slab holds earlier occupants' state
    slab = jax.tree.map(
        lambda t: jnp.asarray(rng.integers(-100, 100, (F,) + t.shape)
                              if t.dtype == jnp.int8 else
                              rng.uniform(0.1, 2.0, (F,) + t.shape),
                              t.dtype),
        m.init_serve_state(B, MAX_SEQ, cd))
    lens = np.array([5, 7, 3, 6], np.int32)
    toks = np.zeros((F * B, 8), np.int32)
    prompts = [rng.integers(1, 400, n) for n in lens]
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    rows, slots = np.repeat(np.arange(F), B), np.tile(np.arange(B), F)
    first, slab, ops = kern.afleet_prefill(
        params, slab, _init_ops(F, B), jnp.asarray(toks), jnp.asarray(lens),
        jnp.asarray(rows, np.int32), jnp.asarray(slots, np.int32),
        jnp.full((F * B,), 50, jnp.int32), jnp.full((F * B,), -1, jnp.int32))
    seqs = [list(p) + [int(t)] for p, t in zip(prompts, np.asarray(first))]

    def step(fn, *extra):
        nonlocal slab, ops
        before = jax.tree.map(np.asarray, slab)
        pos = np.asarray(ops["pos"])
        nxt, _, stepped, _, slab, ops = fn(params, slab, ops, *extra)
        return before, pos, np.asarray(nxt), np.asarray(stepped)

    at_pos = np.arange(MAX_SEQ)[None, None, :]
    for _ in range(n_steps):
        before, pos, nxt, stepped = step(kern.afleet)
        assert stepped.all()
        np.testing.assert_array_equal(_changed(before, slab),
                                      at_pos == pos[..., None])
        for i, (f, b) in enumerate(zip(rows, slots)):
            seqs[i].append(int(nxt[f, b]))

    pos = np.asarray(ops["pos"])
    got = _dequant(slab)
    for i, (f, b) in enumerate(zip(rows, slots)):
        n = int(pos[f, b])
        assert n == lens[i] + n_steps
        _, ref, _ = m.prefill(params, {"tokens": jnp.asarray([seqs[i][:n]])},
                              cache_len=MAX_SEQ, cache_dtype=cd)
        ref = _dequant(ref)
        for name in ("k", "v"):
            want, have = ref[name][:, 0, :n], got[name][f, :, b, :n]
            err = np.abs(have - want).max() / np.abs(want).max()
            assert err < 0.03, (cache_dtype, name, i, err)

    # held slot (row 0, slot 1): its state is kept bit-for-bit
    hrows, hslots = jnp.asarray([0, F], jnp.int32), jnp.asarray([1, 0],
                                                                jnp.int32)
    before, pos, _, _ = step(kern.afleet_hold, hrows, hslots)
    want = at_pos == pos[..., None]
    want[0, 1] = False
    np.testing.assert_array_equal(_changed(before, slab), want)
    # masked: only fleet row 1 steps; row 0 keeps its state bit-for-bit
    before, pos, _, stepped = step(kern.afleet_masked,
                                   jnp.asarray([False, True]))
    assert not stepped[0].any() and stepped[1].all()
    want = at_pos == pos[..., None]
    want[0] = False
    np.testing.assert_array_equal(_changed(before, slab), want)


def _scan_carry_shapes(jaxpr):
    """Shapes of every ``scan`` carry in ``jaxpr`` and its sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            out += [v.aval.shape for v in eqn.invars[nc:nc + nk]]
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    out += _scan_carry_shapes(sub)
    return out


@pytest.mark.parametrize("kernel", ["afleet", "decode"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_decode_scan_carries_no_cache(setup, kernel, cache_dtype):
    """The decode's layer scan carries the hidden state, never the KV
    cache: a cache carried through the scan is rewritten one layer slice
    per layer, and under the fleet ``vmap`` relaid layer-major in and out
    (the TPU compile test sees that as slab-sized copies)."""
    from repro.serving.engine import _init_ops, get_serve_kernels
    c, m, params = setup
    cd = "int8" if cache_dtype == "int8" else jnp.dtype(cache_dtype)
    F, B = 2, 2
    kern = get_serve_kernels(m, MAX_SEQ, cd)
    state = jax.eval_shape(lambda: m.init_serve_state(B, MAX_SEQ, cd))
    slab = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct((F,) + t.shape, t.dtype), state)
    if kernel == "afleet":
        ops = jax.eval_shape(lambda: _init_ops(F, B))
        jaxpr = jax.make_jaxpr(kern.afleet)(params, slab, ops)
    else:
        jaxpr = jax.make_jaxpr(kern.decode)(
            params, state, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    carries = _scan_carry_shapes(jaxpr.jaxpr)
    assert carries                                   # the layer scan
    cache = {t.shape for t in jax.tree.leaves(state)} | \
        {t.shape for t in jax.tree.leaves(slab)}
    assert not cache & set(carries), carries

# ------------------------------------------------------ measured service rate
def test_service_rate_ema_feeds_gpso_planner(setup):
    """The elastic backend measures per-replica req/tick from finished
    requests; once warm, the control plane hands it to the GPSO planner in
    place of the static unit_capacity constant."""
    from repro.configs.paper_cluster import ClusterConfig
    from repro.control import ControlPlane

    c, m, params = setup
    n_new = 4

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)

    def request_factory(rid, tick):
        return Request(rid, [1 + rid % 50, 2, 3, 4], max_new_tokens=n_new)

    cfg = ClusterConfig(num_nodes=2, horizon=4, forecast_window=8,
                        provisioning_delay=1, max_replicas_per_node=2,
                        min_replicas_per_node=1, scale_interval=3, cooldown=6,
                        straggler_prob=0.0, node_mtbf=1e12)
    fe = ElasticClusterFrontend(factory, 2, initial_replicas=1,
                                provisioning_delay=1, max_replicas_per_node=2,
                                request_factory=request_factory, seed=0,
                                est_tokens=n_new)
    static_cap = 2.0 / n_new
    plane = ControlPlane(cfg, fe, balancer="rr", scaler="gpso",
                         unit_capacity=static_cap, seed=0, init_arrival=1.0)
    assert plane.scaler.unit_capacity == static_cap   # fallback pre-warm-up
    last = None
    for _ in range(20):
        last = plane.step(1.0)
    assert last["service_rate"] is not None and last["service_rate"] > 0
    assert plane.scaler.unit_capacity == pytest.approx(last["service_rate"])
    fe.run_until_drained()


def test_hetero_speed_masked_rounds_parity(setup):
    """Mixed replica speeds run sub-step rounds where only a subset of a
    group steps — the masked fleet kernel must leave non-stepping rows'
    state untouched (an SSM/KV state must never double-step)."""
    c, m, params = setup
    speeds = [0.5, 1.0, 2.0, 1.0]

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, speed=speeds[rid % 4])

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        reqs = _make_reqs(12, n_new=7, seed=9)
        for r in reqs:
            fe.submit(r)
        for _ in range(4):
            fe.tick(0.0)
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)
