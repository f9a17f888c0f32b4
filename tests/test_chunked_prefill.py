"""Chunked prefill + fleet-batched admission: the serving admission path.

Acceptance coverage:

  * chunked-vs-single-shot prefill parity for dense / ssm / hybrid, with
    prompt lengths straddling chunk boundaries (C-1, C, C+1, multiples) and
    the ``max_seq - 1`` truncation edge;
  * decode interleaving — a mid-chunk slot is held out of decode (``hold``
    mask) so a concurrent short request's stream and finish ticks are
    untouched by a long prompt streaming in;
  * fleet-batched prefill parity (one vmapped dispatch per distinct bucket
    shape vs per-replica admission) and the ``prefill_dispatches`` metric
    bound: dispatches per tick <= distinct (bucket_batch, bucket_len)
    shapes, not O(replicas);
  * chunked admission inside a fleet across churn (failure, drain,
    scale-up);
  * moe replicas default to the exact-length single-admit path (bucketed
    padding changes expert-capacity drops);
  * the deduped retrace accounting counts the fleet/chunk prefill kernel
    variants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.serving import (ElasticClusterFrontend, ReplicaEngine, Request,
                           total_prefill_traces, total_serve_traces)

MAX_SEQ = 64
CHUNK = 8


@pytest.fixture(scope="module")
def setup():
    c = get_config("granite-3-8b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    return c, m, params


def _reqs(lens, n_new=5, seed=5, vocab=400):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, vocab, L).tolist(),
                    max_new_tokens=n_new) for i, L in enumerate(lens)]


def _snap(reqs):
    return {r.rid: (tuple(r.output), r.finish_time) for r in reqs}


# ------------------------------------------------- chunked vs single-shot
@pytest.mark.parametrize("arch", ["granite-3-8b", "mamba2-1.3b",
                                  "zamba2-2.7b"])
def test_chunked_matches_single_shot(arch):
    """Prompt lengths straddling chunk boundaries (C±1, multiples) and the
    max_seq-1 truncation edge: token streams must match single-shot
    prefill."""
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    lens = [CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 1, 30,
            MAX_SEQ + 13]          # last one truncates to max_seq-1

    def run(chunk_len):
        eng = ReplicaEngine(m, params, max_batch=4, max_seq=MAX_SEQ,
                            chunk_len=chunk_len)
        reqs = _reqs(lens)
        for r in reqs:
            eng.submit(r)
        for _ in range(200):
            eng.step()
            if eng.load == 0:
                break
        assert eng.load == 0
        return [r.output for r in reqs]

    assert run(CHUNK) == run(0)


def test_chunking_does_not_perturb_concurrent_decode(setup):
    """While a long prompt streams in chunks, a short request sharing the
    engine decodes every tick with its state untouched (the hold mask):
    stream AND finish tick match a solo run."""
    c, m, params = setup
    rng = np.random.default_rng(11)
    long_prompt = rng.integers(1, 400, 40).tolist()
    short_prompt = rng.integers(1, 400, 4).tolist()

    def run(with_long):
        eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                            chunk_len=CHUNK)
        short = Request(0, list(short_prompt), max_new_tokens=8)
        eng.submit(short)
        if with_long:
            eng.submit(Request(1, list(long_prompt), max_new_tokens=4))
        for _ in range(60):
            eng.step()
            if eng.load == 0:
                break
        assert eng.load == 0
        return short.output, short.finish_time

    assert run(True) == run(False)


def test_chunked_ttft_spreads_over_ticks(setup):
    """A chunked long prompt produces its first token after ceil(len/C)
    engine steps — admission work is spread instead of front-loaded."""
    c, m, params = setup
    plen = 3 * CHUNK + 2           # 4 chunks
    req = Request(0, np.random.default_rng(0).integers(
        1, 400, plen).tolist(), max_new_tokens=3)
    eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                        chunk_len=CHUNK)
    eng.submit(req)
    for _ in range(30):
        eng.step()
        if eng.load == 0:
            break
    assert req.done
    assert req.first_token_time == pytest.approx(4.0)   # ceil(26/8) ticks


# ------------------------------------------------- fleet-batched admission
def test_fleet_prefill_parity_and_dispatch_bound(setup):
    """4 same-model replicas across 2 nodes: same-bucket admits collapse to
    one fleet prefill dispatch per distinct (kb, sb) shape — never one per
    replica — with streams and finish ticks identical to the per-replica
    loop."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        # equal lengths -> one (kb, sb) shape once every replica admits a
        # full pair
        reqs = _reqs([6] * 8, n_new=4, seed=2)
        for r in reqs:
            fe.submit(r)
        mtr = fe.tick(0.0)
        fe.run_until_drained()
        return _snap(reqs), mtr, fe

    s_on, m_on, fe_on = run(True)
    s_off, m_off, fe_off = run(False)
    assert s_on == s_off
    # admission tick: <= 2 distinct shapes (kb in {1,2} x one sb bucket);
    # the per-replica loop pays one dispatch per admitting replica
    assert 1 <= m_on["prefill_dispatches"] <= 2
    assert m_off["prefill_dispatches"] == 4
    assert fe_on.prefill_dispatches() < fe_off.prefill_dispatches()


def test_fleet_chunked_parity_across_churn():
    """Chunked admission inside a fleet survives failure, drain and
    scale-up with streams + finish ticks identical to the per-replica
    path (hybrid: carried ssm/conv state AND offset KV writes)."""
    c = get_config("zamba2-2.7b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, chunk_len=CHUNK)

    def run(fleet):
        fe = ElasticClusterFrontend(factory, 2, initial_replicas=2, seed=0,
                                    fleet_batch=fleet)
        rng = np.random.default_rng(9)
        reqs = [Request(i, rng.integers(1, 400,
                                        int(rng.integers(3, 40))).tolist(),
                        max_new_tokens=6) for i in range(10)]
        for r in reqs:
            fe.submit(r)
        fe.tick(0.0)
        fe.fail_replica(0, 0)
        fe.tick(0.0)
        fe.scale_to(np.array([1, 1]))
        fe.tick(0.0)
        fe.scale_to(np.array([2, 2]))
        fe.run_until_drained()
        return _snap(reqs)

    assert run(True) == run(False)


# --------------------------------------------------------- moe exactness
def test_moe_defaults_to_exact_length_admission():
    """MoE replicas skip the bucketed path by default: expert capacity
    scales with the padded bucket, so padded prefill can drop different
    tokens than the per-prompt oracle. Exact-length single admits match the
    full-forward greedy oracle."""
    c = get_config("grok-1-314b").reduced()
    m = make_model(c, tp=1)
    params = m.init(jax.random.PRNGKey(0), jnp.float32)
    eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ)
    assert not eng.bucket_prompts          # moe -> exact-length by default
    assert eng.chunk_len == 0              # and no chunked admission
    rng = np.random.default_rng(4)
    reqs = [Request(i, rng.integers(1, 400, 5 + i).tolist(),
                    max_new_tokens=4) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(40):
        eng.step()
        if eng.load == 0:
            break
    assert eng.load == 0
    # first-token parity with the full-forward oracle: exact-length prefill
    # runs the same shapes as the oracle, so capacity drops match. (Later
    # decode tokens are inherently incomparable for moe — the oracle
    # recomputes the whole sequence so its capacity grows with it, while
    # decode routes one token at a time.)
    for r in reqs:
        logits, _ = m.forward(
            params, {"tokens": jnp.asarray([r.prompt], jnp.int32)})
        assert r.output[0] == int(jnp.argmax(logits[0, -1]))


# ----------------------------------------------------- trace accounting
def test_trace_accounting_counts_fleet_and_chunk_variants(setup):
    """total_prefill_traces must include the fleet prefill / chunk kernel
    compilations (deduped via the shared kernel object), and the full serve
    accounting also covers the decode variants."""
    c, m, params = setup

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid, chunk_len=CHUNK)

    fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0)
    for r in _reqs([6, 6, 20, 20], n_new=4, seed=7):
        fe.submit(r)
    fe.run_until_drained()
    engines = fe.replicas
    counts = engines[0]._kernels.trace_counts

    def n(*variants):
        return sum(counts.get(v, 0) for v in variants)

    assert n("afleet_prefill") >= 1
    assert n("afleet_chunk") >= 1
    assert fe.prefill_retraces() == total_prefill_traces(engines)
    assert total_prefill_traces(engines) >= \
        n("afleet_prefill", "afleet_chunk")
    # the all-variant accounting additionally covers decode kernels
    assert total_serve_traces(engines) >= \
        total_prefill_traces(engines) + n("afleet")
    assert fe.serve_kernel_traces() == total_serve_traces(engines)
