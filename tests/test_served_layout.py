"""The q/k/v projections served in (heads, head_dim, d_model) order.

Weights arrive in the published (d_model, heads, head_dim) order; the
serving engine converts each weights object once (``served_weights``) and
every replica and fleet group built from that object serves the one
converted tree. The TPU compile test checks what the order buys (no
per-layer weight copy); these check that it changes no token and that the
conversion happens once.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.models.attention import SERVED_QKV
from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request
from repro.serving.engine import served_weights

MAX_SEQ = 64


def _model(arch):
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    return c, m, m.init(jax.random.PRNGKey(0), jnp.float32)


def _reqs(n, n_new=6, seed=3, vocab=400):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, vocab, rng.integers(3, 9)).tolist(),
                    max_new_tokens=n_new) for i in range(n)]


def test_served_tree_holds_qkv_heads_first():
    """Each attention's q/k/v leaves become (heads, head_dim, d_model),
    ``wq``'s heads split by kv group, under their served names; the
    published ones go, every other leaf is the very object it was, and
    converting again changes nothing."""
    c, m, params = _model("granite-3-8b")
    served = served_weights(m, params)
    pub, srv = params["layers"]["attn"], served["layers"]["attn"]
    G = m.dims.n_kv
    for name, served_name in SERVED_QKV.items():
        assert name not in srv
        L, d, n, h = pub[name].shape
        heads = (G, n // G) if name == "wq" else (n,)
        assert srv[served_name].shape == (L,) + heads + (h, d)
        np.testing.assert_array_equal(
            srv[served_name].reshape(L, n, h, d),
            np.moveaxis(np.asarray(pub[name]), 1, 3))
    assert srv["wo"] is pub["wo"]
    assert served["embed"] is params["embed"]
    assert served["layers"]["mlp"]["w_gate"] is params["layers"]["mlp"][
        "w_gate"]
    assert served_weights(m, params) is served
    assert served_weights(m, served) is served


def test_served_tree_goes_with_its_weights():
    """The model keeps a served tree only while its published weights
    live, so a later tree that reuses their id is converted afresh."""
    import gc

    c, m, params = _model("granite-3-8b")
    served_weights(m, params)
    assert len(m._served_weights) == 1
    del params
    gc.collect()
    assert not m._served_weights


@pytest.mark.parametrize("arch", ["granite-3-8b", "granite-4.0-h-small"])
def test_served_tokens_match_forward_on_published_tree(arch):
    """Prefill and decode through the fleet tick, which serves the
    converted tree, give each request the greedy tokens of the full
    forward on the published tree."""
    c, m, params = _model(arch)

    def factory(rid):
        return ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                             rid=rid)

    fe = ElasticClusterFrontend(factory, 1, initial_replicas=2, seed=0)
    reqs = _reqs(4)
    for r in reqs:
        fe.submit(r)
    fe.run_until_drained()
    names = {p[-1].key for p, _ in
             jax.tree_util.tree_leaves_with_path(fe.replicas[0].params)}
    assert set(SERVED_QKV.values()) <= names
    assert not names & set(SERVED_QKV)

    fwd = jax.jit(lambda p, t: m.forward(p, {"tokens": t})[0])
    for r in reqs:
        toks = list(r.prompt)
        for _ in range(r.max_new_tokens):
            logits = fwd(params, jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1, :c.vocab_size])))
        assert r.output == toks[len(r.prompt):], r.rid


def test_replicas_share_one_served_tree_and_group():
    """Every replica built from one weights object, the one added at
    scale-up too, serves the same converted tree, so all of them decode
    in one fleet group; no second tree is built."""
    c, m, params = _model("granite-3-8b")
    built = []

    def factory(rid):
        eng = ReplicaEngine(m, params, max_batch=2, max_seq=MAX_SEQ, rid=rid)
        built.append(eng)
        return eng

    fe = ElasticClusterFrontend(factory, 2, initial_replicas=1, seed=0)
    for r in _reqs(6):
        fe.submit(r)
    fe.tick(0.0)
    fe.scale_to(np.array([2, 2]))            # scale-up: two more replicas
    fe.run_until_drained()
    assert len(built) == 4
    served = built[0].params
    assert "wq_served" in served["layers"]["attn"]
    assert all(e.params is served for e in built)
    assert len({e.fleet_key for e in built}) == 1
    groups = list(fe._fleets.values())
    assert len(groups) == 1 and groups[0].params is served
    assert len(groups[0].members) == 4


@pytest.mark.parametrize("mode,d_axis",
                         [("serve", None), ("train", ("data",))])
def test_served_leaves_put_heads_on_tp(mode, d_axis):
    """The served leaves shard their kv-head axis over ``model`` as the
    published ones shard their heads, so the tensor-parallel serving path
    takes them; stacked layer axes replicate."""
    from repro.distributed.sharding import ShardPlan, param_pspec
    from repro.launch.mesh import make_mesh

    plan = ShardPlan(make_mesh((1, 1), ("data", "model")), mode)
    c, m, params = _model("granite-3-8b")
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            served_weights(m, params)):
        if path[-1].key in SERVED_QKV.values():
            inner = (None,) * (leaf.ndim - 3)       # layers; wq's qpg
            assert param_pspec(plan, path, leaf) == jax.sharding.PartitionSpec(
                *inner[:1], "model", *inner[1:], None, d_axis), path
