"""Causality property: for every autoregressive family, logits at position t
must not depend on tokens after t (catches mask/offset bugs in attention,
SSD scan, chunked attention and the hybrid shared block)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import make_model

DECODER_ARCHS = [a for a in ARCH_NAMES
                 if get_config(a).family in ("dense", "moe", "ssm", "hybrid",
                                             "hybrid_moe")]


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_future_tokens_do_not_affect_past_logits(arch, key):
    c = get_config(arch).reduced()
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    B, S, t = 2, 48, 20
    toks = jax.random.randint(key, (B, S), 0, c.vocab_size)
    toks2 = toks.at[:, t + 1:].set(
        jax.random.randint(jax.random.PRNGKey(9), (B, S - t - 1), 0,
                           c.vocab_size))
    l1, _ = m.forward(params, {"tokens": toks})
    l2, _ = m.forward(params, {"tokens": toks2})
    np.testing.assert_allclose(np.asarray(l1[:, :t + 1]),
                               np.asarray(l2[:, :t + 1]), atol=1e-5,
                               rtol=1e-5)
    # sanity: the future positions DO change
    assert float(np.max(np.abs(np.asarray(l1[:, t + 1:])
                               - np.asarray(l2[:, t + 1:])))) > 1e-4


def test_vlm_text_does_not_affect_patch_positions(key):
    c = get_config("internvl2-2b").reduced()
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    B, S = 2, 16
    patches = jax.random.normal(key, (B, c.num_patches, c.d_model)) * 0.1
    t1 = jax.random.randint(key, (B, S), 0, c.vocab_size)
    t2 = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, c.vocab_size)
    l1, _ = m.forward(params, {"tokens": t1, "patch_embeds": patches})
    l2, _ = m.forward(params, {"tokens": t2, "patch_embeds": patches})
    P = c.num_patches
    np.testing.assert_allclose(np.asarray(l1[:, :P]), np.asarray(l2[:, :P]),
                               atol=1e-5, rtol=1e-5)


def test_whisper_decoder_causal_encoder_bidir(key):
    c = get_config("whisper-base").reduced()
    m = make_model(c, tp=1)
    params = m.init(key, jnp.float32)
    B, S, t = 2, 24, 10
    frames = jax.random.normal(key, (B, c.encoder_seq_len, c.d_model)) * 0.1
    toks = jax.random.randint(key, (B, S), 0, c.vocab_size)
    toks2 = toks.at[:, t + 1:].set(0)
    l1, _ = m.forward(params, {"tokens": toks, "frame_embeds": frames})
    l2, _ = m.forward(params, {"tokens": toks2, "frame_embeds": frames})
    np.testing.assert_allclose(np.asarray(l1[:, :t + 1]),
                               np.asarray(l2[:, :t + 1]), atol=1e-5,
                               rtol=1e-5)
    # encoder frames affect ALL decoder positions (cross-attn is global).
    # NB: a CONSTANT shift sits in LayerNorm's null space — perturb with
    # noise, not a constant (that was a real test-design lesson).
    frames2 = frames + 0.05 * jax.random.normal(jax.random.PRNGKey(5),
                                                frames.shape)
    l3, _ = m.forward(params, {"tokens": toks, "frame_embeds": frames2})
    assert float(np.max(np.abs(np.asarray(l1) - np.asarray(l3)))) > 1e-5
