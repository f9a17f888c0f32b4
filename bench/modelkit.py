"""What every model directory under ``bench/models/`` shares: the seed's
root key and the normal draw its weights come from, the float8 rounding of
the control, and the gap by which a served token's logit lies below the
reference's best.

A model directory is a package that the harness loads by path (see
``harness.load_model``). It provides

* ``make_params(cfg, seed, dtype=...)``: every weight, on the device, in
  the layout and type the program serves;
* ``logits(cfg, seed, tokens, *, lower=False, dtype=...)``: its plain
  float32 reference over right-padded ``tokens``, the weights drawn again
  from ``seed``; ``lower`` is the control (``round_f8`` on every matrix
  product's operands);
* ``weight_bytes``, ``decode_token_flops``, ``decode_token_bytes``,
  ``prefill_flops``, ``prefill_bytes`` and ``model_flops_per_token``: the
  work counts that the per-layer readers take as ``ctx["flops"]``;
* ``program_sizes(cfg)``: ``{ArchConfig attribute: file value}``, which the
  program's configuration has to equal;
* ``reduced_config(cfg, small_arch)``: the file's sizes cut to the
  program's reduced ``ArchConfig``, for tests on the CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from generator import seed_words

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def root_key(seed: int):
    words = seed_words(seed)
    key = jax.random.PRNGKey(words[0])
    for w in words[1:]:
        key = jax.random.fold_in(key, w)
    return key


def normal(key, shape, fan_in, dtype):
    scale = np.float32(1.0 / np.sqrt(fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def round_f8(x):
    """x rounded to float8 e4m3 under a per-tensor absmax scale, back in
    float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@jax.jit
def _gap(ref, picked):
    """How far below the reference's best each picked token's logit lies,
    in units of the reference row's standard deviation."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, picked[..., None], axis=-1)[..., 0]
    return (best - got) / jnp.std(ref, axis=-1)


def served_gaps(logits, cfg: dict, seed: int, tokens: np.ndarray,
                picked: np.ndarray, *, control=False,
                dtype=jnp.bfloat16) -> np.ndarray:
    """Gaps (B, S) at each position of ``tokens`` under a model's reference
    ``logits``: of the ``picked`` tokens (the served stream shifted by one),
    or, with ``control``, of the token that the float8 control puts first
    there."""
    ref = logits(cfg, seed, tokens, dtype=dtype)
    if control:
        low = logits(cfg, seed, tokens, lower=True, dtype=dtype)
        picked = jnp.argmax(low, axis=-1).astype(jnp.int32)
        del low
    out = np.asarray(_gap(ref, jnp.asarray(picked, jnp.int32)))
    del ref
    return out
