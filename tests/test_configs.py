"""Config registry, analytic param counts, padded-dims invariants."""
import math

import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.configs import ARCH_NAMES, SHAPES, applicable_shapes, get_config
from repro.models.dims import padded_dims, q_head_mask

EXPECTED_B = {
    "mistral-nemo-12b": 12, "qwen2.5-14b": 14, "command-r-35b": 35,
    "granite-3-8b": 8, "grok-1-314b": 314,
    "llama4-maverick-400b-a17b": 400, "zamba2-2.7b": 2.7,
    # whisper: 74M nameplate + 17M extended decoder-position table (the
    # assigned prefill_32k cell needs 32k learned positions; DESIGN.md §8)
    "internvl2-2b": 2, "mamba2-1.3b": 1.3, "whisper-base": 0.091,
    # the ten assigned architectures, and granite-4.0-h-small (32B-A9B), the
    # benchmark's hybrid Mamba-2 / attention / MoE configuration
    "granite-4.0-h-small": 32,
}


def test_all_archs_registered():
    assert len(ARCH_NAMES) == 11
    assert set(EXPECTED_B) == set(ARCH_NAMES)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_param_count_matches_nameplate(arch):
    cfg = get_config(arch)
    n = cfg.param_count() / 1e9
    assert n == pytest.approx(EXPECTED_B[arch], rel=0.2), (arch, n)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_active_leq_total(arch):
    cfg = get_config(arch)
    assert cfg.active_param_count() <= cfg.param_count()
    if cfg.uses_moe:
        assert cfg.active_param_count() < 0.5 * cfg.param_count()


def test_applicable_shapes():
    # long_500k only for sub-quadratic archs
    longs = [a for a in ARCH_NAMES
             if SHAPES["long_500k"] in applicable_shapes(get_config(a))]
    assert sorted(longs) == ["mamba2-1.3b", "zamba2-2.7b"]
    # 40 assigned cells; 32 applicable after the directed long_500k skips,
    # and granite-4.0-h-small's three (its attention layers keep a KV cache
    # that grows with the context, so it is not sub-quadratic)
    total = sum(len(applicable_shapes(get_config(a))) for a in ARCH_NAMES)
    assert total == 35


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_padded_dims_tp16(arch):
    cfg = get_config(arch)
    d = padded_dims(cfg, tp=16)
    if cfg.num_heads == 0:
        assert d.n_q == 0
        return
    assert d.n_q % 16 == 0 and d.n_kv % 16 == 0
    assert sum(d.q_real) == cfg.num_heads           # every real head present
    assert d.vocab % 2048 == 0 and d.vocab >= cfg.vocab_size
    assert d.n_q == d.n_kv * d.q_per_group


@given(h_per_kv=st.integers(1, 8), kv=st.sampled_from([1, 2, 4, 8, 16, 32]),
       tp=st.sampled_from([1, 2, 4, 8, 16]))
@settings(max_examples=40, deadline=None)
def test_padded_dims_properties(h_per_kv, kv, tp):
    """For any GQA geometry where kv and tp are compatible, padding preserves
    the real-head count and produces tp-divisible physical heads."""
    if kv >= tp and kv % tp != 0:
        return
    if kv < tp and tp % kv != 0:
        return
    import dataclasses

    from repro.configs.base import ArchConfig
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=128,
                     num_heads=h_per_kv * kv, num_kv_heads=kv, d_ff=256,
                     vocab_size=1000, head_dim=32)
    d = padded_dims(cfg, tp=tp)
    assert d.n_q % tp == 0
    assert d.n_kv % tp == 0
    assert sum(d.q_real) == cfg.num_heads
    assert 0 < d.pad_flops_ratio <= 1.0
    mask = q_head_mask(d)
    assert mask.sum() == cfg.num_heads
