"""Config registry + assignment-rule tests."""

import pytest

from repro.configs import registry
from repro.configs.base import SHAPES, supports_shape


def test_all_archs_registered():
    assert len(registry.ARCH_NAMES) == 11


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_config_loads(arch):
    cfg = registry.get(arch)
    assert cfg.name == arch
    assert cfg.param_count() > 0
    assert registry.get_sharding(arch).tp_axis in ("model", "")


def test_param_counts_match_public_figures():
    # total params within 20% of the advertised size class
    expect = {
        "qwen3-4b": 4.4e9, "olmo-1b": 1.2e9, "nemotron-4-15b": 15.6e9,
        "qwen2.5-3b": 3.4e9, "rwkv6-3b": 3.1e9, "qwen2-vl-7b": 7.6e9,
        "kimi-k2-1t-a32b": 1.04e12, "granite-moe-1b-a400m": 1.3e9,
        "zamba2-2.7b": 2.4e9, "whisper-tiny": 6e7, "deepseek-v2-lite": 15.7e9,
    }
    for arch, n in expect.items():
        got = registry.get(arch).param_count()
        assert abs(got - n) / n < 0.2, (arch, got, n)


def test_kimi_active_params():
    cfg = registry.get("kimi-k2-1t-a32b")
    assert 28e9 < cfg.active_param_count() < 36e9  # ~32B active


def test_long_500k_rules():
    # sub-quadratic only
    for arch in registry.ARCH_NAMES:
        cfg = registry.get(arch)
        ok = supports_shape(cfg, SHAPES["long_500k"])
        assert ok == (cfg.family in ("ssm", "hybrid")), arch


def test_cell_count():
    # 11 archs x 4 shapes - 9 skipped long_500k = 35
    assert len(registry.all_cells()) == 35


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_smoke_reduction_preserves_family(arch):
    full = registry.get(arch)
    smoke = registry.get_smoke(arch)
    assert smoke.family == full.family
    assert smoke.param_count() < full.param_count() / 50
    assert (smoke.moe is None) == (full.moe is None)
    assert (smoke.ssm is None) == (full.ssm is None)
    assert (smoke.encoder is None) == (full.encoder is None)
