"""Dry-run machinery units that don't need 512 devices."""
import re

import pytest

from repro.configs.base import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.presets import production_parallel


def test_shape_applicability_matrix():
    """35 runnable cells + 9 documented skips per mesh."""
    runnable = sum(
        1 for a in ARCH_IDS for s in SHAPES.values()
        if shape_applicable(get_config(a), s))
    assert runnable == 35
    skipped = 11 * 4 - runnable
    assert skipped == 9
    # only the sub-quadratic archs keep long_500k
    keep = [a for a in ARCH_IDS
            if shape_applicable(get_config(a), SHAPES["long_500k"])]
    assert sorted(keep) == ["jamba_v01_52b", "rwkv6_3b"]


def test_presets_cover_every_arch():
    for a in ARCH_IDS:
        cfg = get_config(a)
        for kind in ("train", "prefill", "decode"):
            for mp in (False, True):
                par = production_parallel(cfg, multi_pod=mp, kind=kind)
                assert par.tp == 16 and par.dp == 16
                assert par.pods == (2 if mp else 1)
                if cfg.moe and cfg.moe.num_experts > 16:
                    assert par.ep_over_dp
                if mp and kind == "train":
                    assert par.grad_compress


def test_hlo_collective_regex():
    # NOTE: never import repro.launch.dryrun in-process (it forces 512
    # devices before jax init); the census lives in analysis for this reason
    from repro.analysis.hlo_census import hlo_collective_counts
    text = """
      %ag = all-gather(...), %ar-start = all-reduce-start(...)
      %rs = reduce-scatter(...), %cp = collective-permute-start(...)
      %a2a = all-to-all(...)
    """
    counts = hlo_collective_counts(text)
    assert counts["all-gather"] == 1
    assert counts["reduce-scatter"] == 1
    assert counts["collective-permute"] == 1
    assert counts["all-to-all"] == 1


def test_param_count_magnitudes():
    """Analytic param counts land near the archs' nameplate sizes."""
    from repro.models.model import count_params_analytic
    expect = {
        "codeqwen15_7b": (6e9, 9e9),
        "qwen15_110b": (95e9, 125e9),
        "deepseek_v3_671b": (600e9, 720e9),
        "jamba_v01_52b": (45e9, 60e9),
        "rwkv6_3b": (2.2e9, 4.5e9),
        "minicpm_2b": (2e9, 3.6e9),
        "phi4_mini_38b": (3e9, 5e9),
        "musicgen_medium": (1.2e9, 2.4e9),
        "qwen2_vl_72b": (62e9, 82e9),
        "llama4_scout_17b_a16e": (95e9, 120e9),
    }
    for a, (lo, hi) in expect.items():
        n = count_params_analytic(get_config(a))
        assert lo < n < hi, (a, n)


def test_moe_active_params():
    from repro.models.model import count_params_analytic
    cfg = get_config("deepseek_v3_671b")
    total = count_params_analytic(cfg)
    active = count_params_analytic(cfg, active_only=True)
    # DeepSeek-V3: 671B total / 37B active nameplate
    assert 25e9 < active < 50e9, active
    assert active < total / 10
