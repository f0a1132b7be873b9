"""The benchmark's work counts and its table of peaks, on the CPU."""
import dataclasses
import json
from pathlib import Path

import pytest

from bench import flops, roofline

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def arch_of(cfg) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def test_published_widths_match_the_hand_count():
    arch = json.loads((CONFIGS / "nerf-icarus.json").read_text())["nerf"]
    # weights 593,408 + biases 2,436 per network, two FLOP each
    assert flops.net_params(arch) == (593_408, 2_436)
    assert flops.flops_per_sample(arch) == 1_191_688
    assert flops.samples_per_ray(arch) == 64 + 192
    assert flops.flops_per_ray(arch) == 305_072_128


def test_tiny_config_matches_the_hand_count():
    from repro.configs.nerf_icarus import tiny
    arch = arch_of(tiny())
    # 39x64 + 64x64 + 103x64 + 64x64 trunk, 64x1 sigma, 64x64 feat,
    # 85x32 colour, 32x3 rgb; 4x64 + 1 + 64 + 32 + 3 biases
    assert flops.net_params(arch) == (24_256, 356)
    assert flops.flops_per_ray(arch) == 2 * 24_612 * 48


@pytest.mark.parametrize("make", ["CONFIG", "tiny"])
def test_counts_agree_with_the_programs_parameter_declarations(make):
    from repro.configs import nerf_icarus
    from repro.core.plcore import plcore_decls
    from repro.models.params import param_count
    cfg = getattr(nerf_icarus, make)
    cfg = cfg() if callable(cfg) else cfg
    per_net = param_count(plcore_decls(cfg)) // 2
    assert sum(flops.net_params(arch_of(cfg))) == per_net


def test_kernel_bytes_count_weights_once_and_rays_per_call():
    arch = json.loads((CONFIGS / "nerf-icarus.json").read_text())["nerf"]
    heads = 256 + 128 * 3
    f32 = 2 * (593_408 + 2_436) * 4.0
    rmcm = 2 * ((593_408 - heads) * 1.125 + (heads + 2_436) * 4.0)
    assert flops.kernel_bytes(arch, "float32", 0) == f32
    assert flops.kernel_bytes(arch, "rmcm9", 512) == rmcm + 512 * 60


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_shares_are_not_clipped():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.roofline_share(50.0, 1.0, 1.0, peak) == (50.0,
                                                             "compute")
    # work counted too high reads above 100% and says so
    assert roofline.roofline_share(300.0, 1.0, 1.0, peak)[0] == 300.0
    assert roofline.roofline_share(1.0, 40.0, 2.0, peak) == (200.0,
                                                             "memory")
