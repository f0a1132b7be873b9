"""The Mip-NeRF cell on the CPU at a tiny size: its plain reference
against the program's own reference and the served program (the
comparison that decides ``correct`` on the chip, in small), its work
counts, its traffic, and its readers on a run with nothing to read."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, loadgen, mip_flops

CONFIG = Path(__file__).resolve().parents[1] / "configs" / \
    "mipnerf-blender.json"
CELL = "mipnerf.multiscale"
TINY = dict(trunk_layers=4, trunk_width=64, skip_at=[2], color_width=32,
            pos_freqs=6, dir_freqs=3, n_coarse=16, n_fine=16,
            image_hw=[24, 24], kernel_interpret=None)
# the program's mean gap at this size is ~2e-7 (f32 on the CPU); the
# control's is above 5e-4 on these seeds
TINY_LIMITS = {"mean_abs_err": 1e-4, "share_over_1e-3": 0.05}


def tiny_cell() -> harness.Cell:
    cell = harness.load_cell(CELL)
    cell.config["nerf"].update(TINY)
    cell.traffic.update(hw=[32, 16, 8, 4])
    cell.limits = dict(TINY_LIMITS)
    return cell


def reference():
    return harness.reference_module(json.loads(CONFIG.read_text()))


def test_reference_agrees_with_the_programs_reference():
    from repro.kernels.ref import mipnerf_render_ref
    ref = reference()
    cell = tiny_cell()
    arch = cell.config["nerf"]
    w = harness.scene_weights(ref, arch, 2 ** 34 + 1, 0)
    assert w["coarse"] is w["fine"]
    px = np.arange(0, 24 * 24, 5)
    o, d4 = ref.camera_rays(30.0, -30.0, 4.0, 24, px)
    want = ref.render(arch, w, o, d4, "highest", block=64)
    cfg = harness.nerf_config(cell.config)
    params = {"coarse": harness.program_params(w["coarse"],
                                               arch["trunk_layers"])}
    got = mipnerf_render_ref(cfg, params, jnp.asarray(o),
                             jnp.asarray(d4[:, :3]), jnp.asarray(d4[:, 3:]))
    np.testing.assert_allclose(np.asarray(got["rgb"]), want, atol=2e-6)
    # the radius column is the program's own footprint
    from repro.data import rays as R
    np.testing.assert_allclose(d4[:, 3], R.pixel_radii(24, 24, 21.6)[px],
                               rtol=1e-6)


def run(cell, seed=2 ** 33 + 3):
    return harness.run_cell(cell, seed, 2.0, False, jax.devices(),
                            time.perf_counter(), grace_s=20.0)


def test_a_sound_run_is_correct():
    out = run(tiny_cell())
    assert out["correct"], out["checks"]
    assert out["checks"]["mean_abs_err"]["value"] < 1e-5
    assert out["metrics"]["rays_per_s"]["value"] > 0


def test_a_broken_footprint_is_not_correct(monkeypatch):
    """Every ray served with a zero radius (a pinhole ray): the
    comparison sees the lost blur."""
    from repro.core.pipeline import PackedPlcore
    produce = PackedPlcore.dispatch_tile

    def pinhole(self, o, d, **kw):
        kw["radii"] = jnp.zeros_like(kw["radii"])
        return produce(self, o, d, **kw)

    monkeypatch.setattr(PackedPlcore, "dispatch_tile", pinhole)
    out = run(tiny_cell())
    assert not out["correct"]


@pytest.mark.parametrize("seed", [5, 2 ** 35 + 1])
def test_the_control_is_not_correct(seed):
    cell = tiny_cell()
    cell.traffic.update(in_flight=4)        # enough pixels to compare
    c, _ = control.reading(cell, seed, "bfloat16")
    assert c["pixels_checked"]["value"] >= c["pixels_checked"]["at_least"]
    assert not harness.passed(c), c


def test_published_widths_match_the_hand_count():
    arch = json.loads(CONFIG.read_text())["nerf"]
    # 96x256 + 6 x 256x256 + 352x256 trunk, 256x1 sigma, 256x256 feat,
    # 283x128 colour, 128x3 rgb; 8x256 + 1 + 256 + 128 + 3 biases
    assert mip_flops.net_params(arch) == (610_304, 2_436)
    assert mip_flops.flops_per_sample(arch) == 1_225_480
    assert mip_flops.samples_per_ray(arch) == 128 + 128
    assert mip_flops.flops_per_ray(arch) == 313_722_880
    assert mip_flops.kernel_bytes(arch, 512) == \
        612_740 * 4.0 + 512 * 16 * 4.0


def test_counts_agree_with_the_programs_parameter_declarations():
    from repro.configs.nerf_icarus import MIPNERF, tiny_mip
    from repro.core.plcore import plcore_decls
    from repro.models.params import param_count
    for cfg in (MIPNERF, tiny_mip()):
        arch = {k: getattr(cfg, k) for k in (
            "trunk_layers", "trunk_width", "skip_at", "color_width",
            "pos_freqs", "dir_freqs")}
        assert sum(mip_flops.net_params(arch)) == \
            param_count(plcore_decls(cfg))


def test_multiscale_classes_are_the_four_sizes():
    cell = harness.load_cell(CELL)
    assert [c["hw"] for c in loadgen.classes(cell.traffic)] == \
        [800, 400, 200, 100]
    specs = loadgen.requests(cell.traffic, 2 ** 40 + 7)
    first = [next(specs).hw for _ in range(8)]
    assert sorted(first) == [100, 100, 200, 200, 400, 400, 800, 800]
    assert cell.chips == 1 and cell.traffic["in_flight"] == 2


@pytest.mark.parametrize("metric", ["cone_plcore_roofline.multiscale",
                                    "mfu.multiscale",
                                    "footprint_share.multiscale"])
def test_readers_read_nothing_as_none(metric):
    """A run with nothing to read (no trace, no spans, no rays, or a
    trace without the cone kernel) reads None, never 0."""
    read = harness.metric_reader(metric)
    empty = SimpleNamespace(
        trace=None, peak={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9},
        stats={"rays_rendered": 0}, spans=[], chips=1, tile_rays=512,
        arch=json.loads(CONFIG.read_text())["nerf"],
        window=SimpleNamespace(seconds=30.0))
    assert read(empty) is None
    nerf_trace = SimpleNamespace(
        kernel_calls={0: 10}, kernel_s={0: 0.1},
        device_ops=[("%plcore_two_pass.1 custom-call", 0.1)])
    other = SimpleNamespace(**{**vars(empty), "trace": nerf_trace,
                               "spans": [("engine.submit", 0.0, 1.0)]})
    if metric != "mfu.multiscale":
        assert read(other) is None


def test_readers_read_a_cone_run():
    arch = json.loads(CONFIG.read_text())["nerf"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = SimpleNamespace(
        kernel_calls={0: 100}, kernel_s={0: 1.0},
        device_ops=[("%plcore_two_pass_cone.1 custom-call", 1.0)])
    run = SimpleNamespace(
        trace=trace, peak=peak, stats={"rays_rendered": 51_200},
        spans=[("request.footprint", 0.0, 0.002),
               ("request.footprint", 1.0, 1.004)],
        chips=1, tile_rays=512, arch=arch,
        window=SimpleNamespace(seconds=1.0))
    roof = harness.metric_reader("cone_plcore_roofline.multiscale")(run)
    assert roof == pytest.approx(
        100.0 * 100 * 512 * 313_722_880 / 197e12)
    mfu = harness.metric_reader("mfu.multiscale")(run)
    assert mfu == pytest.approx(100.0 * 51_200 * 313_722_880 / 197e12)
    assert harness.metric_reader(
        "footprint_share.multiscale")(run) == pytest.approx(0.6)
