"""Single-dispatch serving pipeline tests: the seed tile loop is the
oracle — the one-XLA-program path must match it bit-for-bit at fp32 with
deterministic sampling; PackedPlcore must pack weights exactly once per
param set; ERT must only repaint rays the coarse pass proved terminated;
the quantized (RMCM) fused kernel must track the quantized reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore, render_image_single
from repro.core.plcore import (plcore_decls, render_image,
                               render_image_tiled, render_rays)
from repro.data import rays as R
from repro.kernels import ops as kops
from repro.kernels.ref import fused_render_ref
from repro.models.params import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32")
    scene = R.blob_scene()
    c2w = R.pose_spherical(30.0, -20.0, scene.radius)
    ro, rd = R.camera_rays(c2w, 16, 16, 14.4)
    return cfg, params, ro, rd


# ------------------------------------------------- single dispatch ----------
def test_single_dispatch_matches_seed_loop_bitforbit(setup):
    """fp32, deterministic midpoint sampling: the lax.map image program
    must reproduce the seed per-tile host loop exactly."""
    cfg, params, ro, rd = setup
    a = render_image_tiled(cfg, params, ro, rd, rays_per_batch=64)
    b = render_image(cfg, params, ro, rd, rays_per_batch=64)
    assert a.shape == b.shape == (16, 16, 3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_dispatch_batch_size_invariant(setup):
    cfg, params, ro, rd = setup
    a = render_image(cfg, params, ro, rd, rays_per_batch=32)
    b = render_image(cfg, params, ro, rd, rays_per_batch=128)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_single_dispatch_quantized_matches_seed_loop(setup):
    cfg, params, ro, rd = setup
    quant = {"coarse": rmcm.quantize_tree(params["coarse"]),
             "fine": rmcm.quantize_tree(params["fine"])}
    a = render_image_tiled(cfg, params, ro, rd, quant=quant,
                           rays_per_batch=64)
    b = render_image(cfg, params, ro, rd, quant=quant, rays_per_batch=64)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- pack-once caching --------
def test_packed_plcore_packs_once(setup):
    cfg, params, ro, rd = setup
    n0 = kops.pack_count()
    pp = PackedPlcore(cfg, params, use_kernel=True)
    assert kops.pack_count() - n0 == 2          # coarse + fine, at load
    pp.render_image(ro, rd, rays_per_batch=64)
    pp.render_image(ro, rd, rays_per_batch=64)
    pp.render_rays(ro.reshape(-1, 3), rd.reshape(-1, 3))
    assert kops.pack_count() - n0 == 2          # renders never re-pack


def test_packed_kernel_matches_unpacked_kernel_bitforbit(setup):
    """Pre-packing is a pure caching move — same layout, same kernel."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    pp = PackedPlcore(cfg, params, use_kernel=True)
    a = pp.render_rays(o, d)["rgb"]
    b = render_rays(cfg, params, o, d, use_kernel=True)["rgb"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_kernel_matches_xla_path(setup):
    # two-pass tolerance: the kernel's double-angle PEU differs from the
    # direct encoding by ~3e-4, and the importance re-sampling amplifies
    # per-pass deviations by shifting fine sample positions
    cfg, params, ro, rd = setup
    pp = PackedPlcore(cfg, params, use_kernel=True)
    a = pp.render_image(ro, rd, rays_per_batch=64)
    b = render_image(cfg, params, ro, rd, rays_per_batch=64)
    np.testing.assert_allclose(a, b, atol=5e-3)


# ------------------------------------------------- quantized kernel parity --
def test_fused_kernel_quantized_parity_packed():
    """RMCM path: the fused kernel fed a pre-packed layout must match the
    kernels/ref.py oracle on the same quantized weights."""
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(3),
                         "float32")["fine"]
    quant = rmcm.quantize_tree(params)
    packed = kops.stack_plcore_weights(cfg, params, quant)
    k = jax.random.PRNGKey(4)
    rays_o = jnp.zeros((24, 3)).at[:, 2].set(-4.0)
    d = jax.random.normal(k, (24, 3)) * 0.2 + jnp.array([0.0, 0.0, 1.0])
    rays_d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    t = jnp.sort(jax.random.uniform(jax.random.PRNGKey(5), (24, 16)), -1) \
        * 4 + 2
    from repro.core import sampling
    deltas = sampling.deltas_from_t(t)
    rgb_k, aux_k = kops.fused_render(cfg, None, rays_o, rays_d, t, deltas,
                                     packed=packed)
    rgb_r, aux_r = fused_render_ref(cfg, params, rays_o, rays_d, t, deltas,
                                    quant=quant)
    np.testing.assert_allclose(rgb_k, rgb_r, atol=1e-5)
    np.testing.assert_allclose(aux_k["weights"], aux_r["weights"], atol=1e-5)
    np.testing.assert_allclose(aux_k["acc"], aux_r["acc"], atol=1e-5)


# ------------------------------------------------- early ray termination ----
def test_ert_only_touches_terminated_rays(setup):
    """Rays still alive after the coarse pass must render identically;
    terminated rays fall back to the coarse color."""
    from repro.core import sampling
    from repro.core.plcore import _eval_pass
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    eps = 0.05
    exact = render_rays(cfg, params, o, d)
    ert = render_rays(cfg, params, o, d, ert_eps=eps)
    # the termination mask comes from the COARSE pass transmittance
    t_c = sampling.stratified(cfg.near, cfg.far, cfg.n_coarse, o.shape[:-1])
    _, aux_c = _eval_pass(cfg, params["coarse"], None, o, d, t_c, False)
    alive = np.asarray(aux_c["acc"]) < 1.0 - eps
    np.testing.assert_allclose(np.asarray(ert["rgb"])[alive],
                               np.asarray(exact["rgb"])[alive], atol=1e-6)
    dead = ~alive
    if dead.any():
        np.testing.assert_allclose(
            np.asarray(ert["rgb"])[dead],
            np.asarray(exact["rgb_coarse"])[dead], atol=1e-6)


def test_ert_zero_eps_is_exact(setup):
    cfg, params, ro, rd = setup
    a = render_image(cfg, params, ro, rd, rays_per_batch=64, ert_eps=0.0)
    b = render_image_tiled(cfg, params, ro, rd, rays_per_batch=64)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ert_skips_fully_terminated_batch():
    """A wall of huge density terminates every ray in the coarse pass; the
    ERT render must equal the coarse image (fine pass skipped) and stay
    finite."""
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(1), "float32")
    o = jnp.zeros((64, 3)).at[:, 2].set(-4.0)
    d = jnp.tile(jnp.array([[0.0, 0.0, 1.0]]), (64, 1))
    # bias the coarse sigma head so every sample is extremely dense
    dense = jax.tree.map(lambda x: x, params)
    dense["coarse"]["sigma"]["b"] = dense["coarse"]["sigma"]["b"] + 1e4
    out = render_rays(cfg, dense, o, d, ert_eps=1e-3)
    ref = render_rays(cfg, dense, o, d)
    assert bool(jnp.all(jnp.isfinite(out["rgb"])))
    np.testing.assert_allclose(np.asarray(out["rgb"]),
                               np.asarray(ref["rgb_coarse"]), atol=1e-6)


def test_ert_kernel_path_matches_reference_semantics(setup):
    cfg, params, ro, rd = setup
    eps = 0.05
    ref = render_image(cfg, params, ro, rd, rays_per_batch=64, ert_eps=eps)
    pp = PackedPlcore(cfg, params, use_kernel=True, ert_eps=eps)
    kern = pp.render_image(ro, rd, rays_per_batch=64)
    # same cross-path tolerance as above (double-angle PEU + resampling)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref), atol=5e-3)


# ------------------------------------------------- vmem budget knob ---------
def test_vmem_budget_scales_ray_tile():
    cfg = tiny()
    small = kops.pick_ray_tile(cfg, cfg.n_samples,
                               vmem_budget_bytes=1 << 20)
    big = kops.pick_ray_tile(cfg, cfg.n_samples)          # cfg default 64 MB
    assert small <= big
    assert big <= 128
    # budget flows from the config knob
    from dataclasses import replace
    tight = replace(cfg, kernel_vmem_budget_mb=1.0)
    assert kops.pick_ray_tile(tight, tight.n_samples) == small
