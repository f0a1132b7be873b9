"""One-kernel two-pass PLCore tests.

The in-VMEM importance resampler must be BIT-identical to the host
sampler (the kernel-shareable forms in core.sampling restate searchsorted
/ gather / sort as comparison counts and one-hot contractions — exact
arithmetic, not approximations); the fused chain must be one pallas_call
(kernels.ops.dispatch_count) and match the two-dispatch kernel path; ERT
must be invisible for all-alive tiles, keep the coarse color for all-dead
tiles, and match the reference renderer on mixed tiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core import sampling
from repro.core.pipeline import PackedPlcore, render_image_single
from repro.core.plcore import plcore_decls, render_rays
from repro.data import rays as R
from repro.kernels import ops as kops
from repro.models.params import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32")
    scene = R.blob_scene()
    c2w = R.pose_spherical(30.0, -20.0, scene.radius)
    ro, rd = R.camera_rays(c2w, 16, 16, 14.4)
    return cfg, params, ro, rd


# --------------------------------------- kernel-shareable sampling forms ----
def test_importance_det_bitwise_matches_host():
    """Comparison-count searchsorted + one-hot gathers == the
    searchsorted/take_along_axis host path, bit for bit."""
    k = jax.random.PRNGKey(7)
    t_mid = jnp.sort(jax.random.uniform(k, (9, 17)), -1) * 4.0 + 2.0
    w = jax.random.uniform(jax.random.PRNGKey(8), (9, 17))
    a = sampling.importance(t_mid, w, 12, key=None)
    b = sampling.importance_det(t_mid, w, 12)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # degenerate pdf (single hot bin -> duplicate samples) stays exact
    w0 = jnp.zeros((4, 17)).at[:, 8].set(1.0)
    np.testing.assert_array_equal(
        np.asarray(sampling.importance(t_mid[:4], w0, 12, key=None)),
        np.asarray(sampling.importance_det(t_mid[:4], w0, 12)))


def test_merge_sorted_ranks_bitwise_matches_sort():
    """Rank-merge (with in-set and cross-set ties) == jnp.sort merge."""
    k = jax.random.PRNGKey(9)
    # quantize to force duplicates within and across the two sets
    t_a = jnp.sort(jnp.round(jax.random.uniform(k, (6, 10)) * 8) / 8, -1)
    t_b = jnp.sort(jnp.round(
        jax.random.uniform(jax.random.PRNGKey(10), (6, 14)) * 8) / 8, -1)
    np.testing.assert_array_equal(
        np.asarray(sampling.merge_sorted(t_a, t_b)),
        np.asarray(sampling.merge_sorted_ranks(t_a, t_b)))


# --------------------------------------------- one kernel, two passes -------
def test_two_pass_is_one_dispatch(setup):
    """The acceptance assertion: the fused chain issues exactly ONE
    pallas_call where the coarse/fine chain issues two."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    n0 = kops.dispatch_count()
    render_rays(cfg, params, o, d, use_kernel=True)
    assert kops.dispatch_count() - n0 == 2
    n1 = kops.dispatch_count()
    render_rays(cfg, params, o, d, use_kernel=True, fuse_two_pass=True)
    assert kops.dispatch_count() - n1 == 1


def test_kernel_interpret_false_never_falls_back_to_the_interpreter(setup):
    """A config that asks for the compiled kernel fails off the TPU
    instead of quietly running under the interpreter."""
    import dataclasses
    cfg, params, ro, rd = setup
    if jax.devices()[0].platform == "tpu":
        pytest.skip("the compiled kernel runs here")
    o, d = ro.reshape(-1, 3)[:8], rd.reshape(-1, 3)[:8]
    with pytest.raises(Exception, match="(?i)interpret"):
        render_rays(dataclasses.replace(cfg, kernel_interpret=False),
                    params, o, d, use_kernel=True, fuse_two_pass=True)


def test_two_pass_matches_two_dispatch(setup):
    """Same math, one dispatch: the in-VMEM resample chain must track the
    two-dispatch kernel path within fp32 tolerance. The paths run the
    same ops at different tile shapes, so matmul blocking reorders fp32
    sums (~1e-7/op); the importance resampler amplifies that by shifting
    fine sample positions — hence ~1e-3, like the existing cross-path
    image test."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    a = render_rays(cfg, params, o, d, use_kernel=True)
    b = render_rays(cfg, params, o, d, use_kernel=True, fuse_two_pass=True)
    for key in ("rgb", "rgb_coarse", "acc"):
        np.testing.assert_allclose(np.asarray(a[key]), np.asarray(b[key]),
                                   atol=1e-3, err_msg=key)
    # depth integrates t in [near, far] = [2, 6]: scale the tolerance
    np.testing.assert_allclose(np.asarray(a["depth"]),
                               np.asarray(b["depth"]), atol=1e-2)


def test_grid_emulator_matches_pallas_interpret(setup):
    """Off-TPU the interpreter runs the whole batch as ONE tile stepped as
    one block; the chip's shape — a grid of tiles, each walked in small
    ray blocks, down to the one-ray block CONFIG compiles with — must
    reproduce it within fp32 tolerance (the same jaxpr at other matmul
    shapes, so XLA's gemm blocking reorders fp32 sums; the resampler
    amplifies those last-ulp diffs), with and without ERT."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    packed = {n: kops.stack_plcore_weights(cfg, params[n], None)
              for n in ("coarse", "fine")}
    for eps in (0.0, 0.05):
        a = kops.fused_render_two_pass(cfg, packed, o, d, ert_eps=eps)
        for rt, block in ((64, 8), (32, 1)):
            b = kops.fused_render_two_pass(cfg, packed, o, d, ert_eps=eps,
                                           rt=rt, block=block)
            for key in ("rgb", "rgb_coarse", "acc", "acc_coarse"):
                np.testing.assert_allclose(np.asarray(a[key]),
                                           np.asarray(b[key]), atol=1e-3,
                                           err_msg=key)
            np.testing.assert_allclose(np.asarray(a["depth"]),
                                       np.asarray(b["depth"]), atol=1e-2)


def test_two_pass_rejects_sampling_key(setup):
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    with pytest.raises(ValueError, match="deterministic"):
        render_rays(cfg, params, o, d, jax.random.PRNGKey(0),
                    use_kernel=True, fuse_two_pass=True)


def test_two_pass_quantized_matches_two_dispatch(setup):
    """RMCM 9-bit weights dequantize in-register in both kernels."""
    from repro.core import rmcm
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    quant = {"coarse": rmcm.quantize_tree(params["coarse"]),
             "fine": rmcm.quantize_tree(params["fine"])}
    a = render_rays(cfg, params, o, d, quant=quant, use_kernel=True)
    b = render_rays(cfg, params, o, d, quant=quant, use_kernel=True,
                    fuse_two_pass=True)
    np.testing.assert_allclose(np.asarray(a["rgb"]), np.asarray(b["rgb"]),
                               atol=1e-3)


def test_two_pass_image_pipeline_and_pack_once(setup):
    """PackedPlcore(fuse_two_pass) serves through the cached image program
    without re-packing, and matches the two-dispatch kernel image."""
    cfg, params, ro, rd = setup
    n0 = kops.pack_count()
    pp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
    assert kops.pack_count() - n0 == 2          # coarse + fine, at load
    img = pp.render_image(ro, rd, rays_per_batch=64)
    pp.render_image(ro, rd, rays_per_batch=64)
    assert kops.pack_count() - n0 == 2          # renders never re-pack
    ref = render_image_single(cfg, params, ro, rd, use_kernel=True,
                              rays_per_batch=64)
    np.testing.assert_allclose(np.asarray(img), np.asarray(ref), atol=1e-3)


def test_fuse_two_pass_requires_kernel(setup):
    cfg, params, _, _ = setup
    with pytest.raises(ValueError, match="use_kernel"):
        PackedPlcore(cfg, params, fuse_two_pass=True)


# -------------------------------------------------- per-ray ERT skips ----
def test_ert_all_alive_tile_matches_uncompacted(setup):
    """When no ray terminates, ERT must be invisible: the fine pass runs
    for every block, so the render matches the ERT-off render to the
    last-ulp wobble of the lax.cond compilation boundary — at the
    interpreter's one-block tile and at the chip's small blocks."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    # empty the scene: sigma bias way down -> acc ~ 0 -> every ray alive
    thin = jax.tree.map(lambda x: x, params)
    thin["coarse"]["sigma"]["b"] = thin["coarse"]["sigma"]["b"] - 1e3
    packed = {n: kops.stack_plcore_weights(cfg, thin[n], None)
              for n in ("coarse", "fine")}
    for rt, block in ((None, None), (64, 8)):
        base = kops.fused_render_two_pass(cfg, packed, o, d, rt=rt,
                                          block=block)
        a = kops.fused_render_two_pass(cfg, packed, o, d, ert_eps=1e-6,
                                       rt=rt, block=block)
        assert bool(jnp.all(a["acc_coarse"] < 1.0 - 1e-6))
        # identical math, but the fine pass sits behind a lax.cond whose
        # body XLA compiles separately -> last-ulp gemm-blocking wobble
        np.testing.assert_allclose(np.asarray(base["rgb"]),
                                   np.asarray(a["rgb"]), atol=1e-5)


def test_ert_all_dead_tile_keeps_coarse(setup):
    """A wall of density kills every ray in the coarse pass: every fine
    chunk is skipped and the output must be the coarse render, finite."""
    cfg, params, _, _ = setup
    o = jnp.zeros((64, 3)).at[:, 2].set(-4.0)
    d = jnp.tile(jnp.array([[0.0, 0.0, 1.0]]), (64, 1))
    dense = jax.tree.map(lambda x: x, params)
    dense["coarse"]["sigma"]["b"] = dense["coarse"]["sigma"]["b"] + 1e4
    out = render_rays(cfg, dense, o, d, use_kernel=True, fuse_two_pass=True,
                      ert_eps=1e-3)
    assert bool(jnp.all(jnp.isfinite(out["rgb"])))
    np.testing.assert_allclose(np.asarray(out["rgb"]),
                               np.asarray(out["rgb_coarse"]), atol=1e-6)


def test_ert_mixed_tile_matches_reference(setup):
    """Mixed alive/dead tiles: the block skips must reproduce the reference
    renderer (two-dispatch kernel ERT) — alive rays get the full fine
    render, dead rays keep coarse."""
    cfg, params, ro, rd = setup
    o, d = ro.reshape(-1, 3), rd.reshape(-1, 3)
    eps = 0.05
    coarse_only = render_rays(cfg, params, o, d, use_kernel=True,
                              fuse_two_pass=True)
    alive = np.asarray(coarse_only["acc"]) < 1.0 - eps
    assert 0 < alive.sum() < alive.size, "scene must mix alive and dead"
    ref = render_rays(cfg, params, o, d, use_kernel=True, ert_eps=eps)
    got = render_rays(cfg, params, o, d, use_kernel=True, fuse_two_pass=True,
                      ert_eps=eps)
    # same cross-tile-shape tolerances as test_two_pass_matches_two_dispatch
    for key in ("rgb", "rgb_coarse", "acc"):
        np.testing.assert_allclose(np.asarray(ref[key]),
                                   np.asarray(got[key]), atol=1e-3,
                                   err_msg=key)
    np.testing.assert_allclose(np.asarray(ref["depth"]),
                               np.asarray(got["depth"]), atol=1e-2)


# ------------------------------------------------- two-pass VMEM sizing ----
def test_two_pass_ray_tile_accounts_for_both_nets():
    cfg = tiny()
    # same budget: the two-pass kernel pins 2x the weights + bigger
    # scratch, so its tile can never exceed the one-pass tile
    budget = 1 << 21
    tp = kops.pick_ray_tile_two_pass(cfg, vmem_budget_bytes=budget)
    op = kops.pick_ray_tile(cfg, cfg.n_samples, vmem_budget_bytes=budget)
    assert tp <= op
    assert tp >= 8
    # budget flows from the config knob
    from dataclasses import replace
    tight = replace(cfg, kernel_vmem_budget_mb=1.0)
    assert (kops.pick_ray_tile_two_pass(tight)
            == kops.pick_ray_tile_two_pass(cfg, vmem_budget_bytes=1 << 20))


def test_ray_block_divides_tile():
    """The one-pass kernel's inner loop steps by a block that divides the
    tile and is one ray or a multiple of 8 (Mosaic loads 2 or 4 rows at a
    dynamic offset only from arrays at most 128 lanes wide, and its
    (rt, N) sample blocks are wider)."""
    assert kops.pick_ray_block(192) == 1          # CONFIG: 64 + 128
    assert kops.pick_ray_block(64) == 8
    assert kops.pick_ray_block(32) == 16          # tiny(): 16 + 16
    assert kops._ray_block(128, 16) == 16
    assert kops._ray_block(120, 16) == 8
    assert kops._ray_block(8, 64) == 8
    assert kops._ray_block(64, 1024) == 64
    assert kops._ray_block(24, 4) == 1
    for rt in (8, 24, 120, 512):
        for want in (1, 8, 16, 64):
            g = kops._ray_block(rt, want)
            assert rt % g == 0 and (g == 1 or g % 8 == 0)


def test_two_pass_block_rule():
    """The two-pass kernels' block: the largest power of two dividing rt
    whose rays' widest pass fits the row budget, from shapes alone (no
    block of 2 or 4 is ruled out: their per-ray blocks are narrow), and
    the published widths keep 512-ray tiles under the default budget."""
    from dataclasses import replace
    from repro.configs.nerf_icarus import CONFIG, MIPNERF
    assert kops.pick_two_pass_block(CONFIG, 512) == 4       # 4 x 192 rows
    assert kops.pick_two_pass_block(MIPNERF, 512) == 4      # 4 x 128 rows
    assert kops.pick_two_pass_block(tiny(), 512) == 16      # 16 x 32 rows
    assert kops.pick_ray_block(192) == 1                    # one-pass kept
    # the rule reads shapes, never the VMEM budget
    assert kops.pick_two_pass_block(
        replace(CONFIG, kernel_vmem_budget_mb=1.0), 512) == 4
    for cfg in (CONFIG, MIPNERF, tiny()):
        for rt in (8, 24, 120, 512):
            g = kops.pick_two_pass_block(cfg, rt)
            assert rt % g == 0 and g & (g - 1) == 0
    assert kops.pick_two_pass_block(tiny(), 24) == 8
    for cfg in (CONFIG, MIPNERF):
        for quantized in (False, True):
            assert kops.pick_ray_tile_two_pass(cfg,
                                               quantized=quantized) == 512


@pytest.fixture(scope="module")
def cone_setup():
    from repro.configs.nerf_icarus import tiny_mip
    cfg = tiny_mip()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32")
    c2w = R.pose_spherical(30.0, -20.0, 4.0)
    ro, rd = R.camera_rays(c2w, 16, 16, 14.4)
    radii = jnp.asarray(R.pixel_radii(16, 16, 14.4).reshape(-1, 1))
    return cfg, params, ro, rd, radii


# the coarse pass differs by f32 rounding alone; the fine samples move
# with the coarse weights' last bits through the importance resample
BLOCK_TOL = {"rgb_coarse": 1e-6, "acc_coarse": 1e-6, "rgb": 1e-4,
             "acc": 1e-4, "depth": 1e-3}


@pytest.mark.parametrize("block", [2, 4])
@pytest.mark.parametrize("kind", ["nerf", "cone"])
def test_two_pass_blocks_match_one_block(kind, block, setup, cone_setup):
    """Stepping a 16-ray tile in blocks of 2 or 4 rays, as the chip does
    at the published widths, renders what the tile as one block does:
    each ray's rows meet the same contractions, only the rows per
    contraction change."""
    if kind == "nerf":
        cfg, params, ro, rd = setup
        radii, nets = None, ("coarse", "fine")
    else:
        cfg, params, ro, rd, radii = cone_setup
        radii, nets = radii[:32], ("coarse",)
    o, d = ro.reshape(-1, 3)[:32], rd.reshape(-1, 3)[:32]
    packed = {n: kops.stack_plcore_weights(cfg, params[n], None)
              for n in nets}
    whole = kops.fused_render_two_pass(cfg, packed, o, d, rt=16, block=16,
                                       radii=radii)
    got = kops.fused_render_two_pass(cfg, packed, o, d, rt=16, block=block,
                                     radii=radii)
    assert kops._RAY_BLOCK.value == block
    for key, tol in BLOCK_TOL.items():
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(whole[key]), rtol=0,
                                   atol=tol, err_msg=key)
