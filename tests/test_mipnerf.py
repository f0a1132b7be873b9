"""Mip-NeRF (``MIPNERF``'s tiny sibling) on the served path, on the CPU.

The program — the engine over the fused cone kernel (interpreted), the
XLA path and the retry ladder's oracle — against the plain reference
``kernels.ref.mipnerf_render_ref`` on seeded random weights; the pieces
the kernel shares with the XLA path (the frustum Gaussian, the IPE in
the PEU's form, the mask-form resample) against independent forms; one
packed network when the passes share it; and every mode the cone path
lacks refusing a cone scene by name.

Tolerances: the program and the reference are both f32 at HIGHEST
matmul precision on the CPU, so they differ by rounding alone: the
pixels by ~2e-7 at this size, held to 2e-6. The IPE's recurrence takes
up to 12 double-angle steps from the first block's sin/cos, each
doubling its error of about 1.2e-7: 2^12 x 1.2e-7 ~ 5e-4 where the
attenuation leaves an octave whole (measured: 1.8e-4 at the footprints
of 800 px frames).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nerf_icarus import (CONFIG, MIPNERF, NerfConfig, tiny,
                                       tiny_mip)
from repro.core import encoding, sampling
from repro.core.pipeline import PackedPlcore, build_scene_aux
from repro.core.plcore import plcore_decls, render_rays
from repro.data import rays as R
from repro.kernels import ops as kops
from repro.kernels.ref import mipnerf_render_ref
from repro.models.params import init_params
from repro.serving import RenderEngine, RenderRequest, SceneCache
from repro.serving.scene_cache import plcore_nbytes

PIXEL_TOL = 2e-6
TILE = 32


@pytest.fixture(scope="module")
def scene():
    cfg = tiny_mip()
    return cfg, init_params(plcore_decls(cfg), jax.random.PRNGKey(3))


def frame_rays(req):
    c2w = R.pose_spherical(req.theta, req.phi, req.radius)
    o, d = R.camera_rays(c2w, req.hw, req.hw, 0.9 * req.hw)
    r = R.pixel_radii(req.hw, req.hw, 0.9 * req.hw).reshape(-1, 1)
    return o.reshape(-1, 3), d.reshape(-1, 3), jnp.asarray(r)


# 9 x 9 = 81 rays: the second frame's rays join the first's last tile
FRAMES = [RenderRequest("s", hw=9, theta=20.0, phi=-30.0),
          RenderRequest("s", hw=6, theta=200.0, phi=-20.0)]


@pytest.fixture(scope="module")
def served(scene):
    """Both frames through RenderEngine.submit/step at 32-ray tiles."""
    cfg, params = scene
    cache = SceneCache(lambda sid: PackedPlcore(
        cfg, params, use_kernel=True, fuse_two_pass=True))
    cache.get("s")
    engine = RenderEngine(cache, tile_rays=TILE)
    ids = [engine.submit(r) for r in FRAMES]
    engine.drain()
    return [engine.take(i).image.reshape(-1, 3) for i in ids], cache


def reference(cfg, params, req):
    return np.asarray(mipnerf_render_ref(cfg, params, *frame_rays(req))
                      ["rgb"])


def test_a_tile_mixes_two_frame_sizes():
    n0 = FRAMES[0].hw ** 2
    assert n0 % TILE and n0 // TILE + 1 < -(-(n0 + FRAMES[1].hw ** 2)
                                             // TILE)


@pytest.mark.parametrize("path", ["engine", "xla", "oracle"])
@pytest.mark.parametrize("frame", [0, 1])
def test_program_matches_the_plain_reference(path, frame, scene, served):
    cfg, params = scene
    req = FRAMES[frame]
    o, d, r = frame_rays(req)
    if path == "engine":
        got = served[0][frame]
    elif path == "xla":
        got = render_rays(cfg, params, o, d, radii=r)["rgb"]
    else:
        pp = served[1].get("s")
        got = pp.render_tile_oracle(o, d, radii=r)
    want = reference(cfg, params, req)
    assert np.isfinite(want).all() and want.std() > 1e-3
    np.testing.assert_allclose(np.asarray(got), want, atol=PIXEL_TOL,
                               rtol=0)


def test_engine_computes_the_footprint_at_intake(scene, served):
    """A resident cone scene's request gets its radii inside
    engine.submit (request.footprint); one whose scene loads later gets
    them when its first tile is coalesced. Both render alike."""
    from repro.obs.trace import SpanTracer
    cfg, params = scene
    for resident in (True, False):
        cache = SceneCache(lambda sid: PackedPlcore(
            cfg, params, use_kernel=True, fuse_two_pass=True))
        if resident:
            cache.get("s")
        tr = SpanTracer(capacity=4096)
        engine = RenderEngine(cache, tile_rays=TILE, tracer=tr)
        rid = engine.submit(FRAMES[1])
        spans = {s.name: s for s in tr.spans()}
        assert ("request.footprint" in spans) is resident
        if resident:
            sub = spans["engine.submit"]
            assert sub.t0 <= spans["request.footprint"].t0 <= sub.t1
        engine.drain()
        assert "request.footprint" in {s.name for s in tr.spans()}
        np.testing.assert_array_equal(
            engine.take(rid).image.reshape(-1, 3), served[0][1])


def test_pixel_radii_are_mipnerfs_half_way_radius():
    """Distance to the row below's unit direction times 2 / sqrt(12)."""
    H, W, f = 5, 7, 6.3
    r = R.pixel_radii(H, W, f).reshape(H, W)
    i, j = 3, 2
    def unit(jj):
        v = np.array([(i + 0.5 - W / 2) / f, -(jj + 0.5 - H / 2) / f, -1.0])
        return v / np.linalg.norm(v)
    want = np.linalg.norm(unit(j) - unit(j + 1)) * 2 / math.sqrt(12)
    assert r[j, i] == pytest.approx(want, rel=1e-6)
    # a rotation of the camera keeps the distances: the radii are the
    # world directions' own
    o, d = R.camera_rays(R.pose_spherical(70.0, -30.0, 4.0), H, W, f)
    d = np.asarray(d, np.float64)
    world = np.linalg.norm(d[:-1] - d[1:], axis=-1) * 2 / math.sqrt(12)
    np.testing.assert_allclose(r[:-1], world, rtol=1e-4)


def test_ipe_at_zero_variance_is_the_plain_encoding():
    """IPE with zero variance: sin/cos of 2^l x, no identity — the
    PEU's nerf_fixed features, in Mip-NeRF's all-sines-then-cosines
    order."""
    x = jax.random.uniform(jax.random.PRNGKey(0), (64, 3), minval=-2,
                           maxval=2)
    L = 6
    plain = encoding.nerf_encoding(x, L, include_input=False)  # [s_l, c_l]
    sin = plain.reshape(64, L, 2, 3)[:, :, 0].reshape(64, 3 * L)
    cos = plain.reshape(64, L, 2, 3)[:, :, 1].reshape(64, 3 * L)
    want = jnp.concatenate([sin, cos], -1)
    zero = jnp.zeros_like(x)
    np.testing.assert_allclose(encoding.integrated_pos_enc(x, zero, L),
                               want, atol=1e-6)
    np.testing.assert_allclose(
        encoding.integrated_pos_enc_recurrence(x, zero, L), want,
        atol=1e-5)


@pytest.mark.parametrize("hw", [100, 800])
def test_peu_recurrence_matches_the_direct_ipe(hw):
    """The kernel's IPE (double-angle sin/cos, one exp a block of
    octaves) equals the direct form at the variances of hw-px pixel
    footprints along [near, far], L = 16."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(hw))
    mean = jax.random.uniform(k1, (256, 3), minval=-1.5, maxval=1.5)
    rad = float(R.pixel_radii(hw, hw, 0.9 * hw).mean())
    t = jax.random.uniform(k2, (256, 1), minval=2.0, maxval=6.0)
    var = jnp.broadcast_to((rad * t) ** 2 / 4, (256, 3))
    direct = encoding.integrated_pos_enc(mean, var, 16)
    peu = encoding.integrated_pos_enc_recurrence(mean, var, 16)
    np.testing.assert_allclose(peu, direct, atol=5e-4)


def test_frustum_gaussian_matches_monte_carlo():
    """Mean and per-axis variance of points drawn uniformly from one
    conical frustum (t in [t0, t1), radius r t at distance t)."""
    rng = np.random.default_rng(0)
    d = np.array([0.3, -0.5, 0.81])
    d /= np.linalg.norm(d)
    t0, t1, r = 2.5, 3.7, 0.08
    n = 2_000_000
    # uniform in the frustum's volume: t with density ~ t^2, then a point
    # uniform in the disc of radius r t
    t = np.cbrt(rng.uniform(t0 ** 3, t1 ** 3, n))
    rho = r * t * np.sqrt(rng.uniform(0, 1, n))
    phi = rng.uniform(0, 2 * np.pi, n)
    a = np.cross(d, [1.0, 0.0, 0.0])
    a /= np.linalg.norm(a)
    b = np.cross(d, a)
    pts = (t[:, None] * d + (rho * np.cos(phi))[:, None] * a
           + (rho * np.sin(phi))[:, None] * b)
    t_mean, cov = encoding.conical_frustum_to_gaussian(
        jnp.asarray(d[None], jnp.float32), jnp.full((1, 1), t0),
        jnp.full((1, 1), t1), jnp.full((1, 1), r))
    mean = np.asarray(t_mean)[0, 0] * d
    np.testing.assert_allclose(mean, pts.mean(0), atol=2e-3)
    np.testing.assert_allclose(np.asarray(cov)[0, 0], pts.var(0),
                               rtol=2e-2, atol=1e-5)


def _resample_searchsorted(t0, t1, w, n, padding, eps=1e-5):
    """Mip-NeRF's resample with searchsorted and gathers, numpy f64."""
    w = np.concatenate([w[:, :1], w, w[:, -1:]], -1)
    w = np.maximum(w[:, :-1], w[:, 1:])
    w = 0.5 * (w[:, :-1] + w[:, 1:]) + padding
    pdf = w / w.sum(-1, keepdims=True)
    cdf = np.minimum(1.0, np.cumsum(pdf[:, :-1], -1))
    cdf = np.concatenate([np.zeros_like(cdf[:, :1]), cdf,
                          np.ones_like(cdf[:, :1])], -1)
    edges = np.concatenate([t0, t1[:, -1:]], -1)
    u = np.linspace(0.0, 1.0 - np.finfo(np.float32).eps, n + 1)
    out = []
    for e, c in zip(edges, cdf):
        k = np.clip(np.searchsorted(c, u, side="right") - 1, 0, len(c) - 2)
        f = np.clip((u - c[k]) / (c[k + 1] - c[k]), 0.0, 1.0)
        out.append(e[k] + f * (e[k + 1] - e[k]))
    out = np.stack(out)
    return out[:, :-1], out[:, 1:]


def test_mask_form_resample_matches_searchsorted():
    n = 32
    t0, t1 = sampling.cone_intervals(2.0, 6.0, n)
    k = jax.random.PRNGKey(1)
    w = jax.random.uniform(k, (6, n)) ** 4          # peaked weights
    w = w.at[0].set(0.0)                            # all padding
    got0, got1 = sampling.mip_resample(t0, t1, w, n, 0.01)
    want0, want1 = _resample_searchsorted(
        np.broadcast_to(np.asarray(t0, np.float64), (6, n)),
        np.broadcast_to(np.asarray(t1, np.float64), (6, n)),
        np.asarray(w, np.float64), n, 0.01)
    np.testing.assert_allclose(got0, want0, atol=2e-5)
    np.testing.assert_allclose(got1, want1, atol=2e-5)
    # the new intervals tile [near, far] without gaps
    np.testing.assert_array_equal(np.asarray(got0)[:, 1:],
                                  np.asarray(got1)[:, :-1])


def test_shared_network_packs_and_pins_one_weight_set(scene):
    cfg, params = scene
    both = {"coarse": params["coarse"], "fine": params["coarse"]}
    pp = PackedPlcore(cfg, both, use_kernel=True, fuse_two_pass=True)
    assert list(pp.packed) == ["coarse"] and list(pp.params) == ["coarse"]
    one = jax.tree_util.tree_leaves(
        (params["coarse"], kops.stack_plcore_weights(cfg, params["coarse"])))
    assert plcore_nbytes(pp) == sum(a.nbytes for a in one)
    shared = kops.two_pass_vmem_bytes(MIPNERF, 512, 1)
    assert shared < kops.two_pass_vmem_bytes(CONFIG, 512, 1)


# ----------------------------------------------------- refused modes ----
def _cone_cache(scene, **kw):
    cfg, params = scene
    return SceneCache(lambda sid: PackedPlcore(
        cfg, params, use_kernel=True, fuse_two_pass=True, **kw))


def _first_tile_raises(engine, match):
    engine.submit(RenderRequest("s", hw=4))
    with pytest.raises(ValueError, match=match):
        engine.step()


@pytest.mark.parametrize("mode, kw", [
    ("adaptive sampling", {"adaptive_sampling": True}),
    (r"coarse_only degradation", {"degrade_on_overload": True}),
    ("per-cell dispatch", {"route_by_shard": True,
                           "percell_dispatch": True}),
])
def test_engine_modes_refuse_a_cone_scene(mode, kw, scene):
    _first_tile_raises(RenderEngine(_cone_cache(scene), tile_rays=TILE,
                                    **kw), mode)


def test_cluster_engine_refuses_a_cone_scene(scene):
    from repro.serving.cluster import ClusterEngine
    engine = ClusterEngine([_cone_cache(scene), _cone_cache(scene)],
                           tile_rays=TILE)
    _first_tile_raises(engine, "ClusterEngine")


def test_sharded_residency_refuses_a_cone_scene(scene):
    from repro.runtime import sharding as rsh
    cfg, params = scene
    with pytest.raises(ValueError, match="sharded residency"):
        PackedPlcore(cfg, params, use_kernel=True,
                     shard_mesh=rsh.plcore_mesh())


@pytest.mark.parametrize("mode, kw", [
    ("coarse_only degradation", {"coarse_only": True}),
    ("adaptive sampling", {"budget": 8}),
])
def test_tile_modes_refuse_a_cone_scene(mode, kw, scene):
    cfg, params = scene
    pp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
    rays = jnp.ones((TILE, 3), jnp.float32)
    with pytest.raises(ValueError, match=mode):
        pp.render_tile(rays, rays, **kw)
    if "budget" in kw:
        with pytest.raises(ValueError, match=mode):
            build_scene_aux(pp)


@pytest.mark.parametrize("kw, match", [
    ({"ert_eps": 1e-3}, "early ray termination"),
    ({"key": jax.random.PRNGKey(0)}, "deterministic sampling"),
    ({"use_kernel": True}, "two-dispatch"),
])
def test_render_rays_refuses_what_cone_rays_lack(kw, match, scene):
    cfg, params = scene
    rays = jnp.ones((8, 3), jnp.float32)
    with pytest.raises(ValueError, match=match):
        render_rays(cfg, params, rays, rays, **kw)


@pytest.mark.parametrize("kw", [
    {"ray_shape": "cone"},                             # without ipe / mip
    {"encoding_mode": "ipe"},
    {"shared_net": True},                              # cone-only fields
    {"density_activation": "softplus"},
    {"rgb_padding": 0.001},
])
def test_config_takes_mipnerf_whole(kw):
    with pytest.raises(ValueError):
        NerfConfig(**kw)
    with pytest.raises(ValueError, match="as many intervals"):
        dataclasses.replace(MIPNERF, n_fine=64)
    assert not tiny().cone and tiny_mip().cone


@pytest.mark.parametrize("kw", [
    {"shared_net": False},                  # cone rays read one network
    {"density_activation": "relu"},
])
def test_cone_config_takes_one_network_and_softplus(kw):
    with pytest.raises(ValueError, match="Mip-NeRF comes whole"):
        dataclasses.replace(MIPNERF, **kw)
