"""Consolidated cross-path parity matrix.

Every rendering path the repo grew — seed per-tile loop, one-dispatch
XLA pipeline, one-pass kernel chain, one-kernel two-pass fusion, ERT,
RMCM quantization, device placement, the coalescing engine over the
served fused residents, the pipelined executor — renders ONE canonical
scene in one parameterized module, each against its flag-matched oracle.

Two comparison regimes, matching the per-path tests that pinned them:

* ``exact`` — bit-for-bit. Structural dimensions that reuse the same
  compiled tile body (tiling into the single dispatch, packed-weight
  layout, device placement, engine coalescing, pipelining depth) must
  be pixel-invisible.
* ``atol`` — fp32 tolerance. Cross-PROGRAM comparisons (kernel vs XLA,
  fused vs two-dispatch) run the same math at different tile shapes, so
  XLA's gemm blocking reorders fp32 sums; the importance resampler
  amplifies the last-ulp diffs (see test_two_pass_fused).

Pixel-CHANGING flags (ERT eps, RMCM quant) are held equal on BOTH sides
of a row — the matrix never compares across a flag that changes pixels.
"""
import numpy as np
import pytest

import jax

from repro.configs.nerf_icarus import tiny
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls, render_image_tiled
from repro.data import rays as R
from repro.models.params import init_params
from repro.serving import RenderEngine, RenderRequest, SceneCache

HW = 16
BATCH = 64          # HW*HW = 4 tiles: tiling/coalescing is exercised
ERT_EPS = 0.05      # the eps the ERT per-path tests pin


@pytest.fixture(scope="module")
def scene():
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                         "float32")
    quant = {n: rmcm.quantize_tree(params[n]) for n in ("coarse", "fine")}
    ro, rd = R.camera_rays(R.pose_spherical(45.0, -25.0, 4.0),
                           HW, HW, 0.9 * HW)
    return {"cfg": cfg, "params": params, "quant": quant,
            "ro": ro, "rd": rd, "_imgs": {}}


def _img(scene, *, batch=BATCH, ert_eps=None, **pp_kw):
    """Render the canonical scene through one PackedPlcore configuration
    (memoized per flag tuple — rows share their oracle sides)."""
    key = (batch, ert_eps,
           tuple(sorted((k, id(v) if isinstance(v, dict) else v)
                        for k, v in pp_kw.items())))
    out = scene["_imgs"].get(key)
    if out is None:
        pp = PackedPlcore(scene["cfg"], scene["params"], **pp_kw)
        out = np.asarray(pp.render_image(scene["ro"], scene["rd"],
                                         rays_per_batch=batch,
                                         ert_eps=ert_eps))
        scene["_imgs"][key] = out
    return [out]


def _seed_loop(scene, **kw):
    return [np.asarray(render_image_tiled(scene["cfg"], scene["params"],
                                          scene["ro"], scene["rd"],
                                          rays_per_batch=BATCH, **kw))]


def _engine_imgs(scene, *, fused=False, quant=None, **engine_kw):
    """The engine side of a row: two coalescable same-scene requests plus
    a second resolution, images in submit order."""
    cfg, params = scene["cfg"], scene["params"]
    cache = SceneCache(
        lambda sid: PackedPlcore(cfg, params, quant=quant,
                                 use_kernel=fused, fuse_two_pass=fused),
        capacity_mb=64.0)
    eng = RenderEngine(cache, tile_rays=BATCH, **engine_kw)
    reqs = [RenderRequest("s0", hw=HW), RenderRequest("s0", hw=12),
            RenderRequest("s0", hw=HW)]
    rids = [eng.submit(r) for r in reqs]
    eng.drain()
    out = []
    for rid in rids:
        res = eng.completed[rid]
        assert res.status == "ok", res.status
        out.append(np.asarray(res.image))
    return out


def _engine_direct_oracle(scene, **pp_kw):
    """Per-request single-dispatch renders at the engine's request poses
    — what the engine's scatter must reproduce bit-for-bit."""
    pp = PackedPlcore(scene["cfg"], scene["params"], **pp_kw)
    out = []
    for hw in (HW, 12, HW):
        ro, rd = R.camera_rays(R.pose_spherical(45.0, -25.0, 4.0),
                               hw, hw, 0.9 * hw)
        out.append(np.asarray(pp.render_image(ro, rd,
                                              rays_per_batch=BATCH)))
    return out


# name -> (path_side, oracle_side, atol); atol=None means bit-identity.
# Tolerances are the ones the per-path tests pinned (test_pipeline 5e-3
# kernel-vs-XLA / 1e-5 batch invariance, test_two_pass_fused 1e-3).
_MATRIX = {
    "seed_loop__xla_single_dispatch": (
        lambda s: _seed_loop(s), lambda s: _img(s), None),
    "xla_batch64__xla_batch256": (
        lambda s: _img(s, batch=256), lambda s: _img(s), 1e-5),
    "kernel_one_pass__xla": (
        lambda s: _img(s, use_kernel=True), lambda s: _img(s), 5e-3),
    "kernel_fused__kernel_two_dispatch": (
        lambda s: _img(s, use_kernel=True, fuse_two_pass=True),
        lambda s: _img(s, use_kernel=True), 1e-3),
    "kernel_fused_ert__kernel_two_dispatch_ert": (
        lambda s: _img(s, use_kernel=True, fuse_two_pass=True,
                       ert_eps=ERT_EPS),
        lambda s: _img(s, use_kernel=True, ert_eps=ERT_EPS), 5e-3),
    "rmcm_seed_loop__rmcm_xla": (
        lambda s: _seed_loop(s, quant=s["quant"]),
        lambda s: _img(s, quant=s["quant"]), None),
    "rmcm_kernel__rmcm_xla": (
        lambda s: _img(s, quant=s["quant"], use_kernel=True),
        lambda s: _img(s, quant=s["quant"]), 5e-3),
    "rmcm_fused__rmcm_two_dispatch": (
        lambda s: _img(s, quant=s["quant"], use_kernel=True,
                       fuse_two_pass=True),
        lambda s: _img(s, quant=s["quant"], use_kernel=True), 5e-3),
    "device_placed_fused__fused": (
        lambda s: _img(s, use_kernel=True, fuse_two_pass=True,
                       device=jax.devices()[0]),
        lambda s: _img(s, use_kernel=True, fuse_two_pass=True), None),
    "engine_coalesced__direct": (
        lambda s: _engine_imgs(s), _engine_direct_oracle, None),
    "engine_depth3__engine_depth1": (
        lambda s: _engine_imgs(s, pipeline_depth=3),
        lambda s: _engine_imgs(s), None),
    # the served program: fused residents behind the engine at its
    # default pipeline depth, against per-request fused renders
    "engine_fused__fused_direct": (
        lambda s: _engine_imgs(s, fused=True),
        lambda s: _engine_direct_oracle(s, use_kernel=True,
                                        fuse_two_pass=True), None),
    "engine_fused_rmcm__fused_rmcm_direct": (
        lambda s: _engine_imgs(s, fused=True, quant=s["quant"]),
        lambda s: _engine_direct_oracle(s, quant=s["quant"],
                                        use_kernel=True,
                                        fuse_two_pass=True), None),
    # ASDR acceptance: adaptive sampling OFF is not a degraded mode — an
    # engine with the flag explicitly off is the construction-for-
    # construction SAME pipeline as one that never heard of it
    "adaptive_off_engine__engine": (
        lambda s: _engine_imgs(s, fused=True, adaptive_sampling=False),
        lambda s: _engine_imgs(s, fused=True), None),
}


@pytest.mark.parametrize("combo", sorted(_MATRIX))
def test_parity(combo, scene):
    path_fn, oracle_fn, atol = _MATRIX[combo]
    got, want = path_fn(scene), oracle_fn(scene)
    assert len(got) == len(want) > 0
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape, (combo, i, a.shape, b.shape)
        assert np.isfinite(a).all(), (combo, i)
        if atol is None:
            np.testing.assert_array_equal(a, b, err_msg=f"{combo}[{i}]")
        else:
            np.testing.assert_allclose(a, b, atol=atol,
                                       err_msg=f"{combo}[{i}]")


def test_matrix_breadth():
    """The consolidation contract: >= 8 path combinations in ONE module,
    and the structural (bit-identity) rows cover device placement, the
    engine over the served fused residents, and pipelining."""
    assert len(_MATRIX) >= 8
    exact = {name for name, (_, _, atol) in _MATRIX.items()
             if atol is None}
    for needle in ("seed_loop", "device_placed", "engine_coalesced",
                   "engine_depth3", "engine_fused", "adaptive"):
        assert any(needle in name for name in exact), needle
