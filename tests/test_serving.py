"""Multi-tenant serving engine tests.

The load-bearing claims: (1) cross-request ray coalescing is INVISIBLE in
the output — every request's image is bit-identical to a per-request
``render_image`` at the same tile size; (2) padded tail tiles never leak
into neighboring framebuffers (the NaN-initialized framebuffer turns any
gap or leak into a NaN); (3) the engine issues fewer tile dispatches
than a request-at-a-time server (the coalescing accounting); (4) the
scene cache is a real LRU whose residents pack weights exactly once
(``kernels.ops.pack_count``); (5) priorities complete out of order.
"""
import jax
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls, render_image
from repro.data import rays as R
from repro.kernels import ops as kops
from repro.models.params import init_params
from repro.serving import RenderEngine, RenderRequest, SceneCache
from repro.serving import loadgen
from repro.serving.scene_cache import plcore_nbytes

TILE = 64


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    param_sets = {
        f"scene{i}": init_params(plcore_decls(cfg), jax.random.PRNGKey(i),
                                 "float32")
        for i in range(3)}
    return cfg, param_sets


def _engine(cfg, param_sets, **kw):
    cache = SceneCache(lambda sid: PackedPlcore(cfg, param_sets[sid]),
                       capacity_mb=kw.pop("capacity_mb", 256.0))
    return RenderEngine(cache, tile_rays=kw.pop("tile_rays", TILE), **kw)


def _reference(cfg, params, req: RenderRequest, tile: int = TILE):
    c2w = R.pose_spherical(req.theta, req.phi, req.radius)
    ro, rd = R.camera_rays(c2w, req.hw, req.hw, 0.9 * req.hw)
    return np.asarray(render_image(cfg, params, ro, rd,
                                   rays_per_batch=tile))


# ------------------------------------------------ coalescing correctness ----
def test_mixed_trace_bit_identical_and_fewer_dispatches(setup):
    """The acceptance trace: 3 scenes, mixed resolutions, all coalesced.
    Every completed image must equal the sequential per-request render
    bit-for-bit, while the engine's dispatch accounting shows coalescing
    issued FEWER tiles than the per-request baseline."""
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets)
    reqs = [RenderRequest("scene0", hw=10, theta=10.0),
            RenderRequest("scene1", hw=12, theta=50.0),
            RenderRequest("scene0", hw=10, theta=90.0),
            RenderRequest("scene2", hw=16, theta=130.0),
            RenderRequest("scene1", hw=10, theta=170.0),
            RenderRequest("scene0", hw=12, theta=210.0)]
    rids = [eng.submit(r) for r in reqs]
    eng.drain()
    assert eng.stats["requests_completed"] == len(reqs)
    for rid, req in zip(rids, reqs):
        img = eng.completed[rid].image
        assert np.isfinite(img).all()           # NaN fb: no gap, no leak
        np.testing.assert_array_equal(
            img, _reference(cfg, param_sets[req.scene_id], req))
    # 3x100 + 144 + 100 + 144 rays grouped by scene beats per-request
    # ceil(n/64) tiling
    assert eng.stats["dispatches"] < eng.stats["dispatch_baseline"]
    assert eng.stats["rays_rendered"] == sum(r.hw * r.hw for r in reqs)


def test_tail_padding_does_not_leak(setup):
    """Two same-scene requests whose ray counts don't divide the tile:
    tiles span the request boundary and the tail tile is padded; both
    framebuffers must still be exact and fully painted."""
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets)
    a = RenderRequest("scene0", hw=10, theta=20.0)   # 100 rays
    b = RenderRequest("scene0", hw=10, theta=200.0)  # 100 rays
    ra, rb = eng.submit(a), eng.submit(b)
    eng.drain()
    # 200 rays -> 4 tiles of 64, 56 pad rays in the tail; baseline 2+2
    assert eng.stats["dispatches"] == 4
    assert eng.stats["padded_rays"] == 56
    for rid, req in ((ra, a), (rb, b)):
        img = eng.completed[rid].image
        assert np.isfinite(img).all()
        np.testing.assert_array_equal(
            img, _reference(cfg, param_sets[req.scene_id], req))


def test_priority_completes_out_of_order(setup):
    """A small high-priority request submitted after a large one must
    finish first (continuous batching, not FIFO image serving)."""
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets)
    big = eng.submit(RenderRequest("scene0", hw=24, priority=0))
    small = eng.submit(RenderRequest("scene1", hw=8, priority=1))
    eng.drain()
    assert eng.completion_order[0] == small
    assert eng.completion_order[-1] == big
    res = eng.completed[small]
    np.testing.assert_array_equal(
        res.image, _reference(cfg, param_sets["scene1"],
                              RenderRequest("scene1", hw=8, priority=1)))


def test_sticky_scene_grouping(setup):
    """Equal-priority requests over two scenes: the engine must finish one
    scene's queued rays before switching weights, not ping-pong."""
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets)
    for sid in ("scene0", "scene1", "scene0", "scene1"):
        eng.submit(RenderRequest(sid, hw=10))
    eng.drain()
    # scene0's two requests (200 rays = 4 tiles) run before scene1's:
    # exactly one switch into scene0 and one into scene1
    assert eng.stats["scene_switches"] == 2
    assert eng.cache.misses == 2


@pytest.mark.parametrize("flags", [
    {"use_kernel": True},
    {"use_kernel": True, "fuse_two_pass": True},
])
def test_kernel_ert_coalescing_matches_per_request(setup, flags):
    """Kernel paths under ERT: per-kernel-tile and per-ray-block skip
    decisions depend on WHICH rays share a tile — exactly what
    cross-request coalescing changes — so the engine output must still
    match the per-request render through the same PackedPlcore."""
    cfg, param_sets = setup
    cache = SceneCache(
        lambda sid: PackedPlcore(cfg, param_sets[sid], ert_eps=0.05,
                                 **flags),
        capacity_mb=256.0)
    eng = RenderEngine(cache, tile_rays=TILE)
    reqs = [RenderRequest("scene0", hw=8, theta=15.0),    # 64 + 36 rays:
            RenderRequest("scene0", hw=6, theta=240.0)]   # tile 2 is mixed
    rids = [eng.submit(r) for r in reqs]
    eng.drain()
    for rid, req in zip(rids, reqs):
        c2w = R.pose_spherical(req.theta, req.phi, req.radius)
        ro, rd = R.camera_rays(c2w, req.hw, req.hw, 0.9 * req.hw)
        ref = np.asarray(cache.get(req.scene_id).render_image(
            ro, rd, rays_per_batch=TILE))
        np.testing.assert_array_equal(eng.completed[rid].image, ref)


# ------------------------------------------------------- scene cache --------
def test_scene_cache_lru_evicts_and_packs_once(setup):
    """LRU semantics over packed-weight bytes, with kernels.ops.pack_count
    proving weights pack exactly once per residency."""
    cfg, param_sets = setup
    loader = lambda sid: PackedPlcore(cfg, param_sets[sid], use_kernel=True)
    probe = loader("scene0")
    two = 2 * plcore_nbytes(probe) / (1 << 20)
    cache = SceneCache(loader, capacity_mb=two * 1.25)  # room for 2 scenes

    n0 = kops.pack_count()
    cache.get("scene0")
    cache.get("scene1")
    assert (cache.misses, cache.hits) == (2, 0)
    assert kops.pack_count() - n0 == 4          # coarse+fine per scene
    cache.get("scene0")                         # hit -> scene1 becomes LRU
    cache.get("scene0")
    assert cache.hits == 2
    assert kops.pack_count() - n0 == 4          # residents never re-pack
    cache.get("scene2")                         # miss -> evicts scene1
    assert cache.evictions == 1
    assert "scene1" not in cache
    assert cache.resident_scenes == ["scene0", "scene2"]
    assert kops.pack_count() - n0 == 6
    cache.get("scene1")                         # re-touch = new residency
    assert cache.misses == 4
    assert kops.pack_count() - n0 == 8


def test_scene_cache_keeps_just_inserted_when_over_capacity(setup):
    cfg, param_sets = setup
    cache = SceneCache(lambda sid: PackedPlcore(cfg, param_sets[sid]),
                       capacity_mb=1e-6)       # smaller than any scene
    pp = cache.get("scene0")
    assert pp is not None and len(cache) == 1
    cache.get("scene1")
    assert cache.resident_scenes == ["scene1"]
    assert cache.evictions == 1


# ---------------------------------------------------------- loadgen ---------
def test_poisson_trace_deterministic():
    a = loadgen.poisson_trace(8, ["s0", "s1"], rate_rps=100.0, seed=7)
    b = loadgen.poisson_trace(8, ["s0", "s1"], rate_rps=100.0, seed=7)
    c = loadgen.poisson_trace(8, ["s0", "s1"], rate_rps=100.0, seed=8)
    assert a == b
    assert a != c
    assert all(x.arrival_s < y.arrival_s for x, y in zip(a, a[1:]))


def test_closed_loop_reports_and_completes(setup):
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets)
    trace = loadgen.poisson_trace(6, list(param_sets), rate_rps=100.0,
                                  hw_choices=(8, 12), seed=0)
    rep = loadgen.run_trace(eng, trace, mode="closed", concurrency=3)
    assert rep["requests_completed"] == 6
    assert rep["dispatch_savings"] >= 0
    assert rep["cache"]["hit_rate"] > 0
    assert set(rep["latency_ms"]) == {"p50", "p95", "p99"}
    assert all(v is not None for v in rep["latency_ms"].values())
