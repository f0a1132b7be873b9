"""Scheduler/executor/completion engine layers: pipelined async dispatch,
cache pinning, latency split.

The load-bearing claims on top of test_serving.py's synchronous ones:
(1) any ``pipeline_depth`` renders framebuffers BIT-IDENTICAL to the
synchronous depth=1 loop (per-ray independence makes tile-partition
differences invisible) while actually holding ``depth`` tiles in flight
— for the XLA program and for the fused two-pass programs the chip
serves (f32, RMCM, and a cone scene); (2) a scene with in-flight executor
tiles is PINNED in the ``SceneCache`` — eviction pressure from loading
other scenes cannot drop its weights until the last slot drains; (3)
request latency splits exactly into queueing delay + service time; (4)
an engine built without ``pipeline_depth`` pipelines two tiles deep,
counts each dispatch that overlapped an occupied slot, and reads each
tile's service from the later of its dispatch and the previous drain, so
admission control's estimate does not double at depth 2.
"""
import jax
import numpy as np
import pytest

from test_mipnerf import _cone_cache

from repro.configs.nerf_icarus import tiny, tiny_mip
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.models.params import init_params
from repro.serving import RenderEngine, RenderRequest, SceneCache
from repro.serving import loadgen

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


@pytest.fixture(scope="module")
def residents(setup):
    """Scene caches by the program their residents run: the XLA path,
    and the fused two-pass kernel the chip serves, in f32, in RMCM
    weights, and for a cone (Mip-NeRF) scene at ``tiny_mip()``. Each
    call builds a fresh cache."""
    cfg, param_sets = setup
    quant = {sid: {n: rmcm.quantize_tree(p[n]) for n in ("coarse", "fine")}
             for sid, p in param_sets.items()}
    mip = tiny_mip()
    mip_scene = (mip, init_params(plcore_decls(mip), jax.random.PRNGKey(3)))

    def fused(sid, **kw):
        return PackedPlcore(cfg, param_sets[sid], use_kernel=True,
                            fuse_two_pass=True, **kw)
    return {
        "xla": lambda: SceneCache(
            lambda sid: PackedPlcore(cfg, param_sets[sid])),
        "fused": lambda: SceneCache(fused),
        "fused_rmcm": lambda: SceneCache(
            lambda sid: fused(sid, quant=quant[sid])),
        "cone": lambda: _cone_cache(mip_scene),
    }


MIXED = [RenderRequest("scene0", hw=10, theta=10.0),
         RenderRequest("scene1", hw=12, theta=50.0),
         RenderRequest("scene0", hw=10, theta=90.0),
         RenderRequest("scene2", hw=16, theta=130.0),
         RenderRequest("scene1", hw=10, theta=170.0),
         RenderRequest("scene0", hw=12, theta=210.0)]


# ------------------------------------------------ pipelined bit-identity ----
def _run_mixed(cfg, param_sets, cache=None, **kw):
    eng = (_engine(cfg, param_sets, **kw) if cache is None
           else RenderEngine(cache, tile_rays=TILE, **kw))
    rids = [eng.submit(r) for r in MIXED]
    eng.drain()
    assert eng.in_flight_tiles == 0
    assert eng.stats["requests_completed"] == len(MIXED)
    return eng, rids


@pytest.fixture(scope="module")
def sync_mixed(setup, residents):
    """The synchronous depth=1 engine over ``MIXED``, per residents: the
    anchor (run once per residents, on first use)."""
    runs = {}

    def anchor(name):
        if name not in runs:
            eng, rids = _run_mixed(*setup, cache=residents[name](),
                                   pipeline_depth=1)
            assert eng.pipeline_depth == 1
            assert eng.stats["max_in_flight"] == 1
            runs[name] = eng, rids
        return runs[name]
    return anchor


_DEPTHS = [({"pipeline_depth": 2}, 2, "depth2"),
           ({"pipeline_depth": 3}, 3, "depth3"),
           ({}, 2, "default")]


@pytest.mark.parametrize("name, kw, depth", [
    pytest.param(name, kw, depth,
                 id=tag if name == "xla" else f"{name}-{tag}")
    for name in ("xla", "fused", "fused_rmcm", "cone")
    for kw, depth, tag in _DEPTHS])
def test_pipeline_depths_bit_identical(setup, residents, sync_mixed, name,
                                       kw, depth):
    """Depths 2/3, and an engine built without ``pipeline_depth`` (the
    served default, 2), over the same submitted-upfront trace as the
    synchronous loop: identical scheduler decisions (dispatch/pad counts
    equal), identical images, and the deep engines really pipeline
    (peak in-flight == depth) — for the XLA residents and for the fused
    residents every cell serves."""
    cfg, param_sets = setup
    base, base_rids = sync_mixed(name)
    eng, rids = _run_mixed(cfg, param_sets, cache=residents[name](), **kw)
    assert eng.pipeline_depth == depth
    # all requests queued before the first step -> the scheduler walks
    # the same policy path at any depth
    assert eng.stats["dispatches"] == base.stats["dispatches"]
    assert eng.stats["padded_rays"] == base.stats["padded_rays"]
    assert eng.stats["scene_switches"] == base.stats["scene_switches"]
    assert eng.stats["max_in_flight"] == depth
    for rid, brid in zip(rids, base_rids):
        img = eng.completed[rid].image
        assert np.isfinite(img).all()       # NaN fb: no gap, no leak
        np.testing.assert_array_equal(img, base.completed[brid].image)


@pytest.mark.parametrize("depth", [1, 2])
def test_overlapped_dispatch_counter(setup, depth):
    """``engine_overlapped_dispatches_total`` counts tiles dispatched
    while another slot was occupied: every dispatch but the first on a
    back-to-back trace at depth 2, none at depth 1 — and it stays out of
    the serialized stats."""
    eng, _ = _run_mixed(*setup, pipeline_depth=depth)
    overlapped = eng.registry.get("engine_overlapped_dispatches_total")
    d = eng.stats["dispatches"]
    assert d > 1
    assert overlapped.value == (d - 1 if depth > 1 else 0)
    assert not any("overlapped" in k for k in eng.stats)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _FakeDevice:
    """A chip on a fake clock: tiles run one after another, each
    ``service_s`` long, starting when both enqueued and the chip is
    free; the host's own work takes no time."""

    def __init__(self, clock: _FakeClock, service_s: float):
        self.clock = clock
        self.service_s = service_s
        self.free_at = 0.0

    def enqueue(self) -> float:
        self.free_at = max(self.clock.t, self.free_at) + self.service_s
        return self.free_at


class _PendingTile:
    """A dispatched tile's result: materializing it waits (on the fake
    clock) until the fake chip has finished the tile."""

    def __init__(self, rgb, clock: _FakeClock, done_at: float):
        self.rgb, self.clock, self.done_at = rgb, clock, done_at

    def __array__(self, dtype=None, copy=None):
        self.clock.t = max(self.clock.t, self.done_at)
        return np.asarray(self.rgb, dtype)


class _TimedPlcore:
    """A resident whose dispatches run on a ``_FakeDevice``."""

    def __init__(self, pp, device: _FakeDevice):
        self._pp, self._device = pp, device

    def __getattr__(self, name):
        return getattr(self._pp, name)

    def dispatch_tile(self, o, d, **kw):
        rgb, cost = self._pp.dispatch_tile(o, d, **kw)
        return (_PendingTile(rgb, self._device.clock,
                             self._device.enqueue()), cost)


def test_service_estimate_is_per_tile_at_any_depth(setup):
    """On a fake chip of 10 ms a tile, the service EWMA and histogram
    read 10 ms at depth 1 and at depth 2 — not the ~20 ms from dispatch
    to drain that a tile queued behind another spends at depth 2 — so
    admission control predicts the same queueing at both depths."""
    cfg, param_sets = setup
    service_s = 0.01
    readings = {}
    for depth in (1, 2):
        clk = _FakeClock()
        dev = _FakeDevice(clk, service_s)
        cache = SceneCache(lambda sid: _TimedPlcore(
            PackedPlcore(cfg, param_sets[sid]), dev), capacity_mb=256.0)
        eng = RenderEngine(cache, tile_rays=TILE, clock=clk,
                           pipeline_depth=depth)
        for r in MIXED:
            eng.submit(r)
        eng.drain()
        d = eng.stats["dispatches"]
        assert clk.t == pytest.approx(d * service_s)    # chip never idle
        hist = eng.registry.get("engine_tile_service_seconds").default
        assert hist.count == d
        assert hist.sum / d == pytest.approx(service_s)
        assert eng.stats["tile_service_s_ewma"] == pytest.approx(service_s)
        eng.submit(RenderRequest("scene0", hw=16))      # 4 tiles queued
        readings[depth] = eng.scheduler._estimated_queueing_s()
    assert readings[1] == pytest.approx(4 * service_s)
    assert readings[2] == pytest.approx(readings[1])


def test_step_makes_progress_while_in_flight(setup):
    """With all rays handed out but tiles still in flight, step() must
    drain (returning True) rather than stall or re-dispatch — and only
    report idle once completion has consumed every slot."""
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets, pipeline_depth=4)
    rid = eng.submit(RenderRequest("scene0", hw=10))   # 100 rays = 2 tiles
    assert eng.step() and eng.step()                   # both tiles dispatched
    assert eng.in_flight_tiles == 2 and eng.pending == 1
    assert eng.pending_rays == 0                       # all rays handed out
    assert eng.step()                                  # drains tile 1
    assert eng.in_flight_tiles == 1
    assert eng.step()                                  # drains tile 2
    assert eng.in_flight_tiles == 0 and eng.pending == 0
    assert rid in eng.completed
    assert not eng.step()                              # now truly idle


# --------------------------------------------------------- cache pinning ----
def test_inflight_scene_pinned_until_slots_drain(setup):
    """Eviction pressure while a scene has in-flight executor tiles: the
    resident must survive until its last slot drains, then become
    evictable again."""
    cfg, param_sets = setup
    probe = PackedPlcore(cfg, param_sets["scene0"])
    from repro.serving.scene_cache import plcore_nbytes
    one = plcore_nbytes(probe) / (1 << 20)
    cache = SceneCache(lambda sid: PackedPlcore(cfg, param_sets[sid]),
                       capacity_mb=one * 1.25)         # fits ONE scene
    eng = RenderEngine(cache, tile_rays=TILE, pipeline_depth=3)
    eng.submit(RenderRequest("scene0", hw=10))         # 2 tiles
    eng.submit(RenderRequest("scene1", hw=8))
    assert eng.step() and eng.step()                   # scene0 fully in flight
    assert cache.pinned("scene0") and eng.in_flight_tiles == 2
    eng.step()    # scene1's load overflows the cache; scene0 is pinned
    assert "scene0" in cache and cache.evictions == 0
    assert cache.stats()["pinned_scenes"] >= 1
    eng.drain()
    assert not cache.pinned("scene0")                  # pins released
    assert np.isfinite(eng.completed[0].image).all()
    assert np.isfinite(eng.completed[1].image).all()
    cache.get("scene2")      # now over-capacity eviction works again
    assert cache.evictions >= 1 and "scene2" in cache


def test_scene_cache_pin_refcounts():
    """Unit semantics: pinned entries are skipped by eviction; refcounts
    nest; unpinned LRU eviction is unchanged."""
    from types import SimpleNamespace
    blank = SimpleNamespace(params=None, quant=None, packed=None)
    cache = SceneCache(lambda sid: blank, capacity_mb=0.0)
    # capacity 0 -> every insert tries to evict everything unpinned
    cache._entries["a"] = (blank, 1 << 20)
    cache.pin("a")
    cache.pin("a")
    cache.get("b")
    assert "a" in cache and cache.evictions == 0       # pinned survives
    cache.unpin("a")
    assert cache.pinned("a")                           # refcount nests
    cache.unpin("a")
    cache.get("c")
    assert "a" not in cache and cache.evictions >= 1   # evictable again


# ------------------------------------------------------ latency split -------
def test_latency_splits_into_queueing_plus_service(setup):
    cfg, param_sets = setup
    eng = _engine(cfg, param_sets, pipeline_depth=2)
    trace = loadgen.poisson_trace(6, list(param_sets), rate_rps=100.0,
                                  hw_choices=(8, 12), seed=0)
    rep = loadgen.run_trace(eng, trace, mode="closed", concurrency=3)
    for key in ("latency_ms", "queueing_ms", "service_ms"):
        assert set(rep[key]) == {"p50", "p95", "p99"}
        assert all(v is not None and v >= 0 for v in rep[key].values())
    for res in eng.completed.values():
        assert res.queueing_s >= 0 and res.service_s >= 0
        assert np.isclose(res.queueing_s + res.service_s, res.latency_s)


# ------------------------------------------------------------ dispatch ----
def test_dispatch_tile_matches_render_tile(setup):
    cfg, param_sets = setup
    pp = PackedPlcore(cfg, param_sets["scene0"])
    from repro.data import rays as R
    ro, rd = R.camera_rays(R.pose_spherical(30.0, -25.0, 4.0), 8, 8, 7.2)
    o = np.asarray(ro, np.float32).reshape(-1, 3)
    d = np.asarray(rd, np.float32).reshape(-1, 3)
    rgb, cost = pp.dispatch_tile(o.copy(), d.copy())
    assert cost == {}
    np.testing.assert_array_equal(np.asarray(rgb),
                                  np.asarray(pp.render_tile(o, d)))
