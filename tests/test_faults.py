"""Fault-tolerance tests: the serving engine under injected failure.

The load-bearing claims: (1) a ``FaultPlan`` is seed-deterministic, so
a chaos trace is replayable; (2) a loader that raises leaves the
``SceneCache`` exactly as it was — no partial entry, no stale pin,
consistent ``stats()`` — and arms negative-result backoff; (3) the
retry -> oracle recovery ladder reconstructs EXACT pixels: a request
that ends ``ok`` under 100%-rate dispatch errors or tile corruption is
bit-identical to a clean run; (4) delivered framebuffers are asserted
finite (``check_finite``, on by default) — a NaN image cannot ship
silently; (5) deadlines, bounded-queue admission and SLO admission
control produce the documented terminal statuses; (6) priority aging
bounds how long overload can starve a low-priority request; (7)
overload degradation delivers the coarse-only image, flagged; (8) the
``StragglerMonitor`` wiring abandons+redispatches slow tiles without
paying their stall; (9) under a randomized seeded interleaving of
submit/step/take with chaos faults, the engine always terminates and
every request reaches exactly one terminal status.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.data import rays as R
from repro.models.params import init_params
from repro.runtime.straggler import StragglerConfig
from repro.serving import (STATUSES, FaultConfig, FaultPlan, RenderEngine,
                           RenderRequest, SceneCache, SceneLoadError)

TILE = 64


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    param_sets = {
        f"scene{i}": init_params(plcore_decls(cfg), jax.random.PRNGKey(i),
                                 "float32")
        for i in range(3)}
    return cfg, param_sets


def _loader(cfg, param_sets):
    return lambda sid: PackedPlcore(cfg, param_sets[sid])


def _run(engine, requests):
    rids = [engine.submit(r) for r in requests]
    engine.drain()
    return {rid: engine.take(rid) for rid in rids}


def _requests(n=4, hw=16):
    return [RenderRequest(scene_id=f"scene{i % 2}", hw=hw, theta=30.0 * i)
            for i in range(n)]


# ------------------------------------------------------------ fault plan ---
def test_fault_plan_deterministic():
    a = FaultPlan(FaultConfig.chaos(seed=5))
    b = FaultPlan(FaultConfig.chaos(seed=5))
    assert [a.draw_dispatch() for _ in range(50)] == \
           [b.draw_dispatch() for _ in range(50)]
    rgb = np.ones((32, 3), np.float32)
    for _ in range(20):
        ca, cb = a.corrupt_tile(rgb), b.corrupt_tile(rgb)
        assert (ca is None) == (cb is None)
        if ca is not None:
            np.testing.assert_array_equal(ca, cb)
    assert [a.loader_fault("s") for _ in range(20)] == \
           [b.loader_fault("s") for _ in range(20)]
    assert a.summary() == b.summary()
    assert a.total_injected > 0              # chaos rates actually fire
    # corruption poisons a COPY — the drained buffer is never mutated
    np.testing.assert_array_equal(rgb, np.ones((32, 3), np.float32))


def test_fault_plan_straggle_suppressed_in_sync_ladder():
    plan = FaultPlan(FaultConfig(seed=0, straggler_rate=1.0))
    assert plan.draw_dispatch()["kind"] == "straggle"
    # the blocking retry ladder has no in-flight window to straggle in:
    # the draw is consumed (streams stay aligned) but reports healthy
    assert plan.draw_dispatch(allow_straggle=False) is None
    assert plan.draws["dispatch"] == 2
    assert plan.injected["straggle"] == 1


# ------------------------------------------------------------ scene cache --
def test_scene_cache_loader_failure_leaves_no_partial_state(setup):
    cfg, param_sets = setup
    calls = {"n": 0}

    def flaky(sid):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("checkpoint unreadable")
        return PackedPlcore(cfg, param_sets[sid])

    cache = SceneCache(flaky, capacity_mb=256.0, fail_backoff=2)
    with pytest.raises(SceneLoadError) as ei:
        cache.get("scene0")
    assert not ei.value.fail_fast
    # the failed load left NOTHING behind: no entry, no bytes, no pin
    assert "scene0" not in cache
    assert len(cache) == 0 and cache.resident_bytes == 0
    st = cache.stats()
    assert st["load_failures"] == 1
    assert st["resident_scenes"] == 0 and st["pinned_scenes"] == 0
    assert st["failing_scenes"] == 1
    assert cache.consecutive_failures("scene0") == 1
    # negative-result backoff: the next fail_backoff gets short-circuit
    # WITHOUT invoking the loader
    for _ in range(2):
        with pytest.raises(SceneLoadError) as ei:
            cache.get("scene0")
        assert ei.value.fail_fast
    assert calls["n"] == 1
    assert cache.stats()["fail_fasts"] == 2
    # post-backoff retry hits the loader for real; success clears the
    # failure state entirely
    pp = cache.get("scene0")
    assert pp is cache.get("scene0")
    assert cache.consecutive_failures("scene0") == 0
    assert cache.stats()["failing_scenes"] == 0


# ------------------------------------------------------- recovery ladder ---
def test_dispatch_errors_recovered_bit_exact(setup):
    cfg, param_sets = setup
    reqs = _requests()
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), reqs)
    plan = FaultPlan(FaultConfig(seed=1, dispatch_error_rate=1.0))
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, faults=plan)
    faulty = _run(eng, reqs)
    # EVERY dispatch raised, EVERY retry raised -> every tile resolved
    # by the oracle rung, and the pixels are still bit-identical
    assert eng.stats["dispatch_errors"] > 0
    assert eng.stats["oracle_fallbacks"] == eng.stats["dispatches"] > 0
    for rid, res in faulty.items():
        assert res.status == "ok"
        assert res.retries > 0 and res.fallbacks > 0
        np.testing.assert_array_equal(res.image, clean[rid].image)


def test_corrupt_tiles_recovered_bit_exact(setup):
    cfg, param_sets = setup
    reqs = _requests()
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), reqs)
    plan = FaultPlan(FaultConfig(seed=2, corrupt_rate=1.0))
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, faults=plan)
    faulty = _run(eng, reqs)
    assert eng.stats["corrupt_tiles"] > 0
    assert eng.stats["oracle_fallbacks"] >= 1
    for rid, res in faulty.items():
        assert res.status == "ok"
        np.testing.assert_array_equal(res.image, clean[rid].image)


# ----------------------------------------------------------- check_finite --
class _NaNPlcore:
    """A resident whose every program returns NaN — models a scene whose
    weights are poisoned beyond what retry/oracle can fix."""

    def __init__(self, pp):
        self._pp = pp
        self.params, self.quant, self.packed = pp.params, pp.quant, pp.packed

    def commit(self, x):
        return self._pp.commit(x)

    def dispatch_tile(self, o, d, coarse_only=False):
        rgb, rest = self._pp.dispatch_tile(o, d, coarse_only=coarse_only)
        return jnp.full_like(rgb, jnp.nan), rest

    def render_tile(self, o, d, coarse_only=False):
        return jnp.full((o.shape[0], 3), jnp.nan, jnp.float32)

    def render_tile_oracle(self, o, d):
        return jnp.full((o.shape[0], 3), jnp.nan, jnp.float32)


def test_check_finite_rejects_nan_framebuffer(setup):
    cfg, param_sets = setup
    loader = lambda sid: _NaNPlcore(PackedPlcore(cfg, param_sets[sid]))
    eng = RenderEngine(SceneCache(loader), tile_rays=TILE)  # default: on
    eng.submit(RenderRequest(scene_id="scene0", hw=8))
    with pytest.raises(RuntimeError, match="non-finite"):
        eng.drain()


def test_check_finite_off_ships_silently(setup):
    cfg, param_sets = setup
    loader = lambda sid: _NaNPlcore(PackedPlcore(cfg, param_sets[sid]))
    eng = RenderEngine(SceneCache(loader), tile_rays=TILE,
                       check_finite=False)
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8))
    eng.drain()
    res = eng.take(rid)
    assert res.status == "ok"                 # the flag exists for perf;
    assert np.isnan(res.image).all()          # tests/CI keep it ON


# ------------------------------------------------- admission + deadlines ---
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_bounded_queue_rejects_at_admission(setup):
    cfg, param_sets = setup
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, max_queue=1)
    rid_a = eng.submit(RenderRequest(scene_id="scene0", hw=8))
    rid_b = eng.submit(RenderRequest(scene_id="scene0", hw=8))
    res_b = eng.take(rid_b)                   # terminal immediately
    assert res_b.status == "rejected"
    assert "queue full" in res_b.error
    eng.drain()
    assert eng.take(rid_a).status == "ok"
    assert eng.stats["status_counts"] == {"ok": 1, "rejected": 1}


def test_slo_admission_control_rejects_predicted_miss(setup):
    cfg, param_sets = setup
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)), tile_rays=TILE)
    eng.submit(RenderRequest(scene_id="scene0", hw=16))       # backlog
    eng.stats["tile_service_s_ewma"] = 10.0   # observed: 10 s per tile
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                   deadline_s=0.5))
    res = eng.take(rid)
    assert res.status == "rejected"
    assert "admission control" in res.error
    # a deadline the backlog CAN meet is admitted
    rid2 = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                    deadline_s=1e6))
    assert rid2 not in eng.completed
    eng.stats["tile_service_s_ewma"] = None   # don't skew the drain
    eng.drain()
    assert eng.take(rid2).status == "ok"


def test_cold_start_admission_uses_service_prior(setup):
    cfg, param_sets = setup
    # regression: before tile_service_prior_s, a COLD engine (no service
    # EWMA yet) predicted zero queueing delay and admitted every
    # deadlined request into an arbitrary backlog — the prior closes the
    # hole until the first real measurement replaces it
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)), tile_rays=TILE,
                       tile_service_prior_s=10.0)
    assert eng.stats["tile_service_s_ewma"] is None        # genuinely cold
    eng.submit(RenderRequest(scene_id="scene0", hw=16))    # backlog
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                   deadline_s=0.5))
    res = eng.take(rid)
    assert res.status == "rejected" and "admission control" in res.error
    # the same cold engine WITHOUT a prior has no estimate and admits
    # optimistically (the documented pre-prior behavior, still default)
    eng2 = RenderEngine(SceneCache(_loader(cfg, param_sets)), tile_rays=TILE)
    eng2.submit(RenderRequest(scene_id="scene0", hw=16))
    rid2 = eng2.submit(RenderRequest(scene_id="scene0", hw=8,
                                     deadline_s=0.5))
    assert rid2 not in eng2.completed          # admitted, not rejected
    eng.drain()
    eng2.drain()
    # a real measurement outranks the prior: once the EWMA exists the
    # prior no longer dominates the estimate
    eng.stats["tile_service_s_ewma"] = 1e-6
    rid3 = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                    deadline_s=0.5))
    assert rid3 not in eng.completed
    eng.drain()
    assert eng.take(rid3).status == "ok"


def test_deadline_expiry_statuses(setup):
    cfg, param_sets = setup
    clk = _FakeClock()
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, clock=clk, pipeline_depth=1)
    # expired: deadline passes before any ray is tiled
    rid_e = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                     deadline_s=1.0))
    clk.advance(2.0)
    eng.step()
    res_e = eng.completed[rid_e]
    assert res_e.status == "expired"
    assert np.isnan(res_e.image).all()        # nothing fabricated
    # partial: some tiles land, then the deadline passes mid-render
    rid_p = eng.submit(RenderRequest(scene_id="scene0", hw=16,
                                     deadline_s=1.0))
    eng.step()                                # one 64-ray tile scatters
    clk.advance(2.0)
    eng.step()
    res_p = eng.completed[rid_p]
    assert res_p.status == "partial"
    flat = res_p.image.reshape(-1, 3)
    assert np.isfinite(flat[:TILE]).all()     # delivered pixels are real
    assert np.isnan(flat[TILE:]).all()        # the rest is visibly absent
    assert eng.pending == 0


def test_late_scatter_after_expiry_is_dropped(setup):
    cfg, param_sets = setup
    clk = _FakeClock()
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, clock=clk, pipeline_depth=3)
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8,
                                   deadline_s=1.0))
    eng.step()                                # tile in flight, not drained
    assert eng.in_flight_tiles == 1
    clk.advance(2.0)
    eng.drain()
    assert eng.completed[rid].status == "partial" \
        or eng.completed[rid].status == "expired"
    # the in-flight tile's pixels scattered into the void, not a crash
    assert eng.stats["late_rays"] > 0 \
        or eng.completed[rid].status == "partial"


# ------------------------------------------------------- priority aging ----
def test_priority_aging_bounds_starvation(setup):
    cfg, param_sets = setup
    # aging raises a WAITING request's effective priority relative to
    # LATER arrivals (requests submitted together age in lockstep), so
    # the starvation scenario is a steady stream of fresh high-priority
    # work: without aging the low request loses to every new arrival;
    # with aging its accumulated wait outranks them boundedly soon
    def order(aging):
        eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                           tile_rays=TILE, aging_tiles=aging)
        low = eng.submit(RenderRequest(scene_id="scene0", hw=16,
                                       priority=0))
        last_high = None
        for i in range(3):
            last_high = eng.submit(RenderRequest(
                scene_id="scene0", hw=16, priority=1, theta=10.0 * i))
            for _ in range(4):     # one request's worth of tiles
                eng.step()
        eng.drain()
        return (eng.completion_order.index(low),
                eng.completion_order.index(last_high))

    lo, hi = order(None)
    assert lo > hi                 # no aging: starved past every arrival
    lo, hi = order(1)
    assert lo < hi                 # aged ahead of later arrivals


# ------------------------------------------------- overload degradation ----
def test_overload_degradation_delivers_coarse_image(setup):
    cfg, param_sets = setup
    cache = SceneCache(_loader(cfg, param_sets))
    eng = RenderEngine(cache, tile_rays=TILE, degrade_on_overload=True,
                       degrade_queue_tiles=2, degrade_max_priority=0)
    reqs = [RenderRequest(scene_id="scene0", hw=16, theta=15.0 * i)
            for i in range(3)]                # 12 queued tiles > 2
    results = _run(eng, reqs)
    assert eng.stats["degraded_requests"] == 3
    assert eng.stats["degraded_tiles"] == eng.stats["dispatches"] > 0
    assert eng.robustness()["goodput"] == 1.0  # degraded still delivers
    pp = cache.get("scene0")
    # the degraded image IS the coarse-only render, bit-exactly
    # (rids are issued in submit order, so results aligns with reqs)
    for r, res in zip(reqs, results.values()):
        assert res.status == "degraded"
        c2w = R.pose_spherical(r.theta, r.phi, r.radius)
        ro, rd = R.camera_rays(c2w, r.hw, r.hw, 0.9 * r.hw)
        ref = np.asarray(pp.render_tile(
            jnp.asarray(np.asarray(ro, np.float32).reshape(-1, 3)),
            jnp.asarray(np.asarray(rd, np.float32).reshape(-1, 3)),
            coarse_only=True)).reshape(r.hw, r.hw, 3)
        np.testing.assert_array_equal(res.image, ref)


# ------------------------------------------------------ straggler wiring ---
def test_straggler_redispatch_avoids_paying_the_stall(setup):
    cfg, param_sets = setup
    # every dispatch straggles by 30 s; a pre-warmed monitor with a tight
    # deadline must abandon+redispatch every tile instead of sleeping
    plan = FaultPlan(FaultConfig(seed=0, straggler_rate=1.0,
                                 straggler_extra_s=30.0))
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), _requests(n=2))
    eng = RenderEngine(
        SceneCache(_loader(cfg, param_sets)), tile_rays=TILE, faults=plan,
        straggler_cfg=StragglerConfig(warmup_steps=0, deadline_factor=2.0,
                                      ewma_alpha=0.01))
    eng.executor.straggler.record_step(1e-3)   # seed a fast baseline
    t0 = time.perf_counter()
    results = _run(eng, _requests(n=2))
    wall = time.perf_counter() - t0
    assert eng.stats["straggler_redispatches"] == eng.stats["dispatches"] > 0
    assert eng.stats["straggle_wait_s"] == 0.0  # never slept the stalls
    assert wall < 25.0
    for rid, res in results.items():
        assert res.status == "ok"               # redispatch is bit-exact
        np.testing.assert_array_equal(res.image, clean[rid].image)


# ------------------------------------------------------ chaos acceptance ---
def test_seeded_chaos_trace_terminates_with_exact_recovery(setup):
    cfg, param_sets = setup
    reqs = [RenderRequest(scene_id=f"scene{i % 3}", hw=16, theta=20.0 * i,
                          priority=i % 2) for i in range(8)]
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), reqs)
    plan = FaultPlan(FaultConfig.chaos(seed=0))
    eng = RenderEngine(
        SceneCache(plan.wrap_loader(_loader(cfg, param_sets))),
        tile_rays=TILE, faults=plan, max_queue=64, aging_tiles=8)
    results = _run(eng, reqs)
    rb = eng.robustness()
    assert plan.total_injected > 0             # the chaos actually fired
    assert sum(rb["status_counts"].values()) == len(reqs)
    assert rb["goodput"] >= 0.75
    for rid, res in results.items():
        assert res.status in STATUSES
        if res.status == "ok":
            np.testing.assert_array_equal(res.image, clean[rid].image)


def test_fuzz_random_interleaving_always_terminates(setup):
    cfg, param_sets = setup
    rng = np.random.RandomState(7)
    plan = FaultPlan(FaultConfig.chaos(seed=3))
    eng = RenderEngine(
        SceneCache(plan.wrap_loader(_loader(cfg, param_sets))),
        tile_rays=32, faults=plan, max_queue=16, aging_tiles=4,
        degrade_on_overload=True, degrade_queue_tiles=4)
    submitted, taken = set(), {}
    for _ in range(6):
        for _ in range(int(rng.randint(0, 4))):
            dl = (None, 0.05, 5.0)[int(rng.randint(3))]
            submitted.add(eng.submit(RenderRequest(
                scene_id=f"scene{int(rng.randint(3))}", hw=8,
                theta=float(rng.uniform(0.0, 360.0)),
                priority=int(rng.randint(2)), deadline_s=dl)))
        for _ in range(int(rng.randint(0, 6))):
            eng.step()
        for rid in list(eng.completed):
            if rng.random_sample() < 0.5:
                taken[rid] = eng.take(rid)
    steps = eng.drain(max_steps=20000)
    assert steps < 20000                       # terminated, not capped
    assert eng.pending == 0 and eng.in_flight_tiles == 0
    results = dict(taken)
    results.update(eng.completed)
    # every submitted request reached EXACTLY ONE terminal status
    assert set(results) == submitted
    assert eng.stats["requests_completed"] == len(submitted)
    for res in results.values():
        assert res.status in STATUSES
        if res.delivered:
            assert np.isfinite(res.image).all()
