"""Observability layer tests: tracer, registry, exporters, validation.

The load-bearing claims: (1) ``log_buckets`` edges are deterministic and
``Histogram`` placement/cumulation follow the Prometheus ``le``
convention; (2) the schema-derived ``StatsView`` is BYTE-IDENTICAL
(json.dumps) to the literal stats dicts it replaced, and every write
through it lands in the backing registry; (3) the span ring keeps the
NEWEST spans on overflow and counts what it dropped; (4) fixed seed +
fake clock => two traced engine runs produce identical span streams;
(5) every dispatched tile under chaos faults — and under a mid-flight
cluster host kill — walks a complete lifecycle to a terminal span
(``validate_trace``); (6) the Chrome trace export round-trips through
``validate_chrome_trace`` and the Prometheus text parses; (7) the
validator actually catches broken chains (orphan dispatch, double
serve, dangling request); (8) ``span`` writes the ring record
``begin``/``end`` write, and with tracing off no profiler annotation is
ever built; (9) the process's compile counter sees each new program
shape once.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.models.params import init_params
from repro.obs import (CLUSTER_STATS_SCHEMA, ENGINE_STATS_SCHEMA,
                       NULL_TRACER, Histogram, MetricsRegistry, Span,
                       SpanTracer, chrome_trace, engine_stats_view,
                       extend_stats_view, global_registry, log_buckets,
                       prometheus_text, snapshot, validate_chrome_trace,
                       validate_trace)
from repro.serving import (ClusterEngine, FaultConfig, FaultPlan, HostEvent,
                           RenderEngine, RenderRequest, SceneCache)

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


def _requests(n=4, hw=16):
    return [RenderRequest(scene_id=f"scene{i % 2}", hw=hw, theta=30.0 * i)
            for i in range(n)]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -------------------------------------------------------- histogram math --
def test_log_buckets_edges():
    b = log_buckets(1e-3, 1e0, per_decade=1)
    assert b == pytest.approx((1e-3, 1e-2, 1e-1, 1e0))
    # integer-exponent construction: same args, same edges, every time
    assert log_buckets(1e-5, 1e2, 4) == log_buckets(1e-5, 1e2, 4)
    # covers hi even when log10(hi/lo) isn't integral
    assert log_buckets(1e-3, 5e-1, per_decade=1)[-1] >= 5e-1
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 1.0)


def test_histogram_placement_and_cumulative():
    h = Histogram((1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 99.0, 1000.0):
        h.observe(v)
    # le convention: v == bound lands IN that bound's bucket
    assert h.counts == [2, 2, 1, 1]
    assert h.cumulative() == [2, 4, 5, 6]
    assert h.count == 6
    assert h.sum == pytest.approx(1115.5)
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0, 2.0))          # non-increasing bounds


# ------------------------------------------------- registry / stats view --
def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    h = reg.histogram("y_seconds", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("y_seconds", buckets=(1.0, 3.0))
    assert reg.histogram("y_seconds", buckets=(1.0, 2.0)) is h


def test_stats_view_byte_identical_to_old_literals():
    # THE old RenderEngine literal (pre-registry), key order and all
    old_engine = {
        "dispatches": 0, "dispatch_baseline": 0, "rays_rendered": 0,
        "padded_rays": 0, "scene_switches": 0, "requests_completed": 0,
        "status_counts": {}, "plcore_gather_count": 0,
        "plcore_gather_bytes": 0, "routed_tiles": 0, "max_in_flight": 0,
        "dispatch_errors": 0, "corrupt_tiles": 0, "tile_retries": 0,
        "oracle_fallbacks": 0, "scene_load_errors": 0,
        "scene_load_fail_fasts": 0, "straggler_redispatches": 0,
        "straggle_wait_s": 0.0, "degraded_requests": 0,
        "degraded_tiles": 0, "late_rays": 0, "tile_service_s_ewma": None,
    }
    old_cluster_ext = {
        "cross_host_redispatches": 0, "host_kills": 0,
        "host_slow_events": 0, "requeued_tiles": 0, "quarantines": 0,
        "quarantine_probes": 0, "quarantine_recoveries": 0,
        "affinity_migrations": 0, "heartbeat_timeouts": 0,
        "slow_host_flags": 0, "host_drains": 0, "host_rejoins": 0,
        "failovers": 0, "failover_latency_s": 0.0,
    }
    view = engine_stats_view(MetricsRegistry())
    assert json.dumps(dict(view)) == json.dumps(old_engine)
    extend_stats_view(view, CLUSTER_STATS_SCHEMA)
    assert json.dumps(dict(view)) == \
        json.dumps({**old_engine, **old_cluster_ext})
    # value TYPES survive too (0 vs 0.0 matter for json round-trips)
    assert isinstance(view["straggle_wait_s"], float)
    assert isinstance(view["dispatches"], int)
    assert view["tile_service_s_ewma"] is None


def test_stats_view_writes_through_to_registry():
    reg = MetricsRegistry()
    view = engine_stats_view(reg)
    view["dispatches"] += 3
    view["tile_service_s_ewma"] = 0.25
    view.update({"rays_rendered": 128})
    view["status_counts"]["ok"] = \
        view["status_counts"].get("ok", 0) + 1
    assert reg.get("engine_dispatches_total").value == 3
    assert reg.get("engine_tile_service_s_ewma").value == 0.25
    assert reg.get("engine_rays_rendered_total").value == 128
    assert reg.get("engine_requests_by_status_total") \
        .labels(status="ok").value == 1
    assert view["status_counts"] == {"ok": 1}


def test_engine_stats_schema_covers_old_keys():
    # the schema IS the init list: every engine layer's counter must be
    # pre-registered (a KeyError here means a layer grew a counter
    # without adding it to the schema)
    keys = [k for k, _, _, _ in ENGINE_STATS_SCHEMA]
    assert len(keys) == len(set(keys))
    assert keys[0] == "dispatches" and keys[-1] == "tile_service_s_ewma"
    assert len(CLUSTER_STATS_SCHEMA) == 14


# ---------------------------------------------------------------- tracer --
def test_ring_overflow_keeps_newest():
    clk = _FakeClock()
    tr = SpanTracer(capacity=4, clock=clk)
    for i in range(10):
        tr.event("e", cat="tile", i=i)
        clk.advance(1.0)
    names = [s.attrs["i"] for s in tr.spans()]
    assert names == [6, 7, 8, 9]
    assert tr.dropped == 6
    assert tr.summary()["dropped"] == 6
    # a dropped-span stream cannot be proven complete
    assert not validate_trace(tr)["ok"]


def test_open_spans_survive_overflow():
    tr = SpanTracer(capacity=2, clock=_FakeClock())
    sp = tr.begin("request", cat="request", request=0)
    for i in range(5):
        tr.event("e", i=i)
    assert tr.open_spans() == [sp]
    tr.end(sp, status="ok")
    assert tr.spans()[-1] is sp


def test_tracer_sampling_and_validation():
    tr = SpanTracer(sample_every=3)
    assert [tr.sampled_request(r) for r in range(6)] == \
        [True, False, False, True, False, False]
    assert SpanTracer().sampled_request(17)       # sample_every=1: all
    with pytest.raises(ValueError):
        SpanTracer(capacity=0)
    with pytest.raises(ValueError):
        SpanTracer(sample_every=0)


def _counting_annotations(monkeypatch) -> list:
    """Stands in for ``jax.profiler.TraceAnnotation``; returns the list
    of the names it was built with."""
    made = []

    class Annotation:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return made


def test_span_writes_the_ring_record_of_begin_end(monkeypatch):
    made = _counting_annotations(monkeypatch)
    clk_a, clk_b = _FakeClock(), _FakeClock()
    a, b = SpanTracer(clock=clk_a), SpanTracer(clock=clk_b)
    sp = a.begin("tile.scatter", cat="tile", tile=3, scene="s")
    clk_a.advance(2.0)
    a.end(sp, late=0)
    with b.span("tile.scatter", cat="tile", tile=3, scene="s") as sp:
        clk_b.advance(2.0)
        sp.attrs["late"] = 0
    assert [s.key() for s in b.spans()] == [s.key() for s in a.spans()]
    assert made == ["tile.scatter"]        # the bare name, no attributes
    # a discarded span leaves no record and nothing open
    with b.span("tile.coalesce", cat="tile") as sp:
        b.discard(sp)
    assert len(b.spans()) == 1 and not b.open_spans()
    # an exception closes the span and propagates
    with pytest.raises(KeyError):
        with b.span("cache.load", cat="cache"):
            raise KeyError("loader")
    assert b.spans()[-1].name == "cache.load" and not b.open_spans()


def test_null_tracer_span_builds_no_annotation(setup, monkeypatch):
    made = _counting_annotations(monkeypatch)
    assert NULL_TRACER.span("engine.step") is \
        NULL_TRACER.span("tile.wait", cat="tile", tile=1)
    with NULL_TRACER.span("tile.coalesce", cat="tile") as sp:
        NULL_TRACER.discard(sp)
    assert sp is None
    # a whole untraced engine run builds none either
    cfg, param_sets = setup
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE)
    rids = [eng.submit(r) for r in _requests(2)]
    eng.drain()
    assert all(eng.take(r).status == "ok" for r in rids)
    assert made == []


@pytest.fixture
def no_persistent_cache():
    """A program read back from JAX's persistent cache is not compiled:
    keep the cache off around a test that counts compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_compile_counter_sees_each_new_tile_shape_once(setup,
                                                        no_persistent_cache):
    cfg, param_sets = setup
    pp = PackedPlcore(cfg, param_sets["scene2"])
    tr = SpanTracer()                      # registers the listener
    compiles = global_registry().get("jax_compiles_total")
    seconds = global_registry().get("jax_compile_seconds")
    tile = np.zeros((TILE + 3, 3), np.float32)    # a shape no test uses
    tile[:, 2] = 1.0
    n0, s0 = compiles.value, seconds.default.count
    jax.block_until_ready(pp.render_tile(tile, tile))
    assert compiles.value == n0 + 1
    assert seconds.default.count == s0 + 1
    jax.block_until_ready(pp.render_tile(tile, tile))
    assert compiles.value == n0 + 1        # the same shape: no compile
    spans = [s for s in tr.spans() if s.name == "jax.compile"]
    assert len(spans) == 1 and spans[0].t1 > spans[0].t0
    # a tracer on a fake clock cannot place a host-clock compile
    fake = SpanTracer(clock=_FakeClock())
    tile2 = np.zeros((TILE + 5, 3), np.float32)
    jax.block_until_ready(pp.render_tile(tile2, tile2))
    assert compiles.value == n0 + 2
    assert not fake.spans()


def _traced_run(cfg, param_sets, *, faults=None):
    clk = _FakeClock()
    tr = SpanTracer(clock=clk)
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, pipeline_depth=2, clock=clk,
                       tracer=tr, faults=faults)
    rids = [eng.submit(r) for r in _requests(4)]
    eng.drain()
    for rid in rids:
        eng.take(rid)
    return tr


def test_trace_determinism_fixed_seed_fake_clock(setup):
    cfg, param_sets = setup
    fa = FaultPlan(FaultConfig.chaos(seed=7))
    ka = [s.key() for s in _traced_run(cfg, param_sets, faults=fa).spans()]
    fb = FaultPlan(FaultConfig.chaos(seed=7))
    kb = [s.key() for s in _traced_run(cfg, param_sets, faults=fb).spans()]
    assert ka == kb
    assert len(ka) > 0


# ------------------------------------------------------ chain completeness --
def test_span_chain_complete_under_chaos(setup):
    cfg, param_sets = setup
    tr = _traced_run(cfg, param_sets,
                     faults=FaultPlan(FaultConfig.chaos(seed=3)))
    out = validate_trace(tr)
    assert out["ok"], out["errors"]
    assert out["dispatched_tiles"] >= 1
    assert out["requests"] == 4
    names = {s.name for s in tr.spans()}
    # the full lifecycle chain actually fired, end to end
    assert {"request.submit", "request.admit", "tile.coalesce",
            "tile.dispatch", "tile.device_compute", "tile.drain",
            "tile.scatter", "request.complete", "request",
            "plcore.dispatch", "cache.load"} <= names


def test_span_chain_complete_under_host_kill(setup):
    cfg, param_sets = setup
    tr = SpanTracer()
    caches = [SceneCache(_loader(cfg, param_sets)) for _ in range(2)]
    eng = ClusterEngine(caches, tile_rays=TILE, pipeline_depth=2,
                        tracer=tr)
    eng.schedule_host_events([HostEvent("kill", 0, at_dispatch=3)])
    rids = [eng.submit(r) for r in _requests(6)]
    eng.drain()
    for rid in rids:
        assert eng.take(rid).status in ("ok", "failed", "degraded")
    out = validate_trace(tr)
    assert out["ok"], out["errors"]
    assert out["dispatched_tiles"] >= 1
    names = {s.name for s in tr.spans()}
    assert "host.kill" in names
    # requeued tiles still ended terminal (scatter after redispatch)
    if eng.stats["requeued_tiles"]:
        assert "tile.requeue" in names or "tile.abandon" in names


# -------------------------------------------------------------- exporters --
def test_chrome_trace_structure_and_revalidation(setup):
    cfg, param_sets = setup
    tr = _traced_run(cfg, param_sets)
    obj = chrome_trace(tr)
    evs = obj["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    data = [e for e in evs if e["ph"] != "M"]
    assert all({"name", "cat", "ts", "pid", "tid"} <= set(e) for e in data)
    assert all("dur" in e for e in data if e["ph"] == "X")
    assert min(e["ts"] for e in data) == 0.0       # rebased to earliest
    # device-compute spans get one track per executor slot
    slots = {e["tid"] for e in data if e["name"] == "tile.device_compute"}
    assert slots and all(t >= 10 for t in slots)
    # the artifact gate replays the SAME chain check from the JSON
    out = validate_chrome_trace(json.loads(json.dumps(obj)))
    assert out["ok"], out["errors"]
    assert out["dispatched_tiles"] >= 1


def test_prometheus_text_format(setup):
    cfg, param_sets = setup
    reg = MetricsRegistry()
    eng = RenderEngine(SceneCache(_loader(cfg, param_sets)),
                       tile_rays=TILE, registry=reg)
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=16))
    eng.drain()
    eng.take(rid)
    text = prometheus_text(reg)
    lines = text.splitlines()
    assert "# TYPE engine_dispatches_total counter" in lines
    assert any(l.startswith("engine_dispatches_total ") for l in lines)
    assert any(l.startswith("engine_requests_by_status_total"
                            '{status="ok"}') for l in lines)
    # histograms export cumulative buckets + sum + count
    bucket = [l for l in lines
              if l.startswith("engine_tile_service_seconds_bucket")]
    assert bucket and bucket[-1].split('le="')[1].startswith("+Inf")
    assert any(l.startswith("engine_tile_service_seconds_count ")
               for l in lines)
    # never-observed gauges must NOT export as 0
    assert not any(l.startswith("engine_host_state ") for l in lines)
    snap = snapshot(reg)
    assert snap["engine_dispatches_total"]["series"][0]["value"] \
        == eng.stats["dispatches"]


# ------------------------------------------------------- validator teeth --
def _tile_ev(sid, name, tid):
    return Span(sid, name, "tile", "i", float(sid), float(sid),
                {"tile": tid})


def test_validator_catches_orphan_dispatch():
    spans = [_tile_ev(0, "tile.dispatch", 1),
             _tile_ev(1, "tile.drain", 1)]      # never scattered/dropped
    out = validate_trace(spans)
    assert not out["ok"]
    assert any("non-terminal" in e for e in out["errors"])


def test_validator_catches_double_serve_and_dangling_request():
    spans = [_tile_ev(0, "tile.dispatch", 1),
             _tile_ev(1, "tile.scatter", 1),
             _tile_ev(2, "tile.dispatch", 1),   # re-dispatch after done
             _tile_ev(3, "tile.scatter", 1),
             Span(4, "request.submit", "request", "i", 4.0, 4.0,
                  {"request": 0})]              # no terminal / no span
    out = validate_trace(spans)
    assert not out["ok"]
    msgs = "\n".join(out["errors"])
    assert "dispatched again after terminal" in msgs
    assert "request 0" in msgs


def test_validator_accepts_legal_retry_chain():
    spans = [_tile_ev(0, "tile.dispatch", 1),
             _tile_ev(1, "tile.abandon", 1),    # straggler abandoned...
             _tile_ev(2, "tile.dispatch", 1),   # ...legal re-dispatch
             _tile_ev(3, "tile.drain", 1),
             _tile_ev(4, "tile.scatter", 1),
             _tile_ev(5, "tile.drop", 2)]       # dropped tile: terminal
    out = validate_trace(spans)
    assert out["ok"], out["errors"]
    assert out["tiles"] == 2
    assert out["dispatched_tiles"] == 1
