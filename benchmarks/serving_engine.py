"""Serving-engine benchmark: the multi-tenant request path end to end.

Drives a fixed-seed closed-loop trace (N scenes, mixed resolutions)
through ``repro.serving.RenderEngine`` and reports request throughput,
p50/p95/p99 latency — split into queueing delay vs service time — the
coalescing dispatch savings vs a request-at-a-time server, and the
scene-cache hit rate — then renders the SAME trace request-by-request
through ``PackedPlcore.render_image`` as the sequential baseline, so
the engine's scheduling win (not just the kernel's) is what the number
isolates.

One more interleaved pass covers the scheduler/executor split:

* ``pipeline``: the SAME trace at ``pipeline_depth >= 2`` (env
  ``BENCH_SERVING_DEPTH``, default 2) next to the depth=1 numbers — the
  double-buffered executor's req/s + latency vs the synchronous loop,
  persisted per PR so the async-dispatch trajectory is tracked like the
  kernel one.

A second pass covers the fault-tolerance layer:

* ``robustness``: the SAME trace under the canonical seeded chaos plan
  (``FaultConfig.chaos(seed=0)`` — injected dispatch errors, corrupted
  tiles, loader failures, stragglers) through a COLD chaos-wrapped
  cache: goodput (delivered / submitted), per-status terminal counts,
  and the recovery-ladder counters (retries, oracle fallbacks,
  redispatches). Deterministic in the seed, so the persisted history
  shows the recovery surface shifting across PRs, not noise.

A third pass covers the multi-host fabric:

* ``multihost``: the SAME trace through a 2-host ``ClusterEngine``
  (per-host SceneCache + TileExecutor behind the global scheduler) with
  one host KILLED at a fixed global dispatch count mid-trace — per-host
  req/s and dispatch counts, cross-host redispatches, re-queued tiles,
  and the requeue -> redispatch failover latency. Clockless kill
  trigger, so the persisted counters are deterministic.

``benchmarks/run.py serving`` lands the result in ``BENCH_plcore.json``'s
append-only history next to the kernel variants, so the serving-layer
trajectory is tracked across PRs like the kernel one. BENCH_SERVING_*
env knobs shrink the run for CI smoke (which, like the fusion suite's
BENCH_PLCORE_HW, skips persisting).
"""
from __future__ import annotations

import os
import time

import jax

from benchmarks.common import emit
from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.models.params import init_params
from repro.serving import FaultConfig, FaultPlan, RenderEngine, SceneCache
from repro.serving import loadgen
from repro.serving.cluster import ClusterEngine, HostEvent, split_devices


def _warm(cache, scene_ids, hw_mix, tile_rays):
    """Touch EVERY scene (load + pack) and compile the tile +
    per-resolution image programs, then zero the cache counters so the
    measured run's hit rate describes the measured trace, not warm-up."""
    from repro.data import rays as R
    warm_engine = RenderEngine(cache, tile_rays=tile_rays)
    for sid in scene_ids:
        warm_engine.submit(loadgen.poisson_trace(
            1, [sid], rate_rps=1e3, hw_choices=hw_mix, seed=1)[0].request)
    warm_engine.drain()
    for hw in hw_mix:
        ro_w, rd_w = R.camera_rays(R.pose_spherical(0.0, -25.0, 4.0),
                                   hw, hw, 0.9 * hw)
        cache.get(scene_ids[0]).render_image(
            ro_w, rd_w, rays_per_batch=tile_rays).block_until_ready()
    cache.hits = cache.misses = cache.evictions = 0


def run() -> dict:
    n_scenes = int(os.environ.get("BENCH_SERVING_SCENES", "3"))
    n_requests = int(os.environ.get("BENCH_SERVING_REQUESTS", "12"))
    tile_rays = int(os.environ.get("BENCH_SERVING_TILE", "512"))
    depth = max(2, int(os.environ.get("BENCH_SERVING_DEPTH", "2")))
    hw_mix = (16, 32)
    cfg = tiny()
    scene_ids = [f"scene{i}" for i in range(n_scenes)]
    param_sets = {sid: init_params(plcore_decls(cfg), jax.random.PRNGKey(i),
                                   "float32")
                  for i, sid in enumerate(scene_ids)}

    cache = SceneCache(lambda sid: PackedPlcore(cfg, param_sets[sid]),
                       capacity_mb=256.0)
    trace = loadgen.poisson_trace(n_requests, scene_ids, rate_rps=100.0,
                                  hw_choices=hw_mix, seed=0)
    from repro.data import rays as R
    _warm(cache, scene_ids, hw_mix, tile_rays)

    # interleaved rounds + best (min-wall) per pass — the fusion suite's
    # rationale: on a shared CI box, back-to-back passes record
    # contention bursts as signal; interleaving + min compares the
    # engine variants and the sequential baseline on equal footing
    reps, reps_pl, seq_walls = [], [], []
    for _ in range(2):
        engine = RenderEngine(cache, tile_rays=tile_rays, pipeline_depth=1)
        reps.append(loadgen.run_trace(engine, trace, mode="closed",
                                      concurrency=4))
        # sequential request-at-a-time baseline over the same trace
        t0 = time.perf_counter()
        for item in trace:
            req = item.request
            c2w = R.pose_spherical(req.theta, req.phi, req.radius)
            ro, rd = R.camera_rays(c2w, req.hw, req.hw, 0.9 * req.hw)
            cache.get(req.scene_id).render_image(
                ro, rd, rays_per_batch=tile_rays).block_until_ready()
        seq_walls.append(time.perf_counter() - t0)
        # pipelined executor: same trace, depth >= 2 in-flight tile slots
        engine_pl = RenderEngine(cache, tile_rays=tile_rays,
                                 pipeline_depth=depth)
        reps_pl.append(loadgen.run_trace(engine_pl, trace, mode="closed",
                                         concurrency=4))
    rep = min(reps, key=lambda r: r["wall_s"])
    rep_pl = min(reps_pl, key=lambda r: r["wall_s"])
    seq_wall = min(seq_walls)

    # robustness pass: same trace, canonical chaos plan, COLD wrapped
    # cache (loader faults only fire on misses, so warm-up would hide
    # them); counters are seed-deterministic — one round suffices
    plan = FaultPlan(FaultConfig.chaos(seed=0))
    cache_chaos = SceneCache(
        plan.wrap_loader(lambda sid: PackedPlcore(cfg, param_sets[sid])),
        capacity_mb=256.0)
    engine_chaos = RenderEngine(cache_chaos, tile_rays=tile_rays,
                                faults=plan)
    rep_chaos = loadgen.run_trace(engine_chaos, trace, mode="closed",
                                  concurrency=4)

    # multihost pass: same trace through a 2-host cluster (per-host cold
    # caches over split device groups), then the BUSY host killed at
    # half its dispatch count on a fresh cluster — residency affinity
    # concentrates a small trace on one host, so the probe run finds the
    # host whose death actually forces cross-host failover. at_dispatch
    # triggers keep the counters seed-deterministic.
    n_hosts = 2
    mh_groups = split_devices(n_hosts)

    def _mh_engine():
        caches_mh = [SceneCache(lambda sid: PackedPlcore(cfg, param_sets[sid]),
                                capacity_mb=256.0) for _ in range(n_hosts)]
        return ClusterEngine(caches_mh, device_groups=mh_groups,
                             tile_rays=tile_rays, pipeline_depth=depth)
    probe = _mh_engine()
    disp_hosts = []
    probe_dispatch = probe._dispatch_on
    def _record(host, tile, now):
        probe_dispatch(host, tile, now)
        disp_hosts.append(host.id)
    probe._dispatch_on = _record
    loadgen.run_trace(probe, trace, mode="closed", concurrency=4)
    busy = max(probe.pool, key=lambda h: h.dispatches)
    # kill MID-BATCH for the victim: the event fires at the step after
    # global dispatches reach kill_at, so aiming one past the middle of
    # the victim's own dispatch sequence guarantees it holds in-flight
    # slots when it dies (an idle victim's death forces no failover)
    busy_idx = [i for i, hid in enumerate(disp_hosts) if hid == busy.id]
    kill_at = busy_idx[len(busy_idx) // 2] + 1
    engine_mh = _mh_engine()
    rep_mh = loadgen.run_trace(
        engine_mh, trace, mode="closed", concurrency=4,
        host_events=[HostEvent("kill", busy.id, at_dispatch=kill_at)])

    # adaptive-sampling pass (ASDR): the SAME trace on the canonical
    # mixed empty-space scenes (same param draws, sigma-head bias -0.5 —
    # real empty space, so all budget classes populate and a large ray
    # fraction is provably dead) through the static-budget FUSED engine
    # vs the adaptive engine (per-ray budget classes + trunk memo).
    # samples/s is ORACLE-EQUIVALENT: delivered rays x the full
    # static-path sample count / wall — the adaptive engine delivers the
    # same rays for less work, so its equivalent throughput rises.
    n_samples_per_ray = cfg.n_coarse + cfg.n_coarse + cfg.n_fine
    # per-scene calibrated sigma-head bias: each random init lands at a
    # different base density, so a uniform shift leaves some scenes
    # nearly solid (scene1 at -0.5 is ~90% occupied). The per-key biases
    # put EVERY scene in the canonical mixed profile — roughly 2/3 of
    # camera rays traverse provably-empty space while all budget classes
    # keep non-empty rays to classify.
    scene_bias = {0: -0.5, 1: -0.7, 2: -0.5}
    param_sets_b = {}
    for i, sid in enumerate(scene_ids):
        p = init_params(plcore_decls(cfg), jax.random.PRNGKey(i), "float32")
        for net in p:
            p[net]["sigma"]["b"] = (p[net]["sigma"]["b"]
                                    + scene_bias.get(i, -0.5))
        param_sets_b[sid] = p
    cache_fb = SceneCache(
        lambda sid: PackedPlcore(cfg, param_sets_b[sid], use_kernel=True,
                                 fuse_two_pass=True), capacity_mb=256.0)
    _warm(cache_fb, scene_ids, hw_mix, tile_rays)
    # one untimed adaptive pass: the probe/memo warm (load-time work) and
    # the per-budget program compiles land here, not in the timed rounds
    engine_ad_w = RenderEngine(cache_fb, tile_rays=tile_rays,
                               adaptive_sampling=True, memo_mb=16.0,
                               adaptive_grid_res=24, adaptive_probe_hw=12)
    loadgen.run_trace(engine_ad_w, trace, mode="closed", concurrency=4)
    reps_fb, reps_ad = [], []
    engines_ad = []
    for _ in range(2):
        engine_fb = RenderEngine(cache_fb, tile_rays=tile_rays)
        reps_fb.append(loadgen.run_trace(engine_fb, trace, mode="closed",
                                         concurrency=4))
        engine_ad = RenderEngine(cache_fb, tile_rays=tile_rays,
                                 adaptive_sampling=True, memo_mb=16.0,
                                 adaptive_grid_res=24, adaptive_probe_hw=12)
        reps_ad.append(loadgen.run_trace(engine_ad, trace, mode="closed",
                                         concurrency=4))
        engines_ad.append(engine_ad)
    rep_fb = min(reps_fb, key=lambda r: r["wall_s"])
    i_ad = min(range(len(reps_ad)), key=lambda i: reps_ad[i]["wall_s"])
    rep_ad = reps_ad[i_ad]
    sampling_rep = engines_ad[i_ad].sampling_report()

    # observability pass: the SAME trace tracing-off vs tracing-on,
    # interleaved rounds + min wall each — prices the SpanTracer on the
    # hot path (the NULL_TRACER fast path must stay ~free; the armed
    # tracer's cost is the number this block tracks across PRs) and
    # holds the traced run to full span-chain integrity
    from repro.obs import SpanTracer
    from repro.obs.export import validate_trace
    reps_off, reps_on = [], []
    tracers = []
    for _ in range(3):
        eng_off = RenderEngine(cache, tile_rays=tile_rays)
        reps_off.append(loadgen.run_trace(eng_off, trace, mode="closed",
                                          concurrency=4))
        tracer = SpanTracer()
        eng_on = RenderEngine(cache, tile_rays=tile_rays, tracer=tracer)
        reps_on.append(loadgen.run_trace(eng_on, trace, mode="closed",
                                         concurrency=4))
        tracers.append(tracer)
    rep_off = min(reps_off, key=lambda r: r["wall_s"])
    i_on = min(range(len(reps_on)), key=lambda i: reps_on[i]["wall_s"])
    rep_on = reps_on[i_on]
    integ = validate_trace(tracers[i_on])

    out = {
        "scenes": n_scenes, "requests": n_requests, "tile_rays": tile_rays,
        "req_per_s": rep["req_per_s"], "rays_per_s": rep["rays_per_s"],
        "latency_ms": rep["latency_ms"],
        "queueing_ms": rep["queueing_ms"], "service_ms": rep["service_ms"],
        "dispatches": rep["engine"]["dispatches"],
        "dispatch_baseline": rep["engine"]["dispatch_baseline"],
        "dispatch_savings": rep["dispatch_savings"],
        "cache_hit_rate": rep["cache"]["hit_rate"],
        "sequential_wall_s": round(seq_wall, 4),
        "engine_wall_s": rep["wall_s"],
        "speedup_engine_vs_sequential": round(seq_wall / rep["wall_s"], 2)
        if rep["wall_s"] else None,
        # depth=1 vs depth>=2: the double-buffered async executor next to
        # the synchronous loop it must be bit-identical to
        "pipeline": {
            "depth": depth,
            "req_per_s": rep_pl["req_per_s"],
            "latency_ms": rep_pl["latency_ms"],
            "service_ms": rep_pl["service_ms"],
            "max_in_flight": rep_pl["engine"]["max_in_flight"],
            "req_per_s_depth1": rep["req_per_s"],
            "speedup_vs_depth1": round(rep["wall_s"] / rep_pl["wall_s"], 2)
            if rep_pl["wall_s"] else None,
        },
        # the fault-tolerance surface under the canonical chaos plan:
        # goodput + status counts + the recovery-ladder accounting
        # (RenderEngine.robustness schema, see docs/benchmarks.md)
        "robustness": {
            "fault_seed": 0,
            "req_per_s": rep_chaos["req_per_s"],
            **rep_chaos["robustness"],
        },
        # the multi-host fabric under a mid-trace host kill: per-host
        # req/s shares + the failover accounting (serving.multihost
        # schema, see docs/benchmarks.md)
        "multihost": {
            "hosts": n_hosts,
            "devices_per_host": [len(g) if g else None for g in mh_groups],
            "killed_host": busy.id,
            "kill_at_dispatch": kill_at,
            "req_per_s": rep_mh["req_per_s"],
            "goodput": rep_mh["goodput"],
            "latency_ms": rep_mh["latency_ms"],
            # per-host share of the trace: dispatch counts stand in for
            # per-host req/s (requests complete globally, tiles don't) —
            # req_per_s_per_host prices each host's slice of the wall
            "host_dispatches": {
                hid: h["dispatches"]
                for hid, h in rep_mh["cluster"]["hosts"].items()},
            "host_states": {
                hid: h["state"]
                for hid, h in rep_mh["cluster"]["hosts"].items()},
            "req_per_s_per_host": {
                hid: (round(rep_mh["req_per_s"] * h["dispatches"]
                            / max(1, rep_mh["engine"]["dispatches"]), 2)
                      if rep_mh["req_per_s"] is not None else None)
                for hid, h in rep_mh["cluster"]["hosts"].items()},
            "host_kills": rep_mh["cluster"]["host_kills"],
            "requeued_tiles": rep_mh["cluster"]["requeued_tiles"],
            "cross_host_redispatches":
                rep_mh["cluster"]["cross_host_redispatches"],
            "failovers": rep_mh["cluster"]["failovers"],
            "mean_failover_latency_ms": (
                round(rep_mh["cluster"]["mean_failover_latency_s"] * 1e3, 3)
                if rep_mh["cluster"]["mean_failover_latency_s"] is not None
                else None),
        },
        # adaptive per-ray sample budgets + trunk memoization vs the
        # static-budget fused engine on the canonical mixed empty-space
        # scenes; samples/s is oracle-equivalent (delivered rays x full
        # sample count / wall) so the >= 1.5x gate prices real wall-time
        # savings (serving.adaptive schema, see docs/benchmarks.md)
        "adaptive": {
            "scene_bias": {f"scene{k}": v for k, v in scene_bias.items()
                           if k < n_scenes},
            "budgets": (next(iter(sampling_rep["scenes"].values()))
                        ["budgets"] if sampling_rep["scenes"] else []),
            "req_per_s_static": rep_fb["req_per_s"],
            "req_per_s_adaptive": rep_ad["req_per_s"],
            "samples_per_s_static": round(
                rep_fb["rays_per_s"] * n_samples_per_ray, 1)
            if rep_fb["rays_per_s"] else None,
            "samples_per_s_adaptive": round(
                rep_ad["rays_per_s"] * n_samples_per_ray, 1)
            if rep_ad["rays_per_s"] else None,
            "speedup_samples_per_s": round(
                rep_fb["wall_s"] / rep_ad["wall_s"], 2)
            if rep_ad["wall_s"] else None,
            "latency_ms_static": rep_fb["latency_ms"],
            "latency_ms_adaptive": rep_ad["latency_ms"],
            "adaptive_tiles": sampling_rep["adaptive_tiles"],
            "full_dead_tiles": sampling_rep["full_dead_tiles"],
            "dead_ray_fraction": sampling_rep["dead_ray_fraction"],
            "skipped_fine_samples": sampling_rep["skipped_fine_samples"],
            "memo_hits": sampling_rep["memo_hits"],
            "memo_evictions": sampling_rep["memo_evictions"],
            "memo_resident_mb": sampling_rep["memo_resident_mb"],
            "budget_rays": {
                b: sum(r["budget_rays"].get(b, 0)
                       for r in sampling_rep["scenes"].values())
                for b in (str(x) for x in (
                    next(iter(sampling_rep["scenes"].values()))["budgets"]
                    if sampling_rep["scenes"] else []))},
        },
        # lifecycle tracing priced against the NULL_TRACER fast path on
        # the same closed-loop trace (min wall over interleaved rounds);
        # the traced run must also pass the span-chain integrity check
        "observability": {
            "req_per_s_untraced": rep_off["req_per_s"],
            "req_per_s_traced": rep_on["req_per_s"],
            "tracing_overhead_pct": (
                round((rep_on["wall_s"] / rep_off["wall_s"] - 1.0) * 100, 2)
                if rep_off["wall_s"] else None),
            "spans": rep_on["observability"]["spans"],
            "events": rep_on["observability"]["events"],
            "dropped_spans": rep_on["observability"]["dropped"],
            "trace_integrity_ok": integ["ok"],
            "dispatched_tiles": integ["dispatched_tiles"],
        },
    }
    emit("serving/req_per_s", 0.0, f"req_per_s={out['req_per_s']}")
    emit("serving/pipelined_req_per_s", 0.0,
         f"depth{depth}_req_per_s={out['pipeline']['req_per_s']}")
    emit("serving/latency_p50_ms", out["latency_ms"]["p50"],
         f"p99={out['latency_ms']['p99']}")
    emit("serving/queueing_p50_ms", out["queueing_ms"]["p50"],
         f"service_p50={out['service_ms']['p50']}")
    emit("serving/dispatch_savings", 0.0,
         f"{out['dispatches']}_vs_{out['dispatch_baseline']}")
    emit("serving/speedup_vs_sequential", 0.0,
         f"x{out['speedup_engine_vs_sequential']}")
    rb = out["robustness"]
    emit("serving/chaos_goodput", 0.0,
         f"goodput={rb['goodput']}_retries={rb['tile_retries']}"
         f"_fallbacks={rb['oracle_fallbacks']}")
    mh = out["multihost"]
    emit("serving/multihost_failover", 0.0,
         f"goodput={mh['goodput']}_kills={mh['host_kills']}"
         f"_xhost={mh['cross_host_redispatches']}"
         f"_failover_ms={mh['mean_failover_latency_ms']}")
    ad = out["adaptive"]
    emit("serving/adaptive_speedup", 0.0,
         f"x{ad['speedup_samples_per_s']}_dead={ad['dead_ray_fraction']}"
         f"_skipped={ad['skipped_fine_samples']}"
         f"_memo_hits={ad['memo_hits']}")
    ob = out["observability"]
    emit("serving/observability_overhead", 0.0,
         f"traced_{ob['req_per_s_traced']}_vs_{ob['req_per_s_untraced']}"
         f"_overhead={ob['tracing_overhead_pct']}pct"
         f"_integrity={'ok' if ob['trace_integrity_ok'] else 'FAIL'}")
    return out


if __name__ == "__main__":
    run()
