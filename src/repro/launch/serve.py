"""Serving driver — the deployment mode the paper targets.

Two services:

* ``--mode nerf``: the ICARUS use-case. Loads the model into a
  ``PackedPlcore`` (weights packed + RMCM-quantized ONCE at load time),
  renders a full image as ONE XLA dispatch (a ``lax.map`` over ray tiles
  with the fused coarse->importance->fine chain inside — no per-tile host
  sync, no per-image retrace), writes it as PPM, and reports throughput +
  the roofline energy model (uJ/sample next to the paper's 0.174
  uJ/sample ASIC figure).

  Flags: ``--rmcm`` serves through 9-bit RMCM weights; ``--kernel``
  routes the per-pass pipeline through the fused Pallas kernel;
  ``--fuse-two-pass`` (with ``--kernel``) collapses the whole
  coarse->importance->fine chain into ONE Pallas kernel per ray tile —
  coarse weights never leave VMEM;
  ``--ert EPS`` enables Cicero-style early ray termination (rays whose
  transmittance after the coarse pass is < EPS skip the fine-pass MLP;
  under ``--fuse-two-pass`` the kernel skips the fine pass of every ray
  block whose rays all terminated);
  ``--vmem-budget-mb`` sizes the fused kernel's VMEM budget — under
  ``--fuse-two-pass`` BOTH networks' weight stacks stay pinned
  as the working set and the per-ray blocks get the remainder;
  ``--tiled`` falls back to the seed per-tile host loop (the benchmark
  baseline — see benchmarks/plcore_fusion.py for the measured gap).

* ``--mode engine``: the multi-tenant serving engine (repro.serving) —
  one process, many scenes, many concurrent requests. Spins up ``--scenes``
  N model instances behind a ``SceneCache`` (LRU over ``--cache-mb`` MB of
  resident packed weights), drives a fixed-seed Poisson trace of
  ``--requests`` requests (``--rate`` req/s, resolutions drawn from
  ``--hw-mix``, priorities from ``--priority-mix``) through the
  continuous-batching ``RenderEngine`` (``--tile-rays`` per coalesced
  tile), and reports throughput, p50/p95/p99 latency, dispatch savings vs
  the per-request baseline, and cache hit/miss/eviction counters.
  ``--loop open`` replays arrival times faithfully (queueing delay in the
  tail); ``--loop closed`` holds ``--concurrency`` in flight
  (deterministic — the CI mode). Reports split request latency into
  queueing delay vs service time (p50/p95/p99 each).
  ``--pipeline-depth N`` gives the executor N in-flight tile slots
  (default: the engine's, 2 on one host — double-buffered async
  dispatch, the host work of tile k-1 overlaps device compute of tile
  k; 1 under ``--hosts > 1``; depth 1 is the synchronous baseline).
  ``--check`` exits nonzero unless every request completed, the cache
  hit rate is > 0, coalescing issued no more dispatches than the
  per-request baseline, and — with ``--pipeline-depth >= 2`` — the
  framebuffers are bit-identical to a depth=1 rerun of the same trace.
  The counter gate (pipelining actually held >= 2 tiles in flight) is
  additionally enforced under ``--loop closed``, where the engine walk
  is clockless-deterministic. ``--kernel``, ``--fuse-two-pass``,
  ``--rmcm``, ``--ert`` and ``--vmem-budget-mb`` apply to the engine's
  render path exactly as in ``--mode nerf``.

  Fault tolerance (the robustness surface): ``--deadline-ms`` stamps
  every trace request with an SLO deadline (arms admission control +
  expiry), ``--max-queue`` bounds the request queue (admission rejects
  beyond it), ``--degrade-on-overload`` lets backlog switch low-priority
  requests to coarse-only rendering (terminal status ``degraded``), and
  ``--inject-faults`` arms the canonical seeded chaos plan
  (``FaultConfig.chaos(--fault-seed)``): injected dispatch errors,
  NaN/Inf-corrupted tiles, loader failures and stragglers, all recovered
  by the engine's retry -> oracle ladder. The report then carries
  ``goodput``, per-status counts and the full ``robustness`` block.
  Under ``--inject-faults``, ``--check`` additionally gates: every
  request reached a terminal status, at least one fault was actually
  injected, goodput >= 0.75, and every request that ended ``ok`` has a
  framebuffer BIT-IDENTICAL to a clean rerun (fresh cache, no faults) of
  the same trace — recovery reconstructs exact pixels or the gate fails.

  Multi-host: ``--hosts N`` serves through the ``ClusterEngine`` fabric
  — N per-host workers (isolated SceneCache + TileExecutor, each a
  replica on its own group of the process's devices) behind one global
  scheduler with heartbeat
  health states, cross-host tile failover, per-host scene quarantine
  and aggregate SLO admission. ``--host-kill H:T`` kills host H at
  trace time T seconds — or, deterministically, at global dispatch
  count N via ``H:@N`` (the CI form) — and ``--host-slow H:T`` adds
  per-dispatch latency on H from time T. With host events + ``--check``
  the gate additionally requires goodput >= 0.75, every ok-status
  framebuffer bit-identical to a CLEAN SINGLE-HOST rerun of the same
  trace, and — for ``@N`` kills in the closed loop — at least one tile
  provably redispatched across hosts (``cross_host_redispatches``).
  ``--service-prior-ms`` seeds the admission-control service estimate
  so a cold engine under burst load doesn't admit everything and
  mass-expire.

* ``--mode lm``: batched LM inference on any assigned arch (smoke config on
  CPU): prefill a prompt batch, decode N tokens with the KV/state cache.

    PYTHONPATH=src python -m repro.launch.serve --mode nerf --hw 64
    PYTHONPATH=src python -m repro.launch.serve --mode engine --scenes 3 \
        --requests 12 --loop closed --check
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch qwen2-1.5b
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.nerf_icarus import CONFIG as NERF_FULL, tiny as nerf_tiny
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls, render_image_tiled
from repro.data import rays as R
from repro.models.model_zoo import build_model
from repro.models.params import init_params


def write_ppm(path: str, img) -> None:
    """Dependency-free image writer (P6 PPM)."""
    arr = np.asarray(jnp.clip(img, 0.0, 1.0) * 255).astype(np.uint8)
    h, w, _ = arr.shape
    with open(path, "wb") as f:
        f.write(f"P6\n{w} {h}\n255\n".encode())
        f.write(arr.tobytes())


# TPU v5e energy model for the uJ/sample report (per-op energy constants:
# ~1.3 pJ/flop at the chip wall for bf16, ~12 pJ/byte HBM — coarse public
# figures; the *relative* GPU-vs-fused comparison is what matters).
PJ_PER_FLOP = 1.3
PJ_PER_BYTE = 12.0


def nerf_energy_uj_per_sample(cfg, fused: bool) -> float:
    """Roofline energy: flops/sample = 2*params; bytes/sample differ by
    ~100x between fused (rays+pixels only) and unfused (activations to
    HBM)."""
    params_per_net = 595_844 if cfg.trunk_width == 256 else 25_000
    flops = 2.0 * params_per_net
    act_bytes = 4.0 * (cfg.pos_enc_dim + cfg.dir_enc_dim
                       + cfg.trunk_layers * cfg.trunk_width + 4)
    io_bytes = 4.0 * (8.0 / cfg.n_samples + 3.0 / cfg.n_samples)
    bytes_per_sample = io_bytes if fused else act_bytes
    return (flops * PJ_PER_FLOP + bytes_per_sample * PJ_PER_BYTE) * 1e-6


def serve_nerf(args) -> dict:
    from dataclasses import replace

    from repro.kernels import ops as kops

    cfg = NERF_FULL if args.full else nerf_tiny()
    if args.ert > 0.0:
        if args.tiled:
            raise SystemExit("--ert requires the single-dispatch pipeline; "
                             "drop --tiled")
        cfg = replace(cfg, ert_eps=args.ert)
    if args.vmem_budget_mb is not None:
        cfg = replace(cfg, kernel_vmem_budget_mb=args.vmem_budget_mb)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(plcore_decls(cfg), key, "float32")
    if args.ckpt:
        from repro.checkpoint.ckpt import Checkpointer
        state, _ = Checkpointer(args.ckpt).restore()
        params = jax.tree.map(jnp.asarray, state["params"])
    quant = None
    if args.rmcm:
        quant = {"coarse": rmcm.quantize_tree(params["coarse"]),
                 "fine": rmcm.quantize_tree(params["fine"])}

    if args.fuse_two_pass and (args.tiled or not args.kernel):
        raise SystemExit("--fuse-two-pass runs the whole chain in one "
                         "Pallas kernel; it requires --kernel and the "
                         "single-dispatch pipeline (drop --tiled)")

    # load-time work: RMCM quantization + kernel weight packing run ONCE
    # here; every render below reuses the packed layout
    engine = None
    if not args.tiled:
        engine = PackedPlcore(cfg, params, quant=quant,
                              use_kernel=args.kernel,
                              fuse_two_pass=args.fuse_two_pass)
    packs_at_load = kops.pack_count()

    scene = R.SCENES[args.scene]()
    c2w = R.pose_spherical(args.theta, -25.0, scene.radius)
    H = W = args.hw
    ro, rd = R.camera_rays(c2w, H, W, 0.9 * W)

    t0 = time.time()
    if args.tiled:
        img = render_image_tiled(cfg, params, ro, rd, quant=quant,
                                 use_kernel=args.kernel,
                                 rays_per_batch=args.rays_per_batch)
    else:
        img = engine.render_image(ro, rd,
                                  rays_per_batch=args.rays_per_batch)
    img.block_until_ready()
    dt = time.time() - t0
    out = Path(args.out or f"runs/serve_nerf_{args.scene}.ppm")
    out.parent.mkdir(parents=True, exist_ok=True)
    write_ppm(str(out), img)
    n_rays = H * W
    n_samples = n_rays * (cfg.n_coarse + cfg.n_coarse + cfg.n_fine)
    stats = {
        "image": str(out), "hw": H, "rays": n_rays,
        "samples": n_samples, "wall_s": round(dt, 3),
        "rays_per_s": round(n_rays / dt, 1),
        "samples_per_s": round(n_samples / dt, 1),
        "uj_per_sample_model_fused": nerf_energy_uj_per_sample(cfg, True),
        "uj_per_sample_model_unfused": nerf_energy_uj_per_sample(cfg, False),
        "rmcm": bool(args.rmcm), "kernel": bool(args.kernel),
        "pipeline": ("tiled" if args.tiled else
                     "two_pass_fused" if args.fuse_two_pass else
                     "single_dispatch"),
        "ert_eps": cfg.ert_eps,
        "weight_packs_since_load": kops.pack_count() - packs_at_load,
    }
    print(json.dumps(stats, indent=2))
    return stats


def _parse_host_events(args):
    """``--host-kill H:T`` / ``--host-slow H:T`` specs -> HostEvents.
    T is seconds from engine start, or ``@N`` for "when the global
    dispatch counter reaches N" (clockless-deterministic, the CI form)."""
    from repro.serving import HostEvent

    def parse(spec, kind):
        host, sep, at = spec.partition(":")
        if not sep or not at:
            raise SystemExit(f"--host-{kind}: expected HOST:AT_S or "
                             f"HOST:@DISPATCHES, got {spec!r}")
        at_s = at_dispatch = None
        if at.startswith("@"):
            at_dispatch = int(at[1:])
        else:
            at_s = float(at)
        return HostEvent(kind, int(host), at_s=at_s,
                         at_dispatch=at_dispatch,
                         extra_s=args.host_slow_extra_ms / 1e3)

    return ([parse(s, "kill") for s in args.host_kill]
            + [parse(s, "slow") for s in args.host_slow])


def make_scene_loader(cfg, scene_ids, *, seed: int = 0,
                      scene_bias: float = 0.0, rmcm_weights: bool = False,
                      use_kernel: bool = False, fuse_two_pass: bool = False,
                      device=None):
    """The engine's scene loader: scene id -> ``PackedPlcore`` with one
    synthetic model per id (a distinct param draw from ``seed`` + the
    id's index stands in for a distinct trained checkpoint). ``device``
    puts the scene on one device (a replica's own chip)."""
    scene_ids = list(scene_ids)

    def load_scene(scene_id: str) -> PackedPlcore:
        idx = scene_ids.index(scene_id)
        params = init_params(plcore_decls(cfg),
                             jax.random.PRNGKey(seed + idx), "float32")
        if scene_bias:
            # shift the sigma-head bias: negative values carve real
            # empty space into the synthetic scenes (the canonical
            # mixed scene for the adaptive-sampling gates is -0.5)
            for net in params:
                params[net]["sigma"]["b"] = (
                    params[net]["sigma"]["b"] + scene_bias)
        quant = None
        if rmcm_weights:
            quant = {"coarse": rmcm.quantize_tree(params["coarse"]),
                     "fine": rmcm.quantize_tree(params["fine"])}
        return PackedPlcore(cfg, params, quant=quant, use_kernel=use_kernel,
                            fuse_two_pass=fuse_two_pass, device=device)
    return load_scene


def host_devices(device_groups) -> list:
    """The device each host's scenes live on: its group's first device.
    None for a single host (JAX's default device)."""
    if len(device_groups) < 2:
        return [None] * len(device_groups)
    return [g[0] for g in device_groups]


def serve_engine(args) -> dict:
    """Multi-tenant serving: N scenes behind an LRU weight cache, a
    Poisson request trace through the coalescing RenderEngine — or,
    with ``--hosts > 1``, through the multi-host ClusterEngine fabric."""
    from dataclasses import replace

    from repro.serving import (ClusterEngine, FaultConfig, FaultPlan,
                               RenderEngine, SceneCache, split_devices)
    from repro.serving import loadgen
    from repro.serving.engine import DEFAULT_PIPELINE_DEPTH

    cfg = NERF_FULL if args.full else nerf_tiny()
    if args.ert > 0.0:
        cfg = replace(cfg, ert_eps=args.ert)
    if args.vmem_budget_mb is not None:
        cfg = replace(cfg, kernel_vmem_budget_mb=args.vmem_budget_mb)
    if args.fuse_two_pass and not args.kernel:
        raise SystemExit("--fuse-two-pass requires --kernel")
    budget_classes = None
    if args.adaptive_sampling:
        # ASDR rides the fused-kernel single-host path: the bit-identity
        # gates need one engine's deterministic memo walk
        if not (args.kernel and args.fuse_two_pass):
            raise SystemExit("--adaptive-sampling rides the fused "
                             "two-pass kernel's dead-row skip; it "
                             "requires --kernel --fuse-two-pass")
        for flag, name in ((args.degrade_on_overload,
                            "--degrade-on-overload"),
                           (args.inject_faults, "--inject-faults"),
                           (args.hosts > 1, "--hosts > 1")):
            if flag:
                raise SystemExit(f"--adaptive-sampling is a single-host "
                                 f"feature — incompatible with {name}")
        if args.budget_classes != "auto":
            budget_classes = tuple(
                int(b) for b in args.budget_classes.split(","))
    if args.hosts < 1:
        raise SystemExit(f"--hosts must be >= 1, got {args.hosts}")
    if args.pipeline_depth is None:
        # each engine's own default: the RenderEngine pipelines, the
        # ClusterEngine (--hosts > 1) keeps its synchronous default
        args.pipeline_depth = 1 if args.hosts > 1 else DEFAULT_PIPELINE_DEPTH
    host_events = _parse_host_events(args)
    if host_events and args.hosts < 2:
        raise SystemExit("--host-kill/--host-slow need --hosts >= 2 "
                         "(a single-host engine has no pool)")

    # the process's devices split into contiguous groups (the
    # xla_force_host_platform_device_count CI idiom); each host is a
    # replica on its group's device: its scenes and tiles live there, not
    # all on the default device
    device_groups = split_devices(args.hosts)
    devices = host_devices(device_groups)

    scene_ids = [f"scene{i}" for i in range(args.scenes)]

    def make_loader(device=None):
        return make_scene_loader(
            cfg, scene_ids, seed=args.seed, scene_bias=args.scene_bias,
            rmcm_weights=args.rmcm, use_kernel=args.kernel,
            fuse_two_pass=args.fuse_two_pass, device=device)

    load_scene = make_loader()
    plan = (FaultPlan(FaultConfig.cluster_chaos(args.fault_seed)
                      if args.hosts > 1
                      else FaultConfig.chaos(args.fault_seed))
            if args.inject_faults else None)
    prior_s = (None if args.service_prior_ms is None
               else args.service_prior_ms / 1e3)

    # --trace-out arms lifecycle tracing on the PRIMARY engine only:
    # reference reruns stay untraced, so the exported span stream
    # describes exactly one run and the integrity gate can hold every
    # dispatched tile to a terminal scatter/drop
    tracer = None
    if args.trace_out:
        from repro.obs import SpanTracer
        tracer = SpanTracer(sample_every=args.trace_sample)

    def make_engine(depth, *, chaos=False, use_cache=None, adaptive=None):
        # reference reruns are always CLEAN and SINGLE-HOST: no fault
        # plan (reusing the primary plan would continue its RNG streams,
        # not replay them), a fresh cache with the unwrapped loader, no
        # host pool — the bit-identity anchor every multi-host/faulted
        # run is compared against
        if adaptive is None:
            adaptive = args.adaptive_sampling
        kw = dict(tile_rays=args.tile_rays, pipeline_depth=depth,
                  max_queue=args.max_queue,
                  degrade_on_overload=args.degrade_on_overload,
                  faults=plan if chaos else None,
                  tile_service_prior_s=prior_s,
                  tracer=tracer if chaos else None)
        if adaptive:
            # adaptive kwargs only when armed: ClusterEngine (hosts > 1,
            # incompatible anyway) never sees them, and an adaptive-off
            # engine is constructed EXACTLY like the pre-ASDR one
            kw.update(adaptive_sampling=True,
                      budget_classes=budget_classes,
                      memo_mb=args.memo_mb)
        if chaos and args.hosts > 1:
            caches = [SceneCache(plan.wrap_loader(make_loader(dev))
                                 if plan else make_loader(dev),
                                 capacity_mb=args.cache_mb)
                      for dev in devices]
            return ClusterEngine(caches, device_groups=device_groups, **kw)
        if use_cache is None:
            use_cache = SceneCache(
                plan.wrap_loader(load_scene)
                if plan is not None and chaos else load_scene,
                capacity_mb=args.cache_mb)
        return RenderEngine(use_cache, **kw)

    engine = make_engine(args.pipeline_depth, chaos=True)
    deadline_choices = ((None,) if args.deadline_ms is None
                        else (args.deadline_ms / 1e3,))
    trace = loadgen.poisson_trace(
        args.requests, scene_ids, rate_rps=args.rate,
        hw_choices=tuple(int(h) for h in args.hw_mix.split(",")),
        priorities=tuple(int(p) for p in args.priority_mix.split(",")),
        deadline_choices=deadline_choices, seed=args.seed)
    stats = loadgen.run_trace(engine, trace, mode=args.loop,
                              concurrency=args.concurrency,
                              host_events=host_events or None)
    if tracer is not None:
        # flush: deadline expiry can leave drained-but-unscattered slots
        # behind once pending hits 0 — drain closes their span chains so
        # the integrity gate sees every dispatched tile reach a terminal
        engine.drain()
    trace_integrity = None
    if args.trace_out:
        from repro.obs.export import validate_trace, write_chrome_trace
        tpath = Path(args.trace_out)
        tpath.parent.mkdir(parents=True, exist_ok=True)
        write_chrome_trace(tracer, str(tpath))
        trace_integrity = validate_trace(tracer)
        stats_tr = dict(tracer.summary())
        stats_tr["integrity"] = trace_integrity
        stats_tr["trace_out"] = str(tpath)
    if args.metrics_out:
        from repro.obs.export import prometheus_text
        from repro.obs.metrics import global_registry
        mpath = Path(args.metrics_out)
        mpath.parent.mkdir(parents=True, exist_ok=True)
        mpath.write_text(prometheus_text(engine.registry,
                                         global_registry()))
    stats = {"scenes": args.scenes, "tile_rays": args.tile_rays,
             "kernel": bool(args.kernel),
             "fuse_two_pass": bool(args.fuse_two_pass),
             "ert_eps": cfg.ert_eps,
             "pipeline_depth": args.pipeline_depth,
             "inject_faults": bool(args.inject_faults),
             "hosts": args.hosts,
             "host_events": [f"{e.kind}:{e.host}" for e in host_events],
             "deadline_ms": args.deadline_ms, **stats}
    if args.trace_out:
        stats["observability"] = stats_tr
    if args.metrics_out:
        stats.setdefault("observability", {})["metrics_out"] = \
            str(args.metrics_out)
    if args.adaptive_sampling:
        stats["adaptive_sampling"] = True
        stats["sampling"] = engine.sampling_report()
    print(json.dumps(stats, indent=2))
    if args.check:
        if stats["requests_completed"] != args.requests:
            raise SystemExit(f"engine check: {stats['requests_completed']}"
                             f"/{args.requests} requests completed")
        if stats["cache"]["hit_rate"] <= 0.0:
            raise SystemExit("engine check: scene-cache hit rate is 0")
        if stats["dispatch_savings"] < 0 and not args.adaptive_sampling:
            # budget bucketing deliberately splits a request's rays
            # across per-class tiles, so under --adaptive-sampling the
            # dispatch COUNT may exceed the per-request baseline — the
            # adaptive figure of merit is skipped fine samples (gated
            # below), not tile count
            raise SystemExit("engine check: coalescing issued MORE "
                             "dispatches than the per-request baseline")
        if trace_integrity is not None:
            # span-chain integrity: every dispatched tile must have
            # walked a legal lifecycle to a terminal scatter/drop, and
            # every traced submit must map to exactly one terminal
            # request span — an orphan chain means lost pixels
            if trace_integrity["dispatched_tiles"] < 1:
                raise SystemExit("engine check: --trace-out armed but "
                                 "the trace recorded no dispatched tiles")
            if not trace_integrity["ok"]:
                raise SystemExit(
                    "engine check: trace integrity FAILED:\n  "
                    + "\n  ".join(trace_integrity["errors"]))
        # gates below rerun the trace on a reference engine and compare
        # framebuffers bit-for-bit (rids align: every run submits in
        # trace order; per-ray independence makes images depth-invariant
        # even when the tile partition differs).
        # Only requests that ended ``ok`` in BOTH runs are compared —
        # a degraded/partial/rejected image is policy-dependent, not a
        # determinism anchor
        def rerun_and_compare(depth, label):
            ref = make_engine(depth)
            loadgen.run_trace(ref, trace, mode=args.loop,
                              concurrency=args.concurrency)
            n_cmp = 0
            for rid, res in engine.completed.items():
                if res.status != "ok":
                    continue
                refres = ref.completed.get(rid)
                if refres is None or refres.status != "ok":
                    continue
                n_cmp += 1
                if not np.array_equal(res.image, refres.image):
                    raise SystemExit(f"engine check: image for request "
                                     f"{rid} differs from the {label} "
                                     f"reference render")
            if n_cmp == 0:
                raise SystemExit(f"engine check: no ok-status requests to "
                                 f"compare against the {label} reference")
            return ref

        if args.inject_faults:
            rb = stats["robustness"]
            if rb["faults_injected"]["total_injected"] < 1:
                raise SystemExit("engine check: --inject-faults armed but "
                                 "the plan injected nothing — the chaos "
                                 "smoke exercised no recovery path")
            if rb["goodput"] is None or rb["goodput"] < 0.75:
                raise SystemExit(f"engine check: chaos goodput "
                                 f"{rb['goodput']} < 0.75")
            # recovery must reconstruct exact pixels: every request that
            # ended ok under faults is bit-identical to a clean rerun
            rerun_and_compare(args.pipeline_depth, "clean (no-fault)")

        if host_events:
            # multi-host gates: the run survived its scheduled host
            # events (goodput), every ok request's pixels are
            # bit-identical to a CLEAN SINGLE-HOST rerun, and a
            # dispatch-count kill provably exercised cross-host failover
            cl = stats["cluster"]
            rb = stats["robustness"]
            if rb["goodput"] is None or rb["goodput"] < 0.75:
                raise SystemExit(f"engine check: goodput {rb['goodput']} "
                                 f"< 0.75 under host events")
            if not args.inject_faults:
                # (with --inject-faults the identical comparison already
                # ran above — make_engine refs are single-host either way)
                rerun_and_compare(args.pipeline_depth, "clean single-host")
            kills = [e for e in host_events if e.kind == "kill"]
            if kills and cl["host_kills"] < 1:
                raise SystemExit("engine check: --host-kill armed but no "
                                 "host actually died")
            deterministic_kill = (args.loop == "closed" and any(
                e.at_dispatch is not None for e in kills))
            if deterministic_kill and cl["cross_host_redispatches"] < 1:
                raise SystemExit(
                    "engine check: host killed mid-run but no tile was "
                    "redispatched across hosts (cross_host_redispatches "
                    "= 0) — failover did not engage")

        # the occupancy gate compares counters across runs, which is
        # only deterministic in the clockless closed loop
        # (open-loop arrival timing changes the tile partition run to
        # run); the bit-identity comparisons hold in either mode
        deterministic = args.loop == "closed"
        if args.pipeline_depth > 1:
            if deterministic and stats["engine"]["max_in_flight"] < 2:
                raise SystemExit("engine check: pipeline_depth "
                                 f"{args.pipeline_depth} never had 2 "
                                 "tiles in flight — async dispatch "
                                 "pipelining did not engage")
            rerun_and_compare(1, "synchronous depth=1")
        if args.adaptive_sampling:
            # adaptive gates: every tile went through the adaptive path,
            # the trunk memo actually served hits, every budget class was
            # exercised by real rays, and an adaptive-OFF rerun of the
            # same trace is bit-identical to the synchronous current
            # pipeline — the flag off must change NOTHING
            sp = stats["sampling"]
            if sp["adaptive_tiles"] < 1:
                raise SystemExit("engine check: --adaptive-sampling armed "
                                 "but no tile took the adaptive path")
            if sp["memo_hits"] < 1:
                raise SystemExit("engine check: adaptive sampling served "
                                 "zero trunk-memo hits — memoization "
                                 "never engaged")
            exercised = set()
            n_classes = 0
            for r in sp["scenes"].values():
                n_classes = max(n_classes, len(r["budgets"]))
                exercised |= {b for b, n in r["budget_rays"].items()
                              if n > 0}
            if len(exercised) < n_classes:
                raise SystemExit(
                    f"engine check: only budget classes "
                    f"{sorted(exercised, key=int)} of {n_classes} "
                    f"exercised — the calibration edges starve classes "
                    f"(is --scene-bias set for a mixed scene?)")
            off1 = make_engine(args.pipeline_depth, adaptive=False)
            loadgen.run_trace(off1, trace, mode=args.loop,
                              concurrency=args.concurrency)
            off2 = make_engine(1, adaptive=False)
            loadgen.run_trace(off2, trace, mode=args.loop,
                              concurrency=args.concurrency)
            n_cmp = 0
            for rid, res in off1.completed.items():
                if res.status != "ok":
                    continue
                r2 = off2.completed.get(rid)
                if r2 is None or r2.status != "ok":
                    continue
                n_cmp += 1
                if not np.array_equal(res.image, r2.image):
                    raise SystemExit(
                        f"engine check: adaptive-off image for request "
                        f"{rid} differs from the synchronous current-"
                        f"pipeline reference — the OFF path regressed")
            if n_cmp == 0:
                raise SystemExit("engine check: no ok-status requests to "
                                 "compare for the adaptive-off gate")
        print("engine check OK")
    return stats


def serve_lm(args) -> dict:
    cfg = smoke_config(args.arch) if not args.full else get_config(args.arch)
    model = build_model(cfg)
    params = init_params(model.param_decls(), jax.random.PRNGKey(args.seed),
                         cfg.param_dtype)
    B, S = args.batch, args.prompt_len
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.ones((B, cfg.vlm.n_patches, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jnp.ones((B, cfg.encdec.enc_seq, cfg.d_model))

    cap = (S + args.decode_tokens + 1
           + getattr(model, "prefix_len", lambda: 0)())
    prefill = jax.jit(lambda p, b: model.prefill(p, b, cap))
    decode = jax.jit(model.decode, donate_argnums=(1,))

    t0 = time.time()
    cache, logits = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    toks = [tok]
    t0 = time.time()
    for i in range(args.decode_tokens):
        cache, logits = decode(params, cache, tok, jnp.asarray(S + i, jnp.int32))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        toks.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    out = {
        "arch": args.arch, "batch": B, "prompt_len": S,
        "prefill_s": round(t_prefill, 3),
        "decode_tokens": args.decode_tokens,
        "decode_tok_per_s": round(args.decode_tokens * B / max(t_decode, 1e-9), 1),
        "sample_tokens": np.asarray(jnp.concatenate(toks, 1)[0, :8]).tolist(),
    }
    print(json.dumps(out, indent=2))
    return out


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["nerf", "engine", "lm"],
                    default="nerf")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # nerf
    ap.add_argument("--scene", default="blobs", choices=sorted(R.SCENES))
    ap.add_argument("--hw", type=int, default=64)
    ap.add_argument("--theta", type=float, default=45.0)
    ap.add_argument("--rays-per-batch", type=int, default=4096)
    ap.add_argument("--rmcm", action="store_true")
    ap.add_argument("--kernel", action="store_true")
    ap.add_argument("--ert", type=float, default=0.0,
                    help="early-ray-termination transmittance threshold "
                         "(0 = exact two-pass render)")
    ap.add_argument("--fuse-two-pass", action="store_true",
                    help="run the whole coarse->importance->fine chain as "
                         "ONE Pallas kernel per ray tile (requires "
                         "--kernel; with --ert, ray blocks whose rays "
                         "all terminated skip the fine MLP)")
    ap.add_argument("--tiled", action="store_true",
                    help="seed per-tile host loop instead of the "
                         "single-dispatch pipeline")
    ap.add_argument("--vmem-budget-mb", type=float, default=None,
                    help="fused-kernel VMEM budget: the weight "
                         "working set (both networks under "
                         "--fuse-two-pass) stays pinned and the "
                         "activation slab gets the remainder")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--out", default=None)
    # engine (multi-tenant serving)
    ap.add_argument("--scenes", type=int, default=3,
                    help="number of resident-candidate scene models")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--tile-rays", type=int, default=512,
                    help="rays per coalesced dispatch tile")
    ap.add_argument("--cache-mb", type=float, default=256.0,
                    help="scene-cache capacity (MB of packed weights)")
    ap.add_argument("--loop", choices=["open", "closed"], default="open")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed-loop in-flight request count")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="executor in-flight tile slots (default: the "
                         "engine's, 2 on one host and 1 with --hosts > 1): "
                         ">= 2 overlaps host commit/dispatch/copy-back/"
                         "scatter with device compute via jax async "
                         "dispatch, 1 = synchronous dispatch->block->"
                         "scatter (the bit-identity baseline)")
    ap.add_argument("--adaptive-sampling", action="store_true",
                    help="ASDR: per-scene density calibration probe at "
                         "scene load, per-ray fine-sample budget classes "
                         "(tiles coalesce (scene, budget)-pure), and a "
                         "cross-ray trunk memo whose fully-empty resident "
                         "rays enter the fused kernel as dead rows "
                         "(requires --kernel --fuse-two-pass; "
                         "single-host only)")
    ap.add_argument("--budget-classes", default="auto", metavar="N,N,N",
                    help="comma list of ascending fine-sample budgets for "
                         "the adaptive classes (default 'auto': derived "
                         "from the config's n_fine, e.g. 8,32,64 for 128)")
    ap.add_argument("--memo-mb", type=float, default=32.0,
                    help="per-scene trunk-memo capacity (MB, LRU; an "
                         "auxiliary resident of the scene's cache entry "
                         "counted against --cache-mb)")
    ap.add_argument("--scene-bias", type=float, default=0.0,
                    help="shift every synthetic scene's sigma-head bias; "
                         "negative values carve real empty space (the "
                         "canonical mixed scene for adaptive gates is "
                         "-0.5)")
    ap.add_argument("--hw-mix", default="16,32",
                    help="comma list of request resolutions")
    ap.add_argument("--priority-mix", default="0",
                    help="comma list of request priorities (higher wins)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO deadline (ms from submit): arms "
                         "admission control (reject when predicted "
                         "queueing delay exceeds it) and expiry "
                         "(partial/expired terminal statuses)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the engine request queue; submissions "
                         "beyond it are terminally rejected at admission")
    ap.add_argument("--degrade-on-overload", action="store_true",
                    help="under backlog, switch low-priority requests to "
                         "coarse-only rendering (terminal status "
                         "'degraded', flagged in stats) instead of "
                         "queueing them at full quality")
    ap.add_argument("--inject-faults", action="store_true",
                    help="arm the canonical seeded chaos plan "
                         "(FaultConfig.chaos): injected dispatch errors, "
                         "corrupted tiles, loader failures, stragglers — "
                         "exercises the retry -> oracle recovery ladder")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --inject-faults chaos plan")
    ap.add_argument("--hosts", type=int, default=1,
                    help="serve through the multi-host ClusterEngine "
                         "fabric: N per-host workers (isolated "
                         "SceneCache + TileExecutor, each a replica on "
                         "its own device group) behind one global "
                         "scheduler with heartbeats, cross-host failover, "
                         "per-host scene quarantine and aggregate SLO "
                         "admission")
    ap.add_argument("--host-kill", action="append", default=[],
                    metavar="HOST:AT",
                    help="kill host HOST at AT seconds from start, or at "
                         "global dispatch count N with HOST:@N (the "
                         "deterministic CI form); repeatable; requires "
                         "--hosts >= 2")
    ap.add_argument("--host-slow", action="append", default=[],
                    metavar="HOST:AT",
                    help="from AT (seconds or @dispatches), every "
                         "dispatch on HOST pays --host-slow-extra-ms of "
                         "added latency (the health layer should flag "
                         "it suspect); repeatable")
    ap.add_argument("--host-slow-extra-ms", type=float, default=50.0,
                    help="added per-dispatch latency for --host-slow")
    ap.add_argument("--service-prior-ms", type=float, default=None,
                    help="seed the SLO admission service estimate "
                         "(per-tile) before any tile has drained — "
                         "closes the cold-start hole where a burst at "
                         "an empty engine was admitted wholesale and "
                         "then mass-expired")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="arm per-tile lifecycle tracing on the primary "
                         "engine and write a Chrome trace-event JSON "
                         "(Perfetto / chrome://tracing loadable; one "
                         "process track per host, one thread track per "
                         "executor slot); with --check, additionally "
                         "gates span-chain integrity — every dispatched "
                         "tile must reach a terminal scatter/drop")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the merged metrics registries (engine + "
                         "process-global kernel counters) in Prometheus "
                         "text exposition format after the run")
    ap.add_argument("--trace-sample", type=int, default=1, metavar="N",
                    help="sample request lifecycle chains: trace 1 in N "
                         "requests (tile/cache/host records stay "
                         "always-on, so the integrity gate still covers "
                         "100%% of dispatched tiles)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless all requests completed, "
                         "cache hit rate > 0, and coalescing saved "
                         "dispatches (the CI smoke gate); with "
                         "--inject-faults additionally gates goodput >= "
                         "0.75, >= 1 injected fault, and ok-status "
                         "bit-identity vs a clean rerun")
    # lm
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-tokens", type=int, default=16)
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return {"nerf": serve_nerf, "engine": serve_engine,
            "lm": serve_lm}[args.mode](args)


if __name__ == "__main__":
    main()
