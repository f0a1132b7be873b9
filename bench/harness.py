"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one cell is found by name:
``BENCHMARK.json`` names the cell's configuration, traffic mix and
chips; ``bench/traffic/<traffic>.json`` holds the mix's parameters and
``bench/workloads/<cell>.json`` the cell's own (``params`` overlay the
mix's; ``limits`` are the correctness limits); the configuration file
names its plain reference, ``bench/references/<reference>.py``; and
every metric, end to end or per layer, is read by
``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number or
``None`` when there is nothing to read.

A run builds the scenes from the seed, warms the shapes the cell's
traffic uses, drives the served entry (``RenderEngine.submit``/``step``)
for the window, and then compares pixels the window delivered with the
reference (see ``check``). Every cell runs on one chip: a cell across
chips needs its own path here (``repro.serving.ClusterEngine``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CACHE_DIR = REPO / ".jax_cache"          # fixed: the path keys the cache
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"   # JAX reads it; it wins if set
DEFAULT_CHECK = {"requests": 32, "pixels": 32768}
# A traced run's window. The profiler never returned from 51 s traces of
# the RMCM kernel (twice, on a TPU v5 lite); 35 s traces of it did.
TRACE_WINDOW_S = 30.0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell ----
@dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration file, as run
    traffic: dict                # the mix's parameters with the cell's
    limits: dict                 # correctness limits
    end_to_end: List[dict]
    per_layer: List[dict]


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = REPO / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path.name} "
                       f"(cells: {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((REPO / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    own = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    traffic = {**traffic, **own.get("params", {})}
    return Cell(name, int(w["chips"]), config, traffic,
                own.get("limits", {}),
                [m for m in spec["end_to_end"] if _in_cell(m, name)],
                [m for m in spec["per_layer"] if _in_cell(m, name)])


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    return _load_module(BENCH / "references" / f"{config['reference']}.py")


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py").read


# ------------------------------------------------------- the system ----
def nerf_config(config: dict):
    """The program's ``NerfConfig`` for a configuration file."""
    from repro.configs.nerf_icarus import NerfConfig
    fields = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in config["nerf"].items()}
    return dataclasses.replace(NerfConfig(), **fields)


def program_params(net: dict, trunk_layers: int) -> dict:
    """One network's weights in the program's parameter tree."""
    return {"trunk": {f"l{i}": net[f"trunk.l{i}"]
                      for i in range(trunk_layers)},
            **{k: net[k] for k in ("sigma", "feat", "color0", "rgb")}}


def scene_weights(ref, arch: dict, seed: int, scene: int):
    import jax
    return ref.init_weights(arch, jax.random.fold_in(ref.seed_key(seed),
                                                     scene))


def build_engine(cell: Cell, seed: int, device, tracer=None):
    """The scene cache on ``device`` and the engine over it, at the
    program's own engine settings. Returns (engine, cache)."""
    from repro.core import rmcm
    from repro.core.pipeline import PackedPlcore
    from repro.serving import RenderEngine, SceneCache

    cfg = nerf_config(cell.config)
    ref = reference_module(cell.config)
    arch = cell.config["nerf"]
    fmt = cell.config["weights"]
    scene_ids = [f"scene{i}" for i in range(cell.traffic["scenes"])]

    def load(scene_id: str):
        w = scene_weights(ref, arch, seed, scene_ids.index(scene_id))
        params = {n: program_params(w[n], arch["trunk_layers"])
                  for n in ("coarse", "fine")}
        quant = None
        if fmt == "rmcm9":
            quant = {n: rmcm.quantize_tree(params[n]) for n in params}
        return PackedPlcore(cfg, params, quant=quant, use_kernel=True,
                            fuse_two_pass=True, device=device)

    cache = SceneCache(load)
    return RenderEngine(cache, tracer=tracer), cache


def warm_up(cell: Cell, engine, cache) -> None:
    """Load the scenes resident before the window and compile what the
    window will run: the tile program, and the camera-ray programs the
    engine builds for each frame size of the mix."""
    import jax

    from bench import loadgen
    from repro.data import rays as R

    tile = np.zeros((engine.tile_rays, 3), np.float32)
    tile[:, 2] = 1.0
    for s in range(cell.traffic.get("resident", cell.traffic["scenes"])):
        cache.get(f"scene{s}")
    pp = cache.get("scene0")
    jax.block_until_ready(pp.render_tile(pp.commit(tile), pp.commit(tile)))
    for hw in sorted({c["hw"] for c in loadgen.classes(cell.traffic)}):
        ro, rd = R.camera_rays(R.pose_spherical(30.0, -25.0, 4.0), hw, hw,
                               0.9 * hw)
        np.asarray(ro), np.asarray(rd)


def robustness(engine) -> int:
    """Tiles the executor had to retry or recover: the timed path is the
    kernel only where this is 0."""
    rb = engine.robustness()
    return int(sum(rb[k] for k in ("dispatch_errors", "oracle_fallbacks",
                                   "corrupt_tiles", "tile_retries")))


# ------------------------------------------------------------- checks ----
def sample(window, cell: Cell, seed: int) -> List[tuple]:
    """[(record, pixel indices)]: up to ``check.requests`` delivered
    requests, drawn from the seed with the largest frame always among
    them, and up to ``check.pixels`` pixels split evenly over them."""
    chk = {**DEFAULT_CHECK, **cell.traffic.get("check", {})}
    done = [r for r in window.records if r.delivered]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 7])
    order = list(rng.permutation(len(done)))
    big = max(range(len(done)), key=lambda i: done[i].spec.hw)
    order.remove(big)
    picked = [done[i] for i in [big] + order[:chk["requests"] - 1]]
    per = max(1, chk["pixels"] // len(picked))
    out = []
    for r in picked:
        n = r.spec.hw ** 2
        out.append((r, np.sort(rng.choice(n, min(per, n), replace=False))))
    return out


def reference_pixels(cell: Cell, seed: int, picked: List[tuple],
                     precision: str = "highest") -> List[np.ndarray]:
    """The reference's colours of the picked pixels, scene by scene."""
    ref = reference_module(cell.config)
    arch = cell.config["nerf"]
    out: List[Optional[np.ndarray]] = [None] * len(picked)
    for scene in sorted({r.spec.scene for r, _ in picked}):
        idx = [i for i, (r, _) in enumerate(picked) if r.spec.scene == scene]
        rays = [ref.camera_rays(picked[i][0].spec.theta,
                                picked[i][0].spec.phi,
                                picked[i][0].spec.radius,
                                picked[i][0].spec.hw, picked[i][1])
                for i in idx]
        w = ref.served_weights(scene_weights(ref, arch, seed, scene),
                               cell.config["weights"])
        rgb = ref.render(arch, w, np.concatenate([o for o, _ in rays]),
                         np.concatenate([d for _, d in rays]), precision)
        off = 0
        for i in idx:
            n = len(picked[i][1])
            out[i] = rgb[off:off + n]
            off += n
    return out


def pixel_gaps(cell: Cell, seed: int, window, images: Dict[int, np.ndarray],
               precision: str = "highest") -> np.ndarray:
    """Per sampled pixel, the widest gap over its channels between the
    delivered colour and the reference's (inf where not finite)."""
    picked = sample(window, cell, seed)
    if not picked:
        return np.zeros(0)
    got = np.concatenate([images[r.rid].reshape(-1, 3)[px]
                          for r, px in picked])
    want = np.concatenate(reference_pixels(cell, seed, picked, precision))
    gap = np.abs(got - want).max(axis=-1)
    return np.where(np.isfinite(gap), gap, np.inf)


# Statistics of the per-pixel gaps. A cell's ``limits`` name the ones it
# compares; the rest are printed beside them.
GAP_STATS = {
    "mean_abs_err": lambda g: float(g.mean()),
    "p50_abs_err": lambda g: float(np.percentile(g, 50)),
    "p99_abs_err": lambda g: float(np.percentile(g, 99)),
    "max_abs_err": lambda g: float(g.max()),
    "share_over_1e-3": lambda g: float((g > 1e-3).mean()),
    "share_over_1e-2": lambda g: float((g > 1e-2).mean()),
}


def gap_summary(gaps: np.ndarray) -> str:
    if not gaps.size:
        return "no pixels"
    return " ".join(f"{k} {f(gaps)}" for k, f in GAP_STATS.items()) + \
        f" over {gaps.size}"


def unexplained(r) -> bool:
    """A request that counts against ``correct``: due and never answered,
    or answered with a failure that no deadline of its own explains."""
    if r.status is None:
        return r.due is not None
    return not r.delivered and r.spec.deadline_ms is None


def check(cell: Cell, window, gaps: np.ndarray, faults: int) -> dict:
    """The numbers that decide ``correct``, each with its limit: the
    statistics of the gaps between delivered pixels and the reference's
    that the cell's ``limits`` name, pixels compared, due requests never
    answered, and tiles the executor had to recover."""
    unanswered = sum(1 for r in window.records if unexplained(r))
    out = {name: {"value": GAP_STATS[name](gaps) if gaps.size else math.inf,
                  "limit": limit}
           for name, limit in cell.limits.items()
           if name != "pixels_checked"}
    out.update({
        "pixels_checked": {"value": int(gaps.size),
                           "at_least": cell.limits.get("pixels_checked",
                                                       1024)},
        "unanswered": {"value": unanswered, "limit": 0},
        "recovered_tiles": {"value": faults, "limit": 0},
    })
    return out


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        if "limit" in c:
            ok &= c["value"] <= c["limit"]
        else:
            ok &= c["value"] >= c["at_least"]
    return bool(ok)


# ---------------------------------------------------------------- run ----
@dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    window: object                       # loadgen.Window
    chips: int
    setup_s: float
    stats: dict                          # engine counters over the window
    spans: List[tuple]                   # (name, t0, t1) engine spans
    trace: Optional[object]              # trace.Summary of --trace 1
    peak: dict
    arch: dict
    weight_format: str
    tile_rays: int
    gave_up_at: float                    # when the run stopped waiting


def _host_waits() -> tuple:
    """(CPU seconds stolen from this machine by its hypervisor, summed
    over its CPUs; involuntary context switches of this process): a stall
    with the process idle and both rising is the host's, not the
    program's."""
    import resource
    with open("/proc/stat") as f:
        steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    return steal, resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw


@contextmanager
def _gc_timer(pauses: List[float]):
    """Appends the length of every garbage collection inside the block."""
    start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)


@contextmanager
def _profiling(enabled: bool):
    import jax
    if not enabled:
        yield None
        return
    d = tempfile.mkdtemp(prefix="bench-profile-")
    # The reading needs the device's ops and the bench.* annotations only.
    # JAX's default also traces every Python call: in a 51 s window that
    # swamps the host and once crashed the profiler.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache: in ``$JAX_COMPILATION_CACHE_DIR``
    where that is set, else in the checkout's fixed ``.jax_cache/``."""
    import jax
    if not os.environ.get(CACHE_ENV):
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def is_kernel(name: str) -> bool:
    """A fused PLCore kernel call among a trace's device operations (the
    tile program's one Mosaic custom call)."""
    return "tpu_custom_call" in name


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, grace_s: float = 60.0) -> dict:
    """One run on ``devices``: set-up, the window, the reading of the
    metrics and the comparison with the reference. Returns the result.
    The program runs at the configuration's matmul precision (JAX's
    default on a TPU rounds f32 operands to bfloat16)."""
    import jax

    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        return _run(cell, seed, seconds, trace, devices, t_start, grace_s)


def _run(cell, seed, seconds, trace, devices, t_start, grace_s) -> dict:
    import jax

    from bench import loadgen, roofline
    from bench import trace as tr
    from repro.obs.trace import SpanTracer
    from repro.serving import RenderRequest

    devs = devices[:cell.chips]
    kind = devs[0].device_kind
    peak = roofline.peaks(kind) if devs[0].platform == "tpu" else {}
    tracer = SpanTracer(capacity=1 << 20) if trace else None
    engine, cache = build_engine(cell, seed, devs[0], tracer)
    warm_up(cell, engine, cache)

    # JAX's own timed events (tracing, compiling, cache reads): none
    # belongs inside the window, and one that does names a stall's cause
    events: List[tuple] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: events.append((time.perf_counter(), ev, dur)))

    def make_request(spec):
        return RenderRequest(
            f"scene{spec.scene}", hw=spec.hw, theta=spec.theta,
            phi=spec.phi, radius=spec.radius, priority=spec.priority,
            deadline_s=(None if spec.deadline_ms is None
                        else spec.deadline_ms / 1e3))

    annotate = ((lambda name: jax.profiler.TraceAnnotation(name)) if trace
                else None)
    driver = loadgen.Driver(engine, make_request, annotate=annotate)
    specs = loadgen.requests(cell.traffic, seed)
    stats0 = dict(engine.stats)
    n_events0 = len(events)
    setup_s = time.perf_counter() - t_start
    gc_pauses: List[float] = []
    waits0 = _host_waits()
    with _profiling(trace) as prof_dir, _gc_timer(gc_pauses):
        if cell.traffic["loop"] == "closed":
            window = driver.closed(specs, cell.traffic["in_flight"], seconds)
        else:
            due = loadgen.arrivals(cell.traffic, seconds)
            window = driver.open(specs, due, seconds, grace_s)
    waits = [b - a for a, b in zip(waits0, _host_waits())]
    in_window = events[n_events0:]
    compiled_in_window = sum(
        1 for _, ev, _ in in_window
        if ev == "/jax/core/compile/backend_compile_duration")
    gave_up_at = time.perf_counter()
    stats = {k: engine.stats[k] - stats0.get(k, 0)
             for k in ("rays_rendered", "padded_rays", "dispatches")}
    spans = ([(s.name, s.t0, s.t1) for s in tracer.spans()
              if s.t1 is not None and window.t0 <= s.t0 <= window.t1]
             if tracer else [])
    faults = robustness(engine)
    tile_rays = engine.tile_rays
    images = {r.rid: engine.completed[r.rid].image
              for r in window.records if r.delivered}
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs)
    del engine, cache
    summary = None
    if trace:
        summary = tr.reduce(tr.load(prof_dir), is_kernel)
        shutil.rmtree(prof_dir, ignore_errors=True)

    run = Run(cell, window, len(devs), setup_s, stats, spans, summary,
              peak, cell.config["nerf"], cell.config["weights"], tile_rays,
              gave_up_at)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    if window.lateness_s:
        late = np.asarray(window.lateness_s) * 1e3
        print(f"loadgen: lateness_ms p50 {np.percentile(late, 50)} p99 "
              f"{np.percentile(late, 99)} max {late.max()} over "
              f"{len(late)} requests", flush=True)
    print(f"window: {window.seconds} s, {len(window.records)} requests, "
          f"{stats['rays_rendered']} rays, {stats['dispatches']} tiles, "
          f"{compiled_in_window} compiles inside the window", flush=True)
    longest = sorted(window.stalls, key=lambda st: -st[2])[:8]
    print(f"stalls: {len(window.stalls)} engine calls over "
          f"{loadgen.STALL_S} s, {sum(st[2] for st in window.stalls)} s in "
          f"all; longest (call, at s, took s, thread CPU s, process CPU s): "
          f"{longest}; garbage collection "
          f"{sum(gc_pauses)} s in {len(gc_pauses)} passes, longest "
          f"{max(gc_pauses, default=0.0)} s; CPU stolen by the host "
          f"{waits[0]} s, involuntary context switches {waits[1]}",
          flush=True)
    top = sorted(in_window, key=lambda e: -e[2])[:8]
    print(f"jax events inside the window: {len(in_window)}; longest "
          f"(event, at s, took s): "
          f"{[(ev, t - dur - window.t0, dur) for t, ev, dur in top]}",
          flush=True)

    gaps = pixel_gaps(cell, seed, window, images)
    say(f"pixel gaps: {gap_summary(gaps)}")
    checks = check(cell, window, gaps, faults)
    correct = passed(checks)
    for name, c in checks.items():
        bound = (f"<= {c['limit']}" if "limit" in c
                 else f">= {c['at_least']}")
        say(f"check {name}: {c['value']} {bound}")
    # refused or failed, or due and still unanswered after the grace period
    failed = sum(1 for r in window.records
                 if not r.delivered and (r.status is not None
                                         or r.due is not None))
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(window.records),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    src = REPO / "src"
    if not (src / "repro" / "serving").is_dir():
        say(f"bench: FAIL: the system under test is not at {src}")
        return 2
    sys.path.insert(0, str(src))
    cell = load_cell(args.workload)
    if cell.chips != 1:
        say(f"bench: FAIL: {cell.name} asks for {cell.chips} chips; the "
            f"harness drives one")
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"bench: FAIL: no TPU: JAX's first device is "
            f"{devices[0].platform}; the benchmark runs on the chip only")
        return 2
    if len(devices) < cell.chips:
        say(f"bench: FAIL: {cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    enable_compile_cache()
    seconds = min(args.seconds, TRACE_WINDOW_S) if args.trace else \
        args.seconds
    result = run_cell(cell, args.seed, seconds, bool(args.trace), devices,
                      t_start)
    print(json.dumps(result), flush=True)
    return 0
