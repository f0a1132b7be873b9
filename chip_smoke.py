"""Chip smoke: the fused PLCore serving path on a TPU at the published
NeRF widths.

Drives nerf-icarus ``CONFIG`` (8x256 trunk, skip at layer 4, 128-wide
colour branch, L=10/4 encodings, 64 + 128 samples, 800x800 frames)
through the serving engine as ``python -m repro.launch.serve --mode
engine --kernel --fuse-two-pass`` builds it: SceneCache -> TileScheduler
-> TileExecutor -> the fused two-pass Pallas kernel, compiled by Mosaic
(``interpret=False``). Three scenes with seeded random f32 weights serve
a few 64x64 requests and one full 800x800 frame.

The run fails — non-zero exit, no result line — unless JAX's first
device is a TPU; every request ends ``ok`` with no caught dispatch
error, oracle fallback or corrupt tile (the executor's retry ladder
would otherwise hide a kernel that does not run); the compiled tile
program holds the Mosaic kernel (``tpu_custom_call``); the frame is not
flat; and a 64x64 request matches the plain f32 XLA path on the same
chip within ``TOL``.

``--four-chips`` runs only the replica phase: four one-chip hosts
behind the ClusterEngine router, each host's scenes and tiles on its
own chip, whose ``ok`` framebuffers must equal a single-host run of the
same trace bit for bit.

    python chip_smoke.py
    python chip_smoke.py --four-chips

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# Max |engine - f32 XLA reference| over a 64x64 framebuffer. Both paths
# are f32; they differ by the kernel's double-angle PEU recurrence (about
# 2^L ulps at the top frequency) and f32 summation order, which the
# importance resampler amplifies by moving fine samples: ~1e-3 on the
# CPU at CONFIG. MLP operands rounded to bf16 instead move the pixels of
# these random-weight scenes by ~0.4 (same CPU comparison), so this bound
# fails a kernel that silently drops f32.
TOL = 2e-2
SMALL_HW = 64
TILE_RAYS = 4096
N_SCENES = 3


def die(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def require_clean(robustness: dict, what: str) -> None:
    """Every fault counter the executor swallows must be zero."""
    for key in ("dispatch_errors", "oracle_fallbacks", "corrupt_tiles",
                "tile_retries"):
        if robustness[key]:
            die(f"{what}: {key} = {robustness[key]} (the retry ladder "
                f"caught a failing dispatch)")


def serve(engine, requests) -> list:
    """Submit each request alone and drain it; return (result, wall s)."""
    out = []
    for req in requests:
        t0 = time.perf_counter()
        rid = engine.submit(req)
        engine.drain()
        out.append((engine.completed[rid], time.perf_counter() - t0))
    return out


def served_config():
    """nerf-icarus ``CONFIG`` with its kernels compiled through Mosaic
    (``interpret=False``): off the chip they fail instead of running
    under the interpreter."""
    import dataclasses
    from repro.configs.nerf_icarus import CONFIG
    return dataclasses.replace(CONFIG, kernel_interpret=False)


def one_chip(args, jax, dev) -> None:
    from repro.data import rays as R
    from repro.launch.serve import make_scene_loader
    from repro.serving import RenderEngine, RenderRequest, SceneCache

    cfg = served_config()
    H, W = cfg.image_hw
    say(f"widths: trunk {cfg.trunk_layers}x{cfg.trunk_width} skip "
        f"{list(cfg.skip_at)}, colour {cfg.color_width}, PE L="
        f"{cfg.pos_freqs}/{cfg.dir_freqs}, samples {cfg.n_coarse}+"
        f"{cfg.n_fine}, frame {H}x{W}, weights {cfg.dtype}")
    scene_ids = [f"scene{i}" for i in range(N_SCENES)]
    cache = SceneCache(make_scene_loader(cfg, scene_ids, seed=args.seed,
                                         use_kernel=True,
                                         fuse_two_pass=True),
                       capacity_mb=256.0)
    engine = RenderEngine(cache, tile_rays=TILE_RAYS)

    # compile the served tile program up front, and look inside it
    pp = cache.get(scene_ids[0])
    tile = np.zeros((TILE_RAYS, 3), np.float32)
    tile[:, 2] = 1.0
    t0 = time.perf_counter()
    program = pp.tile_program(pp.commit(tile), pp.commit(tile))
    jax.block_until_ready(pp.render_tile(pp.commit(tile), pp.commit(tile)))
    say(f"compile_s: {time.perf_counter() - t0:.3f} (the {TILE_RAYS}-ray "
        f"tile program compiled, then dispatched once)")
    if "tpu_custom_call" not in program.as_text():
        die("the compiled tile program holds no tpu_custom_call: the "
            "fused kernel is not on the device path")
    say("tile program: tpu_custom_call present")

    reqs = [RenderRequest(sid, hw=SMALL_HW, theta=30.0 + 90.0 * i)
            for i, sid in enumerate(scene_ids)]
    reqs.append(RenderRequest(scene_ids[0], hw=H, theta=45.0))
    results = serve(engine, reqs)
    for req, (res, wall) in zip(reqs, results):
        say(f"request {req.scene_id} {req.hw}x{req.hw}: status "
            f"{res.status}, wall_s {wall:.3f}")
        if res.status != "ok":
            die(f"request {res.request_id} ended {res.status}: {res.error}")
        if not np.isfinite(res.image).all():
            die(f"request {res.request_id} has non-finite pixels")
    rb = engine.robustness()
    say(f"dispatches: {engine.stats['dispatches']}, dispatch_errors: "
        f"{rb['dispatch_errors']}, oracle_fallbacks: "
        f"{rb['oracle_fallbacks']}, corrupt_tiles: {rb['corrupt_tiles']}")
    require_clean(rb, "engine")
    frame = results[-1][0].image
    if frame.shape != (H, W, 3) or not float(frame.std()) > 0.0:
        die(f"the {H}x{W} frame is flat or misshapen: shape "
            f"{frame.shape}, std {float(frame.std())}")
    say(f"frame {H}x{W}: pixel std {float(frame.std()):.6f}")

    # on-chip reference: the plain XLA path, f32 matmuls throughout
    req = reqs[0]
    ro, rd = R.camera_rays(R.pose_spherical(req.theta, req.phi, req.radius),
                           req.hw, req.hw, 0.9 * req.hw)
    ref_pp = make_scene_loader(cfg, scene_ids, seed=args.seed)(req.scene_id)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ref_pp.render_image(ro, rd,
                                             rays_per_batch=req.hw ** 2))
    err = float(np.abs(results[0][0].image - ref).max())
    say(f"max_abs_err vs f32 XLA reference ({req.hw}x{req.hw}): {err:.3e} "
        f"(tolerance {TOL:.0e})")
    if not err <= TOL:
        die(f"engine framebuffer differs from the f32 reference by {err}")
    mem = dev.memory_stats() or {}
    say(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")


def four_chips(args, jax, devs) -> None:
    """Four one-chip replicas behind the ClusterEngine router."""
    from repro.launch.serve import make_scene_loader
    from repro.serving import (ClusterEngine, RenderEngine, SceneCache,
                               loadgen)

    cfg = served_config()
    if len(devs) < 4:
        die(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    devs = devs[:4]
    # one scene per replica, loaded before the trace as a deployment that
    # assigns scenes to replicas at start; the router keeps each scene's
    # tiles on the replica that holds it
    scene_ids = [f"scene{i}" for i in range(4)]
    caches = [SceneCache(make_scene_loader(cfg, scene_ids, seed=args.seed,
                                           use_kernel=True,
                                           fuse_two_pass=True, device=d),
                         capacity_mb=256.0) for d in devs]
    tile = np.zeros((TILE_RAYS, 3), np.float32)
    tile[:, 2] = 1.0
    t0 = time.perf_counter()
    for cache, sid in zip(caches, scene_ids):   # compile on every chip
        pp = cache.get(sid)
        jax.block_until_ready(pp.render_tile(pp.commit(tile),
                                             pp.commit(tile)))
    say(f"compile_s (4 chips): {time.perf_counter() - t0:.3f}")

    # a tile blocks the single-threaded router while it runs, so the
    # heartbeat would time out hosts for that wait, not for ill health
    cluster = ClusterEngine(caches, device_groups=[[d] for d in devs],
                            tile_rays=TILE_RAYS, heartbeat_timeout_s=600.0)
    trace = loadgen.poisson_trace(24, scene_ids, hw_choices=(64, 128),
                                  seed=args.seed)
    t0 = time.perf_counter()
    loadgen.run_trace(cluster, trace, mode="closed", concurrency=4)
    say(f"cluster wall_s: {time.perf_counter() - t0:.3f} "
        f"({len(trace)} requests)")
    require_clean(cluster.robustness(), "cluster")
    for key in ("cross_host_redispatches", "heartbeat_timeouts"):
        if cluster.stats[key]:
            die(f"cluster: {key} = {cluster.stats[key]}")

    seen = set()
    for host, dev in zip(cluster.pool.hosts, devs):
        wdevs = set()
        for sid in host.cache.resident_scenes:
            pp = host.cache.get(sid)
            for arr in jax.tree.leaves(pp.packed):
                wdevs |= set(arr.devices())
        say(f"host {host.id}: state {host.state}, dispatches "
            f"{host.dispatches}, scenes {host.cache.resident_scenes}, "
            f"weights on {sorted(str(d) for d in wdevs)}")
        if host.state != "healthy" or host.dispatches < 1:
            die(f"host {host.id} served no tile (state {host.state})")
        if wdevs != {dev}:
            die(f"host {host.id}'s weights sit on {wdevs}, not on {dev}")
        seen.add(dev)
    if len(seen) != 4:
        die(f"the four hosts share devices: {seen}")

    single = RenderEngine(SceneCache(make_scene_loader(
        cfg, scene_ids, seed=args.seed, use_kernel=True,
        fuse_two_pass=True), capacity_mb=1024.0), tile_rays=TILE_RAYS)
    loadgen.run_trace(single, trace, mode="closed", concurrency=4)
    require_clean(single.robustness(), "single-host engine")
    n_ok = 0
    for rid, res in cluster.completed.items():
        ref = single.completed.get(rid)
        if res.status != "ok" or ref is None or ref.status != "ok":
            die(f"request {rid}: cluster {res.status}, single-host "
                f"{None if ref is None else ref.status}")
        if not np.array_equal(res.image, ref.image):
            die(f"request {rid}: cluster pixels differ from the "
                f"single-host run")
        n_ok += 1
    say(f"bit-identical to the single-host run: {n_ok}/{len(trace)} ok "
        f"requests")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four one-chip replicas phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro" / "serving").is_dir():
        die(f"the repro package is not at {SRC}: run this script from a "
            f"checkout of the repository")
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        die(f"no TPU: JAX's first device is {dev.platform} "
            f"({dev.device_kind}); this smoke runs on the chip only")
    say(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
        f"{len(devs)} devices)")
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")

    if args.four_chips:
        four_chips(args, jax, devs)
    else:
        one_chip(args, jax, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
