"""Paper C1/Fig. 3 — whole-pipeline fusion: intermediate-data traffic of
the fused PLCore vs. the unfused (GPU-style, Fig. 2a) pipeline.

Three reports:
  1. analytic HBM bytes per sample (the quantity the paper's architecture
     eliminates — computed from tensor shapes, exact);
  2. measured jaxpr intermediate count + wall time of both paths at tiny
     scale (CPU; the kernel path runs interpret=True so its wall time is
     NOT indicative — the bytes number is the architectural claim);
  3. serving-pipeline comparison (``bench_pipeline``): seed per-tile host
     loop vs. the single-dispatch lax.map pipeline (+ERT) vs. the kernel
     paths — two-dispatch coarse/fine, the one-kernel two-pass chain
     (``two_pass_fused``, ``two_pass_fused_ert`` with per-ray-block
     skips) and the mesh-sharded-weight variant
     (``two_pass_fused_sharded``: trunk stacks layer-partitioned over
     the local device mesh, per-layer all-gather in the program; the
     ``sharding`` dict records per-device resident MB vs replicated) —
     full-image wall time at tiny scale. benchmarks/run.py persists this
     one as BENCH_plcore.json (latest + append-only ``history``) so the
     perf trajectory is trackable across PRs.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.configs.nerf_icarus import CONFIG as FULL, tiny
from repro.core import sampling
from repro.core.plcore import plcore_decls
from repro.kernels import ops as kops
from repro.kernels.ref import fused_render_ref
from repro.models.params import init_params, param_count


def analytic_bytes(cfg):
    per_sample_acts = (cfg.pos_enc_dim + cfg.dir_enc_dim
                       + cfg.trunk_layers * cfg.trunk_width
                       + cfg.trunk_width + cfg.color_width + 4)
    unfused = 2 * 4.0 * per_sample_acts        # write+read each intermediate
    fused = 4.0 * (1 + 1 + (3 + 3 + 3 + 1) / cfg.n_samples)  # t,w + rays io
    return unfused, fused


def run() -> None:
    un_f, fu_f = analytic_bytes(FULL)
    emit("plcore_fusion/full_unfused_bytes_per_sample", 0.0, f"bytes={un_f:.0f}")
    emit("plcore_fusion/full_fused_bytes_per_sample", 0.0, f"bytes={fu_f:.0f}")
    emit("plcore_fusion/traffic_reduction", 0.0, f"x{un_f / fu_f:.0f}")

    # measured at tiny scale
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                         "float32")["fine"]
    R_ = 64
    rays_o = jnp.zeros((R_, 3)).at[:, 2].set(-4.0)
    d = jax.random.normal(jax.random.PRNGKey(1), (R_, 3)) * 0.2 \
        + jnp.array([0.0, 0.0, 1.0])
    rays_d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    t = jnp.sort(jax.random.uniform(jax.random.PRNGKey(2), (R_, 32)), -1) * 4 + 2
    deltas = sampling.deltas_from_t(t)

    xla = jax.jit(lambda p, o, dd, tt, dl: fused_render_ref(cfg, p, o, dd, tt, dl)[0])
    us_xla = time_fn(xla, params, rays_o, rays_d, t, deltas)
    emit("plcore_fusion/xla_unfused_tiny", us_xla, f"rays={R_}")

    kern = jax.jit(lambda p, o, dd, tt, dl: kops.fused_render(
        cfg, p, o, dd, tt, dl)[0])
    us_k = time_fn(kern, params, rays_o, rays_d, t, deltas, iters=1)
    emit("plcore_fusion/pallas_interpret_tiny", us_k,
         "NOT_indicative_cpu_interpret_mode")

    # jaxpr intermediate count (proxy for spilled tensors)
    jaxpr = jax.make_jaxpr(lambda p, o, dd, tt, dl: fused_render_ref(
        cfg, p, o, dd, tt, dl)[0])(params, rays_o, rays_d, t, deltas)
    n_eqns = len(jaxpr.jaxpr.eqns)
    emit("plcore_fusion/xla_graph_eqns", 0.0, f"eqns={n_eqns}")

    return bench_pipeline()


def bench_pipeline(hw: int = None, rays_per_batch: int = 1024,
                   ert_eps: float = 1e-2, iters: int = 5) -> dict:
    """Full-image serving comparison: seed tile loop vs single dispatch
    (XLA, +ERT) vs the Pallas kernel paths — the two-dispatch coarse/fine
    chain and the one-kernel two-pass chain (+ per-ray ERT skips).
    Same scene/seed/tiling for all; R = hw*hw rays.

    The seed loop is timed as it serves: it rebuilds its jit wrapper per
    image (a retrace every call), so its steady-state per-image cost
    includes that — exactly the overhead the single-dispatch pipeline
    removes. Set BENCH_PLCORE_HW to shrink for CI smoke runs; with
    BENCH_PLCORE_ENFORCE set, a two_pass_fused result slower than
    single_dispatch on the same run fails the process (the CI gate).
    """
    from repro.core.pipeline import PackedPlcore
    from repro.core.plcore import render_image, render_image_tiled
    from repro.data import rays as R

    hw = hw or int(os.environ.get("BENCH_PLCORE_HW", "64"))
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32")
    scene = R.blob_scene()
    c2w = R.pose_spherical(45.0, -25.0, scene.radius)
    ro, rd = R.camera_rays(c2w, hw, hw, 0.9 * hw)
    n_rays = hw * hw
    n_samples = n_rays * (cfg.n_coarse + cfg.n_coarse + cfg.n_fine)

    from repro.kernels import ops as kops
    from repro.runtime import sharding as rsh
    from repro.serving.scene_cache import plcore_nbytes

    # kernel engines: weights packed once at load, outside the timed loop
    eng_2d = PackedPlcore(cfg, params, use_kernel=True)
    eng_tp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
    # mesh-sharded residency over the local devices (a 1-device CI box
    # degrades to replicated: the variant then times the gather no-ops)
    mesh = rsh.plcore_mesh()
    eng_sh = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True,
                          shard_mesh=mesh)

    # adaptive (ASDR) variant on the canonical mixed empty-space scene:
    # same param draw with the sigma-head bias shifted -0.5, which carves
    # real empty space (all budget classes populated, ~40% dead rays).
    # The static fused path's wall time is param-value-independent (dense
    # compute), so its unbiased-scene number is the fair baseline. The
    # calibration probe + memo warm run at build time — load-time work,
    # outside the timed region, exactly as in serving.
    from repro.core.pipeline import AdaptiveRenderer, build_scene_aux
    params_b = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                           "float32")
    for net in params_b:
        params_b[net]["sigma"]["b"] = params_b[net]["sigma"]["b"] - 0.5
    eng_ad_pp = PackedPlcore(cfg, params_b, use_kernel=True,
                             fuse_two_pass=True)
    eng_ad = AdaptiveRenderer(
        eng_ad_pp, build_scene_aux(eng_ad_pp, grid_res=32, memo_mb=16.0,
                                   probe_hw=8))

    variants = {
        "seed_loop": lambda: render_image_tiled(
            cfg, params, ro, rd, rays_per_batch=rays_per_batch),
        "single_dispatch": lambda: render_image(
            cfg, params, ro, rd, rays_per_batch=rays_per_batch),
        "single_dispatch_ert": lambda: render_image(
            cfg, params, ro, rd, rays_per_batch=rays_per_batch,
            ert_eps=ert_eps),
        "kernel_two_dispatch": lambda: eng_2d.render_image(
            ro, rd, rays_per_batch=rays_per_batch),
        "two_pass_fused": lambda: eng_tp.render_image(
            ro, rd, rays_per_batch=rays_per_batch),
        "two_pass_fused_ert": lambda: eng_tp.render_image(
            ro, rd, rays_per_batch=rays_per_batch, ert_eps=ert_eps),
        "two_pass_fused_sharded": lambda: eng_sh.render_image(
            ro, rd, rays_per_batch=rays_per_batch),
        "two_pass_fused_adaptive": lambda: eng_ad.render_image(
            ro, rd, rays_per_tile=rays_per_batch),
    }
    n_shards = rsh.plcore_shard_count(mesh, cfg.trunk_layers)
    out = {"hw": hw, "rays": n_rays, "samples": n_samples,
           "rays_per_batch": rays_per_batch, "ert_eps": ert_eps,
           "sharding": {
               "devices": int(mesh.size), "weight_shards": n_shards,
               "resident_mb_per_device": round(
                   plcore_nbytes(eng_sh) / (1 << 20), 4),
               "resident_mb_replicated": round(
                   plcore_nbytes(eng_tp) / (1 << 20), 4),
               "resident_model_mb_per_device": round(
                   2 * kops.plcore_resident_weight_bytes(cfg, n_shards)
                   / (1 << 20), 4),
           },
           "variants": {}}
    # Interleaved rounds + MIN wall time per variant: this container's
    # cores are shared, so contention bursts poison means and medians;
    # the per-variant minimum over interleaved rounds is the only
    # statistic that compares variants on equal (uncontended) footing.
    def _sync(r):
        getattr(r, "block_until_ready", lambda: None)()  # np = already sync

    for fn in variants.values():
        _sync(fn())                            # warm (compile cache)
    times = {name: [] for name in variants}
    for _ in range(iters):
        for name, fn in variants.items():
            t0 = time.perf_counter()
            _sync(fn())
            times[name].append(time.perf_counter() - t0)
    for name in variants:
        wall = min(times[name])
        out["variants"][name] = {
            "wall_s": round(wall, 4),
            "rays_per_s": round(n_rays / wall, 1),
            "samples_per_s": round(n_samples / wall, 1),
        }
        emit(f"plcore_fusion/pipeline_{name}", wall * 1e6,
             f"rays_per_s={out['variants'][name]['rays_per_s']}")
    v = out["variants"]
    out["speedup_single_vs_seed"] = round(
        v["seed_loop"]["wall_s"] / v["single_dispatch"]["wall_s"], 2)
    out["speedup_ert_vs_seed"] = round(
        v["seed_loop"]["wall_s"] / v["single_dispatch_ert"]["wall_s"], 2)
    out["speedup_two_pass_vs_seed"] = round(
        v["seed_loop"]["wall_s"] / v["two_pass_fused"]["wall_s"], 2)
    out["speedup_two_pass_ert_vs_seed"] = round(
        v["seed_loop"]["wall_s"] / v["two_pass_fused_ert"]["wall_s"], 2)
    out["speedup_two_pass_sharded_vs_seed"] = round(
        v["seed_loop"]["wall_s"] / v["two_pass_fused_sharded"]["wall_s"], 2)
    out["speedup_adaptive_vs_two_pass"] = round(
        v["two_pass_fused"]["wall_s"]
        / v["two_pass_fused_adaptive"]["wall_s"], 2)
    out["adaptive"] = eng_ad.report()
    emit("plcore_fusion/speedup_adaptive_vs_two_pass", 0.0,
         f"x{out['speedup_adaptive_vs_two_pass']}")
    emit("plcore_fusion/speedup_single_vs_seed", 0.0,
         f"x{out['speedup_single_vs_seed']}")
    emit("plcore_fusion/speedup_two_pass_ert_vs_seed", 0.0,
         f"x{out['speedup_two_pass_ert_vs_seed']}")
    if os.environ.get("BENCH_PLCORE_ENFORCE"):
        # gate with a noise margin: even min-over-interleaved-rounds can
        # wobble a few percent on a contended CI core, so only a clearly
        # out-of-noise shortfall fails the run
        tp = v["two_pass_fused"]["samples_per_s"]
        sd = v["single_dispatch"]["samples_per_s"]
        if tp < 0.9 * sd:
            raise SystemExit(
                f"two_pass_fused regressed below single_dispatch: "
                f"{tp} < 0.9 * {sd} samples/s")
    return out


if __name__ == "__main__":
    run()
