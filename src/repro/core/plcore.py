"""PLCore — the plenoptic core: PEU -> MLP engine -> VRU (paper Fig. 3).

``render_rays`` executes the complete NeRF pipeline for a batch of rays:
positions & directions in, pixel colors out, nothing but the final pixels
leaving the pipeline — the JAX restatement of "no intermediate data going
off-chip". Under jit the whole two-pass render is one XLA program; with
``use_kernel=True`` the per-pass encode->MLP->volume-render runs inside ONE
Pallas kernel with VMEM-resident weights (kernels/fused_plcore.py).

Multi-core scaling (paper §4.1: "the information of different clusters of
rays are fed to different PLCores") = sharding the ray batch over the
("pod","data") mesh axes with replicated weights; ``make_render_step``
builds that jit. The tailored instruction set of the paper maps to the
launch layer (repro.launch.serve).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.nerf_icarus import NerfConfig
from repro.core import rmcm, sampling, volume
from repro.core.encoding import (conical_frustum_to_gaussian,
                                 integrated_pos_enc, nerf_encoding)
from repro.core.mlp import cone_heads, nerf_mlp_apply, nerf_mlp_decls
from repro.models.params import Decl


# ------------------------------------------------------------------ decls ---
def plcore_decls(cfg: NerfConfig) -> dict:
    """Coarse + fine networks (original NeRF trains both); one, under
    "coarse", when the config shares it between the passes (Mip-NeRF)."""
    if cfg.shared_net:
        return {"coarse": nerf_mlp_decls(cfg)}
    return {"coarse": nerf_mlp_decls(cfg), "fine": nerf_mlp_decls(cfg)}


# ------------------------------------------------------------- one pass -----
def _eval_pass(cfg: NerfConfig, params, quant, rays_o, rays_d, t,
               use_kernel: bool, packed: Optional[dict] = None, alive=None):
    """Encode -> MLP -> volume-render one sample set. t: (R, N).

    packed: pre-stacked kernel weight layout (skips per-call packing);
    alive: optional (R,) ERT mask forwarded to the fused kernel."""
    deltas = sampling.deltas_from_t(t, far_cap=1e10)
    if use_kernel:
        from repro.kernels import ops as kops
        rgb_pix, aux = kops.fused_render(cfg, params, rays_o, rays_d, t,
                                         deltas, quant=quant, packed=packed,
                                         alive=alive)
        return rgb_pix, aux
    cdt = jnp.dtype(cfg.compute_dtype)
    pts = rays_o[..., None, :] + t[..., None] * rays_d[..., None, :]
    pe_pos = nerf_encoding(pts, cfg.pos_freqs).astype(cdt)
    dirs = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    # per-ray (R, 1, de): the split color matmul broadcasts it lazily
    pe_dir = nerf_encoding(dirs, cfg.dir_freqs).astype(cdt)[..., None, :]
    if cdt != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(cdt), params)
    sigma, rgb = nerf_mlp_apply(cfg, params, pe_pos, pe_dir, quant=quant)
    # VRU integrates in f32 regardless of the MLP-engine dtype
    return volume.render_parallel(sigma.astype(jnp.float32),
                                  rgb.astype(jnp.float32), deltas)


# ----------------------------------------------- Mip-NeRF: cone rays ------
def _cone_pass(cfg: NerfConfig, params, quant, rays_o, rays_d, radii, t0,
               t1, pe_dir):
    """One pass over the (R, N) intervals [t0, t1): frustum Gaussians ->
    IPE -> MLP -> the volume integral over the finite intervals."""
    t_mean, cov = conical_frustum_to_gaussian(rays_d, t0, t1, radii)
    mean = rays_o[..., None, :] + t_mean[..., None] * rays_d[..., None, :]
    pe = integrated_pos_enc(mean, cov, cfg.pos_freqs)
    sigma, rgb = cone_heads(cfg, *nerf_mlp_apply(cfg, params, pe, pe_dir,
                                                 quant=quant))
    deltas = (t1 - t0) * jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    return volume.render_parallel(sigma, rgb, deltas)


def render_rays_cone(cfg: NerfConfig, params: dict, rays_o, rays_d, radii,
                     *, quant: Optional[dict] = None,
                     white_bkgd: bool = True) -> dict:
    """Mip-NeRF's deterministic two-level render in XLA ops, the math of
    the fused cone kernel: ``n_coarse`` intervals between evenly spaced
    edges, the blurred-weight resample to ``n_fine`` new intervals, and
    the fine pass on those alone (no merge), both levels through the one
    network under "coarse". radii: (R, 1) cone radius per unit of t.
    Returns {rgb, rgb_coarse, depth, acc}."""
    net, q = params["coarse"], (quant or {}).get("coarse")
    R = rays_o.shape[:-1]
    t0, t1 = sampling.cone_intervals(cfg.near, cfg.far, cfg.n_coarse)
    t0 = jnp.broadcast_to(t0, R + t0.shape[-1:])
    t1 = jnp.broadcast_to(t1, R + t1.shape[-1:])
    dirs = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    pe_dir = nerf_encoding(dirs, cfg.dir_freqs)[..., None, :]
    rgb_c, aux_c = _cone_pass(cfg, net, q, rays_o, rays_d, radii, t0, t1,
                              pe_dir)
    t0, t1 = sampling.mip_resample(t0, t1, aux_c["weights"], cfg.n_fine,
                                   cfg.resample_padding)
    rgb_f, aux_f = _cone_pass(cfg, net, q, rays_o, rays_d, radii, t0, t1,
                              pe_dir)
    depth = volume.composite_depth(aux_f["weights"], 0.5 * (t0 + t1))
    if white_bkgd:
        rgb_f = volume.white_background(rgb_f, aux_f["acc"])
        rgb_c = volume.white_background(rgb_c, aux_c["acc"])
    return {"rgb": rgb_f, "rgb_coarse": rgb_c, "depth": depth,
            "acc": aux_f["acc"]}


def _render_cone(cfg, params, rays_o, rays_d, radii, key, quant, use_kernel,
                 fuse_two_pass, packed, ert_eps, white_bkgd, alive):
    """``render_rays`` for a cone config: the fused cone kernel or the XLA
    path; the modes the cone path lacks refuse it by name."""
    if key is not None:
        raise ValueError("cone rays render with deterministic sampling "
                         "only: no sampling key")
    if ert_eps > 0.0 or alive is not None:
        raise ValueError("early ray termination and alive masks do not "
                         "render cone (Mip-NeRF) rays")
    if radii is None:   # a ray without a footprint: a zero-radius cone
        radii = jnp.zeros(rays_o.shape[:-1] + (1,), jnp.float32)
    radii = radii.reshape(rays_o.shape[:-1] + (1,))
    if not use_kernel:
        return render_rays_cone(cfg, params, rays_o, rays_d, radii,
                                quant=quant, white_bkgd=white_bkgd)
    if not fuse_two_pass:
        raise ValueError("cone rays have no two-dispatch kernel path: use "
                         "the fused two-pass kernel or the XLA path")
    from repro.kernels import ops as kops
    if packed is None:
        packed = {net: kops.stack_plcore_weights(
                      cfg, params[net], (quant or {}).get(net))
                  for net in plcore_decls(cfg)}
    out = kops.fused_render_two_pass(cfg, packed, rays_o, rays_d,
                                     radii=radii)
    rgb_f, rgb_c = out["rgb"], out["rgb_coarse"]
    if white_bkgd:
        rgb_f = volume.white_background(rgb_f, out["acc"])
        rgb_c = volume.white_background(rgb_c, out["acc_coarse"])
    return {"rgb": rgb_f, "rgb_coarse": rgb_c, "depth": out["depth"],
            "acc": out["acc"]}


def render_rays(cfg: NerfConfig, params: dict, rays_o, rays_d,
                key: Optional[jax.Array] = None, *,
                quant: Optional[dict] = None, use_kernel: bool = False,
                fuse_two_pass: bool = False,
                packed: Optional[dict] = None, ert_eps: float = 0.0,
                white_bkgd: bool = True, alive=None, radii=None) -> dict:
    """Two-pass render (paper §5.1): n_coarse stratified + n_fine importance.

    rays_o/rays_d: (R, 3). Returns {rgb, rgb_coarse, depth, acc}.
    quant: optional {"coarse": ..., "fine": ...} RMCM trees.
    packed: optional {"coarse": ..., "fine": ...} pre-stacked kernel weight
    layouts (PackedPlcore caches these once per param set).
    ert_eps > 0 enables Cicero-style early ray termination: rays whose
    remaining transmittance after the coarse pass is < ert_eps keep the
    coarse color and are masked out of the fine-pass MLP; if the whole
    batch terminated the fine pass is skipped entirely (lax.cond — a real
    branch under the single-dispatch image scan).
    fuse_two_pass (requires use_kernel, deterministic sampling): the whole
    coarse -> importance -> fine chain runs as ONE Pallas kernel per ray
    tile — coarse weights never leave VMEM, and with ert_eps > 0 the
    kernel skips the fine pass of every ray block whose rays all
    terminated (per ray on the chip at published widths).
    ``alive`` (fuse_two_pass only): optional (R,) float mask of
    externally-live rays — 0-rows (adaptive trunk-memo hits) enter the
    fused kernel dead and the same ERT skip drops their fine pass.
    ``radii`` (cone configs, Mip-NeRF): (R,) or (R, 1) per-ray pixel
    radius per unit of t; None renders each ray as a zero-radius cone.
    """
    if cfg.cone:
        return _render_cone(cfg, params, rays_o, rays_d, radii, key, quant,
                            use_kernel, fuse_two_pass, packed, ert_eps,
                            white_bkgd, alive)
    R = rays_o.shape[:-1]
    k1 = k2 = None
    if key is not None:
        k1, k2 = jax.random.split(key)
    qc = (quant or {}).get("coarse")
    qf = (quant or {}).get("fine")
    pc = (packed or {}).get("coarse")
    pf = (packed or {}).get("fine")

    if alive is not None and not (use_kernel and fuse_two_pass):
        raise ValueError("an external alive mask rides the fused two-pass "
                         "kernel's ERT skip — pass use_kernel=True, "
                         "fuse_two_pass=True")

    if use_kernel and fuse_two_pass:
        if key is not None:
            raise ValueError("fuse_two_pass is the deterministic serving "
                             "path — no sampling key")
        from repro.kernels import ops as kops
        if pc is None or pf is None:
            pc = kops.stack_plcore_weights(cfg, params["coarse"], qc)
            pf = kops.stack_plcore_weights(cfg, params["fine"], qf)
        out = kops.fused_render_two_pass(
            cfg, {"coarse": pc, "fine": pf}, rays_o, rays_d,
            ert_eps=ert_eps, alive=alive)
        rgb_f, rgb_c = out["rgb"], out["rgb_coarse"]
        if white_bkgd:
            rgb_f = volume.white_background(rgb_f, out["acc"])
            rgb_c = volume.white_background(rgb_c, out["acc_coarse"])
        return {"rgb": rgb_f, "rgb_coarse": rgb_c, "depth": out["depth"],
                "acc": out["acc"]}

    # ---- pass 1: coarse --------------------------------------------------
    t_c = sampling.stratified(cfg.near, cfg.far, cfg.n_coarse, R, k1)
    rgb_c, aux_c = _eval_pass(cfg, params["coarse"], qc, rays_o, rays_d, t_c,
                              use_kernel, pc)

    # ---- pass 2: importance resample near surfaces ------------------------
    if ert_eps > 0.0:
        # acc = 1 - T_N exactly, so "T < eps" == "acc > 1 - eps"
        alive = aux_c["acc"] < (1.0 - ert_eps)

        def fine_pass(_):
            # the whole pass-2 chain — resample, merge, MLP, integrate —
            # lives inside the branch so fully-terminated batches skip it
            t_f = sampling.importance(
                t_c, jax.lax.stop_gradient(aux_c["weights"]), cfg.n_fine, k2)
            t_all = sampling.merge_sorted(t_c, t_f)
            rgb, aux = _eval_pass(cfg, params["fine"], qf, rays_o, rays_d,
                                  t_all, use_kernel, pf,
                                  alive.astype(jnp.float32) if use_kernel
                                  else None)
            return (rgb, aux["acc"],
                    volume.composite_depth(aux["weights"], t_all))

        def skip_pass(_):
            return (jnp.zeros(R + (3,), jnp.float32),
                    jnp.zeros(R, jnp.float32), jnp.zeros(R, jnp.float32))

        rgb_f, acc_f, depth_f = jax.lax.cond(jnp.any(alive), fine_pass,
                                             skip_pass, operand=None)
        # dead rays: the coarse estimate already holds ~all the radiance
        rgb_f = jnp.where(alive[..., None], rgb_f, rgb_c)
        aux_f = {"acc": jnp.where(alive, acc_f, aux_c["acc"])}
        depth = jnp.where(alive, depth_f,
                          volume.composite_depth(aux_c["weights"], t_c))
    else:
        t_f = sampling.importance(t_c,
                                  jax.lax.stop_gradient(aux_c["weights"]),
                                  cfg.n_fine, k2)
        t_all = sampling.merge_sorted(t_c, t_f)
        rgb_f, aux_f = _eval_pass(cfg, params["fine"], qf, rays_o, rays_d,
                                  t_all, use_kernel, pf)
        depth = volume.composite_depth(aux_f["weights"], t_all)

    if white_bkgd:
        rgb_f = volume.white_background(rgb_f, aux_f["acc"])
        rgb_c = volume.white_background(rgb_c, aux_c["acc"])
    return {"rgb": rgb_f, "rgb_coarse": rgb_c, "depth": depth,
            "acc": aux_f["acc"]}


# -------------------------------------------------------- image rendering ---
def flatten_pad_rays(rays_o, rays_d, rays_per_batch: int):
    """(H, W, 3) -> tiles (T, rays_per_batch, 3) + true ray count. Shared
    by the seed tile loop and the single-dispatch pipeline so the two
    paths tile identically — the bit-for-bit regression depends on it."""
    flat_o = rays_o.reshape(-1, 3)
    flat_d = rays_d.reshape(-1, 3)
    n = flat_o.shape[0]
    pad = (-n) % rays_per_batch
    flat_o = jnp.pad(flat_o, ((0, pad), (0, 0)))
    flat_d = jnp.pad(flat_d, ((0, pad), (0, 0)),
                     constant_values=1.0)  # avoid zero-norm dirs in padding
    T = (n + pad) // rays_per_batch
    return (flat_o.reshape(T, rays_per_batch, 3),
            flat_d.reshape(T, rays_per_batch, 3), n)


def render_image_tiled(cfg: NerfConfig, params, rays_o, rays_d, *,
                       quant=None, use_kernel: bool = False,
                       rays_per_batch: int = 4096) -> jnp.ndarray:
    """The seed per-tile host loop, kept as the regression oracle for the
    single-dispatch pipeline (core.pipeline) and as the benchmark
    baseline: one dispatch + host sync per tile, and — because the jit
    wrapper is rebuilt per call — a retrace per image. rays: (H, W, 3) ->
    rgb (H, W, 3)."""
    H, W, _ = rays_o.shape
    o_tiles, d_tiles, n = flatten_pad_rays(rays_o, rays_d, rays_per_batch)
    fn = jax.jit(partial(render_rays, cfg, use_kernel=use_kernel,
                         white_bkgd=True))
    outs = []
    for i in range(o_tiles.shape[0]):
        o = fn(params, o_tiles[i], d_tiles[i], quant=quant)
        outs.append(o["rgb"])
    rgb = jnp.concatenate(outs, axis=0)[:n]
    return rgb.reshape(H, W, 3)


def render_image(cfg: NerfConfig, params, rays_o, rays_d, *,
                 quant=None, use_kernel: bool = False,
                 rays_per_batch: int = 4096,
                 ert_eps: Optional[float] = None) -> jnp.ndarray:
    """Render a full image through the PLCore (deterministic midpoint
    sampling — inference mode). rays: (H, W, 3) -> rgb (H, W, 3).

    Single-dispatch: the whole image — every tile, both sampling passes —
    is ONE cached XLA program (core.pipeline); no per-tile host sync, no
    per-call retrace. ``ert_eps`` overrides cfg.ert_eps (None = use cfg)."""
    from repro.core import pipeline
    return pipeline.render_image_single(
        cfg, params, rays_o, rays_d, quant=quant, use_kernel=use_kernel,
        rays_per_batch=rays_per_batch, ert_eps=ert_eps)


# ------------------------------------------------- multi-core dispatch ------
def make_render_step(cfg: NerfConfig, mesh, rules, *, use_kernel=False):
    """jit'd render with rays sharded over the data axes and weights
    replicated — one PLCore per mesh cell, the paper's scaling model."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ray_sharding = NamedSharding(mesh, P(rules.batch_axes(mesh), None))
    repl = NamedSharding(mesh, P())

    def step(params, rays_o, rays_d):
        out = render_rays(cfg, params, rays_o, rays_d, use_kernel=use_kernel)
        return out["rgb"]

    return jax.jit(step,
                   in_shardings=(repl, ray_sharding, ray_sharding),
                   out_shardings=ray_sharding)


# ------------------------------------------------------------- dry-run API --
class PlcoreModel:
    """Adapter so nerf-icarus joins the dry-run/roofline grid alongside the
    assigned LM architectures."""

    def __init__(self, cfg: NerfConfig):
        self.cfg = cfg

    def param_decls(self):
        return plcore_decls(self.cfg)

    def render_step(self, params, batch):
        out = render_rays(self.cfg, params, batch["rays_o"], batch["rays_d"])
        return out["rgb"]

    def input_specs(self, n_rays: int) -> dict:
        f32 = jnp.float32
        return {"rays_o": jax.ShapeDtypeStruct((n_rays, 3), f32),
                "rays_d": jax.ShapeDtypeStruct((n_rays, 3), f32)}

    def input_logical(self) -> dict:
        return {"rays_o": ("batch", None), "rays_d": ("batch", None)}
