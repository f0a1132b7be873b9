"""Single-dispatch PLCore serving pipeline — ICARUS C1 lifted to the host.

The paper's PLCore renders "without any intermediate data going off-chip";
the seed host driver undid that economy at the dispatch level: every
``render_image`` call rebuilt a ``jax.jit`` wrapper (a retrace + recompile
per image), every tile was a separate dispatch with a host sync, and the
kernel path re-packed the RMCM/sign-bit weight layout inside every jitted
call. This module is the weight-stationary restatement:

* ``PackedPlcore`` — loads a param set ONCE: packs the kernel weight
  layout (``stack_plcore_weights`` + RMCM quantization) a single time and
  reuses it across every batch, pass, and image (verifiable via
  ``kernels.ops.pack_count``).
* ``render_image_single`` — the whole image is ONE XLA program: a
  ``jax.lax.map`` over ray tiles whose body holds the fused
  coarse -> importance -> fine two-pass chain; no per-tile host round
  trip, no per-call retrace (compiled programs are cached per
  (config, flags) and re-specialized per shape by jit). Ray buffers are
  donated to the program on non-CPU backends — ``_donating_jit`` resolves
  donation by argument name for every pipeline program.
* ``fuse_two_pass`` — with ``use_kernel`` this drops the chain one level
  further: the coarse pass, the in-VMEM importance resample AND the fine
  pass run inside ONE Pallas kernel per ray tile
  (kernels/fused_plcore.two_pass_plcore_call), so coarse weights never
  round-trip through HBM between the passes; with ``ert_eps > 0`` the
  kernel also skips the fine pass of ray blocks whose rays all
  terminated.
* ``PackedPlcore.render_tile`` — the tile-stream entry point for the
  multi-tenant serving engine (repro.serving.engine): one pre-coalesced
  fixed-shape ray tile in, pixels out, same per-tile body as the image
  program so cross-request coalescing is invisible in the output. The
  call is NON-BLOCKING — jax async dispatch returns an un-materialized
  device array, so a pipelined executor can have several tiles in flight
  and only pay the host sync at its drain points
  (``PackedPlcore.dispatch_tile`` is the executor's traced form).
* ``device`` — a scene's one residency: params, quantized weights and the
  packed layout, replicated whole on one device. A published-width scene
  is ~12 MB, so a chip holds many; replicas across chips are
  ``serving.cluster``'s.
* Early ray termination (Cicero, arXiv 2404.11852): with ``ert_eps > 0``
  rays whose transmittance after the coarse pass fell below the threshold
  keep the coarse color and skip the fine-pass MLP — a real
  ``lax.cond`` branch per scan tile, plus per-kernel-tile skipping inside
  the fused Pallas kernel.

The seed per-tile loop survives as ``plcore.render_image_tiled`` — the
regression oracle (bit-for-bit at fp32) and benchmark baseline
(benchmarks/plcore_fusion.py quantifies the gap).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.nerf_icarus import NerfConfig
from repro.core import plcore
from repro.obs.trace import NULL_TRACER

# Compiled-program caches, keyed on (cfg, flags): cfg is a frozen dataclass
# (hashable); params/quant/packed enter as traced args so a cache entry
# survives param refreshes and ckpt reloads.
_IMAGE_JITS: dict = {}
_RAY_JITS: dict = {}
_TILE_JITS: dict = {}
# The tile programs' name scope: the HLO (and a device trace) name their
# operations under it.
TILE_SCOPE = "plcore.tile"


def _donating_jit(fn, donate_names=()):
    """jit with donation resolved from ``fn``'s signature BY ARGUMENT NAME —
    the one place the pipeline decides what to donate, so no program
    hardcodes positional indices. Donation is a no-op (warning) on CPU;
    enabled on every other backend."""
    if not donate_names or jax.default_backend() == "cpu":
        return jax.jit(fn)
    import inspect
    pos = {n: i for i, n in enumerate(inspect.signature(fn).parameters)}
    return jax.jit(fn, donate_argnums=tuple(pos[n] for n in donate_names))


def _image_fn(cfg: NerfConfig, use_kernel: bool, ert_eps: float,
              fuse_two_pass: bool = False):
    key = (cfg, use_kernel, float(ert_eps), fuse_two_pass)
    fn = _IMAGE_JITS.get(key)
    if fn is None:
        def run(params, quant, packed, o_tiles, d_tiles):
            def tile(od):
                o, d = od
                out = plcore.render_rays(
                    cfg, params, o, d, quant=quant, packed=packed,
                    use_kernel=use_kernel, fuse_two_pass=fuse_two_pass,
                    ert_eps=ert_eps, white_bkgd=True)
                return out["rgb"]
            return jax.lax.map(tile, (o_tiles, d_tiles))

        fn = _donating_jit(run, ("o_tiles", "d_tiles"))
        _IMAGE_JITS[key] = fn
    return fn


def _ray_fn(cfg: NerfConfig, use_kernel: bool, ert_eps: float,
            fuse_two_pass: bool = False):
    # NOTE donation contract: on non-CPU backends the rays_o/rays_d
    # buffers are CONSUMED by the program (standard jax donation) — the
    # serving loop hands each ray batch over and never reuses it. Callers
    # that cache a ray grid across calls must pass a fresh copy.
    key = (cfg, use_kernel, float(ert_eps), fuse_two_pass)
    fn = _RAY_JITS.get(key)
    if fn is None:
        def run(params, quant, packed, rays_o, rays_d, k):
            return plcore.render_rays(
                cfg, params, rays_o, rays_d, k, quant=quant, packed=packed,
                use_kernel=use_kernel, fuse_two_pass=fuse_two_pass,
                ert_eps=ert_eps, white_bkgd=True)

        fn = _donating_jit(run, ("rays_o", "rays_d"))
        _RAY_JITS[key] = fn
    return fn


def _tile_fn(cfg: NerfConfig, use_kernel: bool, ert_eps: float,
             fuse_two_pass: bool = False, coarse_only: bool = False,
             adaptive: bool = False):
    """Tile-stream program: ONE pre-coalesced fixed-shape ray tile ->
    pixel colors. This is the serving-engine entry point — the engine
    coalesces rays from many concurrent requests into a tile, dispatches
    it here, and scatters the pixels back to per-request framebuffers.

    The tile body is the SAME render_rays call the image program's
    lax.map runs per tile, so a coalesced tile reproduces the per-request
    ``render_image`` pixels bit-for-bit (every per-ray op — encoding,
    MLP matmul rows, VRU integration — depends only on its own ray).
    Returns rgb ONLY, so nothing but the pixels leaves the program.
    Compiled once per (cfg, flags) and re-specialized per tile shape;
    tile buffers are donated off-CPU (the engine builds fresh ones per
    dispatch).

    ``coarse_only`` is the overload-degradation program (Cicero's
    controlled quality reduction as an overload response): deterministic
    coarse sampling + the coarse MLP + VRU only — no importance
    resample, no fine pass — at roughly ``n_coarse / (2*n_coarse +
    n_fine)`` of the full sample budget. Per-ray independent like the
    full body, so degraded coalescing is equally partition-invariant.

    ``adaptive`` compiles the budget-bucketed variant: the program takes
    an extra per-ray ``alive`` mask forwarded to the fused kernel's ERT
    skip (trunk-memo hits enter dead). Per-budget programs come from the
    cache key: the caller replaces ``cfg.n_fine`` with the bucket's
    budget, and cfg is the leading key element — each (budget, flags)
    combination is its own compiled artifact."""
    key = (cfg, use_kernel, float(ert_eps), fuse_two_pass, coarse_only,
           adaptive)
    fn = _TILE_JITS.get(key)
    if fn is None and cfg.cone:
        # a cone tile carries its rays' (n, 1) pixel radii; the modes that
        # would reach here otherwise refuse it in PackedPlcore
        def run(params, quant, packed, o_tile, d_tile, r_tile):
            with jax.named_scope(TILE_SCOPE):
                out = plcore.render_rays(
                    cfg, params, o_tile, d_tile, quant=quant, packed=packed,
                    use_kernel=use_kernel, fuse_two_pass=fuse_two_pass,
                    white_bkgd=True, radii=r_tile)
                return out["rgb"]

        fn = _TILE_JITS[key] = _donating_jit(run, ("o_tile", "d_tile",
                                                   "r_tile"))
    if fn is None:
        if coarse_only:
            from repro.core import sampling, volume

            def run(params, quant, packed, o_tile, d_tile):
                with jax.named_scope(TILE_SCOPE):
                    t_c = sampling.stratified(cfg.near, cfg.far,
                                              cfg.n_coarse,
                                              o_tile.shape[:-1], None)
                    rgb_c, aux_c = plcore._eval_pass(
                        cfg, params["coarse"], (quant or {}).get("coarse"),
                        o_tile, d_tile, t_c, use_kernel,
                        (packed or {}).get("coarse"))
                    return volume.white_background(rgb_c, aux_c["acc"])
        elif adaptive:
            def run(params, quant, packed, o_tile, d_tile, alive):
                with jax.named_scope(TILE_SCOPE):
                    out = plcore.render_rays(
                        cfg, params, o_tile, d_tile, quant=quant,
                        packed=packed, use_kernel=use_kernel,
                        fuse_two_pass=fuse_two_pass, ert_eps=ert_eps,
                        white_bkgd=True, alive=alive)
                    return out["rgb"]
        else:
            def run(params, quant, packed, o_tile, d_tile):
                with jax.named_scope(TILE_SCOPE):
                    out = plcore.render_rays(
                        cfg, params, o_tile, d_tile, quant=quant,
                        packed=packed, use_kernel=use_kernel,
                        fuse_two_pass=fuse_two_pass, ert_eps=ert_eps,
                        white_bkgd=True)
                    return out["rgb"]

        fn = _donating_jit(run, ("o_tile", "d_tile"))
        _TILE_JITS[key] = fn
    return fn


def render_image_single(cfg: NerfConfig, params, rays_o, rays_d, *,
                        quant: Optional[dict] = None,
                        packed: Optional[dict] = None,
                        use_kernel: bool = False,
                        fuse_two_pass: bool = False,
                        rays_per_batch: int = 4096,
                        ert_eps: Optional[float] = None) -> jnp.ndarray:
    """One-dispatch full-image render. rays: (H, W, 3) -> rgb (H, W, 3)."""
    H, W, _ = rays_o.shape
    eps = cfg.ert_eps if ert_eps is None else float(ert_eps)
    o_tiles, d_tiles, n = plcore.flatten_pad_rays(rays_o, rays_d,
                                                  rays_per_batch)
    fn = _image_fn(cfg, use_kernel, eps, fuse_two_pass)
    rgb = fn(params, quant, packed, o_tiles, d_tiles)
    return rgb.reshape(-1, 3)[:n].reshape(H, W, 3)


class PackedPlcore:
    """A loaded PLCore: params + (optional) RMCM quantization + kernel
    weight layout, packed once at construction and reused by every render.

    This is the serving-side object: build it at model-load time, then
    stream ``render_image`` / ``render_rays`` calls through it. All jitted
    programs are shared across instances with the same config/flags.

    ``device``: the one device this scene lives on (a serving replica's
    own chip). Params, quantized weights and the packed layout are
    committed there at load, and ``commit`` puts tile buffers beside
    them, so every render program runs on that device. None: JAX's
    default device.

    A cone config (Mip-NeRF) renders tiles with their rays' pixel radii
    (``render_tile(..., radii=)``) and packs ONE network when the config
    shares it between the passes (``params["coarse"]``; a "fine" entry is
    dropped). ``render_tile``'s coarse-only, budget and alive modes
    refuse it.
    """

    def __init__(self, cfg: NerfConfig, params: dict, *,
                 quant: Optional[dict] = None, use_kernel: bool = False,
                 fuse_two_pass: bool = False,
                 ert_eps: Optional[float] = None, device=None):
        if fuse_two_pass and not use_kernel:
            raise ValueError("fuse_two_pass routes through the Pallas "
                             "kernel — pass use_kernel=True")
        nets = tuple(plcore.plcore_decls(cfg))
        params = {net: params[net] for net in nets}
        if quant is not None:
            quant = {net: quant[net] for net in nets}
        self.device = device
        if device is not None:
            # packing below runs on committed inputs, so the packed
            # layout lands on the same device
            params = jax.device_put(params, device)
            if quant is not None:
                quant = jax.device_put(quant, device)
        self.cfg = cfg
        self.use_kernel = use_kernel
        self.fuse_two_pass = fuse_two_pass
        self.ert_eps = cfg.ert_eps if ert_eps is None else float(ert_eps)
        self.packed = None
        if use_kernel:
            from repro.kernels import ops as kops
            q = quant or {}
            self.packed = {
                net: kops.stack_plcore_weights(cfg, params[net], q.get(net))
                for net in nets}
        self.params = params
        self.quant = quant
        if self.packed is not None:
            # materialize now: packing cost is paid at load, not first
            # call
            jax.block_until_ready(self.packed)

    def commit(self, x) -> jax.Array:
        """A host-side tile buffer as a fresh device array beside this
        scene's weights (on ``device``, else the default device) — fresh,
        because off-CPU the tile programs donate their ray buffers."""
        if self.device is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.device)

    def render_rays(self, rays_o, rays_d, key=None, *,
                    ert_eps: Optional[float] = None) -> dict:
        """Render one ray batch. On non-CPU backends rays_o/rays_d are
        DONATED to the program (the streaming-serving contract) — pass a
        fresh batch (or an explicit copy) per call there."""
        eps = self.ert_eps if ert_eps is None else float(ert_eps)
        fn = _ray_fn(self.cfg, self.use_kernel, eps, self.fuse_two_pass)
        return fn(self.params, self.quant, self.packed, rays_o, rays_d, key)

    def render_image(self, rays_o, rays_d, *, rays_per_batch: int = 4096,
                     ert_eps: Optional[float] = None) -> jnp.ndarray:
        return render_image_single(
            self.cfg, self.params, rays_o, rays_d, quant=self.quant,
            packed=self.packed, use_kernel=self.use_kernel,
            fuse_two_pass=self.fuse_two_pass,
            rays_per_batch=rays_per_batch,
            ert_eps=self.ert_eps if ert_eps is None else ert_eps)

    def _cone_radii(self, o_tile, radii):
        """A cone tile's (n, 1) radii beside its rays: zeros, a
        zero-radius cone per ray, when none are given (the same program
        compiles either way)."""
        if radii is None:
            return self.commit(np.zeros((o_tile.shape[0], 1), np.float32))
        return radii

    def render_tile(self, o_tile, d_tile,
                    ert_eps: Optional[float] = None,
                    coarse_only: bool = False,
                    budget: Optional[int] = None,
                    alive=None, radii=None) -> jnp.ndarray:
        """Render ONE pre-coalesced ray tile -> rgb (n, 3). The serving
        engine's dispatch path: fixed tile shapes hit the same compiled
        program every call (no per-request retrace), and the tile body is
        identical to ``render_image``'s per-tile body, so scattered
        pixels match the per-request render bit-for-bit. Off-CPU the
        tile buffers are DONATED — pass fresh arrays per dispatch.
        ``coarse_only=True`` is the overload-degradation program: the
        coarse pass only, ~1/3 of the sample budget (see ``_tile_fn``).

        ``budget`` (adaptive sampling) renders this tile with
        ``n_fine=budget`` instead of the config's full budget: the
        replaced cfg keys its own compiled program, so each budget class
        is a distinct fixed-shape artifact reused across tiles of that
        class. ``alive`` is the optional per-ray dead-row mask (trunk-memo
        hits enter dead; requires the fused-kernel path).

        ``radii`` (cone configs): the tile's (n, 1) per-ray pixel radii,
        a device array like the rays (donated too)."""
        eps = self.ert_eps if ert_eps is None else float(ert_eps)
        cfg = self.cfg
        if cfg.cone:
            if coarse_only or budget is not None or alive is not None:
                mode = ("coarse_only degradation" if coarse_only
                        else "adaptive sampling")
                raise ValueError(f"{mode} does not render cone (Mip-NeRF) "
                                 f"scenes")
            fn = _tile_fn(cfg, self.use_kernel, eps, self.fuse_two_pass)
            return fn(self.params, self.quant, self.packed, o_tile, d_tile,
                      self._cone_radii(o_tile, radii))
        if budget is not None and int(budget) != cfg.n_fine:
            cfg = dataclasses.replace(cfg, n_fine=int(budget))
        if alive is not None:
            fn = _tile_fn(cfg, self.use_kernel, eps, self.fuse_two_pass,
                          coarse_only, adaptive=True)
            return fn(self.params, self.quant, self.packed, o_tile, d_tile,
                      alive)
        fn = _tile_fn(cfg, self.use_kernel, eps, self.fuse_two_pass,
                      coarse_only)
        return fn(self.params, self.quant, self.packed, o_tile, d_tile)

    def tile_program(self, o_tile, d_tile):
        """The compiled program ``render_tile`` dispatches for this tile
        shape (a ``jax.stages.Compiled``: ``as_text()`` shows what the
        device runs, e.g. whether the Mosaic kernel is in it)."""
        fn = _tile_fn(self.cfg, self.use_kernel, self.ert_eps,
                      self.fuse_two_pass)
        extra = (self._cone_radii(o_tile, None),) if self.cfg.cone else ()
        return fn.lower(self.params, self.quant, self.packed, o_tile,
                        d_tile, *extra).compile()

    def render_tile_oracle(self, o_tile, d_tile,
                           ert_eps: Optional[float] = None,
                           radii=None) -> jnp.ndarray:
        """The retry ladder's LAST rung: render one tile through the
        bit-exact oracle program. For a ``fuse_two_pass`` instance that
        is the two-dispatch kernel path (coarse and fine as separate
        Pallas dispatches — PR 2's regression oracle, bit-identical to
        the fused kernel by construction and pinned so in tests); for
        everything else it is the primary tile program itself, so the
        call is simply a fresh synchronous dispatch. Either way the
        pixels equal the healthy primary path's bit-for-bit — recovery
        through the oracle is invisible in delivered framebuffers. The
        fault-injection plan never wraps this path: it is the trusted
        floor the ladder stands on. A cone scene's oracle is the XLA
        program of the same math (``plcore.render_rays_cone``): close to
        the kernel's pixels, not bit-identical."""
        eps = self.ert_eps if ert_eps is None else float(ert_eps)
        if self.cfg.cone:
            fn = _tile_fn(self.cfg, False, eps, False)
            return fn(self.params, self.quant, None, o_tile, d_tile,
                      self._cone_radii(o_tile, radii))
        fn = _tile_fn(self.cfg, self.use_kernel, eps, False)
        return fn(self.params, self.quant, self.packed, o_tile, d_tile)

    def dispatch_tile(self, o_tile, d_tile, *,
                      ert_eps: Optional[float] = None,
                      coarse_only: bool = False,
                      budget: Optional[int] = None,
                      alive=None, radii=None,
                      tracer=None, trace_attrs=None):
        """The pipelined executor's entry point: dispatch ONE coalesced
        ray tile and return ``(rgb, {})`` — ``rgb`` an UN-BLOCKED device
        array (jax async dispatch: the host returns as soon as the
        program is enqueued, so the executor can dispatch tile k+1 and
        scatter tile k-1 while the device computes tile k; materialize
        with ``np.asarray`` only at a drain point). The empty dict keeps
        the pair the benchmark's tests unpack when they wrap this method;
        nothing is accounted in it. ``coarse_only`` selects the
        overload-degradation program. ``tracer``/``trace_attrs`` record
        the host-side enqueue as a ``plcore.dispatch`` span — it covers
        program enqueue only, not device compute (which the executor's
        ``tile.device_compute`` span measures at the drain)."""
        tr = tracer if tracer is not None else NULL_TRACER
        with tr.span("plcore.dispatch", cat="plcore") as sp:
            rgb = self.render_tile(o_tile, d_tile, ert_eps=ert_eps,
                                   coarse_only=coarse_only, budget=budget,
                                   alive=alive, radii=radii)
            if sp is not None:
                sp.attrs.update(rays=int(o_tile.shape[0]),
                                coarse_only=bool(coarse_only),
                                **(trace_attrs or {}))
        return rgb, {}


# ----------------------------------------------------------------- ASDR -----
# Adaptive per-ray sample budgets + cross-ray trunk memoization. The host
# side of the scheme lives here: a load-time coarse probe calibrates a
# per-scene density grid (core.sampling.SampleStats), rays classify into
# fine-sample budget classes from the stats along their frustum, and the
# position-only trunk half of the coarse MLP is memoized per calibration
# voxel (core.sampling.TrunkMemo) so provably-empty, fully-memo-resident
# rays enter the fused two-pass kernel as DEAD rows — the existing ERT
# skip then drops their fine pass, so the saving shows up in measured
# tile latency, not just in counters.

_TRUNK_JITS: dict = {}
_RECON_JITS: dict = {}


def _trunk_rows_fn(cfg: NerfConfig):
    """Compiled probe/memo program: positions (M, 3) -> f32 rows (M, 1+W)
    of ``sigma|feat`` from the COARSE trunk. The exact trunk the render
    paths run (same encoding, same quant slices), so a memoized row is
    bit-identical to recomputing it at the same position."""
    fn = _TRUNK_JITS.get(cfg)
    if fn is None:
        from repro.core.encoding import nerf_encoding
        from repro.core.mlp import nerf_trunk_apply

        def run(params_c, quant_c, pts):
            cdt = jnp.dtype(cfg.compute_dtype)
            pe = nerf_encoding(pts, cfg.pos_freqs).astype(cdt)
            if cdt != jnp.float32:
                params_c = jax.tree.map(lambda a: a.astype(cdt), params_c)
            sigma, feat = nerf_trunk_apply(cfg, params_c, pe, quant=quant_c)
            return jnp.concatenate(
                [sigma[..., None].astype(jnp.float32),
                 feat.astype(jnp.float32)], axis=-1)

        fn = jax.jit(run)
        _TRUNK_JITS[cfg] = fn
    return fn


def _recon_fn(cfg: NerfConfig):
    """Compiled dead-row reconstruction: memoized trunk rows -> pixels.
    Gathered ``sigma`` (R, C) / ``feat`` (R, C, W) rows feed the COARSE
    color branch + VRU + white background — the coarse-only render of the
    full pipeline with the trunk matmuls replaced by memo reads. Valid
    for the rays it is applied to (provably-empty frustums: fine ~= coarse
    ~= white background); the fig8 PSNR gate bounds the residual."""
    fn = _RECON_JITS.get(cfg)
    if fn is None:
        from repro.core import sampling, volume
        from repro.core.encoding import nerf_encoding
        from repro.core.mlp import nerf_color_apply

        def run(params_c, quant_c, sigma, feat, d_tile, t):
            cdt = jnp.dtype(cfg.compute_dtype)
            deltas = sampling.deltas_from_t(t, far_cap=1e10)
            dirs = d_tile / jnp.linalg.norm(d_tile, axis=-1, keepdims=True)
            pe_dir = nerf_encoding(dirs, cfg.dir_freqs).astype(cdt)[
                ..., None, :]
            if cdt != jnp.float32:
                params_c = jax.tree.map(lambda a: a.astype(cdt), params_c)
            rgb_s = nerf_color_apply(cfg, params_c, feat.astype(cdt),
                                     pe_dir, quant=quant_c)
            rgb, aux = volume.render_parallel(
                sigma.astype(jnp.float32), rgb_s.astype(jnp.float32),
                deltas)
            return volume.white_background(rgb, aux["acc"])

        fn = jax.jit(run)
        _RECON_JITS[cfg] = fn
    return fn


def trunk_rows(pp: "PackedPlcore", pts: np.ndarray,
               chunk: int = 2048) -> np.ndarray:
    """Evaluate coarse-trunk ``sigma|feat`` rows at host positions
    (M, 3) -> (M, 1+W) f32, through the fixed-shape compiled program in
    padded chunks (one compiled shape regardless of M)."""
    fn = _trunk_rows_fn(pp.cfg)
    params_c = pp.params["coarse"]
    quant_c = (pp.quant or {}).get("coarse")
    pts = np.asarray(pts, np.float32)
    out = []
    for s in range(0, pts.shape[0], chunk):
        blk = pts[s:s + chunk]
        pad = chunk - blk.shape[0]
        if pad:
            blk = np.concatenate([blk, np.zeros((pad, 3), np.float32)])
        rows = np.asarray(fn(params_c, quant_c, jnp.asarray(blk)))
        out.append(rows[:chunk - pad] if pad else rows)
    W = pp.cfg.trunk_width
    return (np.concatenate(out) if out
            else np.zeros((0, 1 + W), np.float32))


def build_scene_aux(pp: "PackedPlcore", *, grid_res: int = 48,
                    n_classes: int = 3, memo_mb: float = 32.0,
                    probe_hw: int = 12, probe_radius: float = 4.0,
                    empty_tau: float = 1e-2, n_probe_theta: int = 8,
                    warm_memo: bool = True):
    """Per-scene density calibration: the cheap coarse-only probe pass at
    scene load. Renders no pixels — it evaluates the coarse TRUNK at the
    deterministic coarse sample positions of a small spherical pose sweep
    (the serving loadgen's pose distribution: theta 0..360, phi -35..-15,
    radius 4) and accumulates max-sigma per calibration voxel into a
    ``SampleStats`` record. Returns a ``sampling.SceneAux`` to store
    alongside the PackedPlcore in the SceneCache entry.

    ``warm_memo=True`` pre-fills the trunk memo with rows for the EMPTY
    probed voxels (the only rows dead-row detection needs resident), up
    to the memo's byte capacity; serve-time dispatches top up the rest."""
    if pp.cfg.cone:
        raise ValueError("adaptive sampling does not render cone "
                         "(Mip-NeRF) scenes")
    from repro.core import sampling
    from repro.data import rays as drays
    cfg = pp.cfg
    t_row = np.asarray(sampling.stratified(
        cfg.near, cfg.far, cfg.n_coarse, (1,), None))[0].astype(np.float32)
    os_, ds_ = [], []
    for phi in (-35.0, -15.0):
        for th in np.linspace(0.0, 360.0, n_probe_theta, endpoint=False):
            c2w = drays.pose_spherical(float(th), float(phi), probe_radius)
            o, d = drays.camera_rays(c2w, probe_hw, probe_hw,
                                     0.9 * probe_hw)
            os_.append(np.asarray(o).reshape(-1, 3))
            ds_.append(np.asarray(d).reshape(-1, 3))
    o = np.concatenate(os_).astype(np.float32)
    d = np.concatenate(ds_).astype(np.float32)
    pts = o[:, None, :] + t_row[None, :, None] * d[:, None, :]
    rows = trunk_rows(pp, pts.reshape(-1, 3))
    sigma = rows[:, 0].reshape(pts.shape[:2])
    stats = sampling.build_sample_stats(
        pts, sigma, grid_res=grid_res, n_classes=n_classes,
        empty_tau=empty_tau)
    memo = sampling.TrunkMemo(capacity_mb=memo_mb)
    aux = sampling.SceneAux(stats=stats, memo=memo, t_row=t_row)
    if warm_memo:
        g = stats.grid.reshape(-1)
        p = stats.probed.reshape(-1)
        empty = np.nonzero(p & (g < stats.empty_tau))[0]
        row_b = (1 + cfg.trunk_width) * 4 + 48
        cap = max(0, memo.capacity_bytes // row_b)
        empty = empty[:cap]
        if empty.size:
            centers = stats.voxel_centers(empty)
            memo.insert("c", empty, trunk_rows(pp, centers))
    return aux


class AdaptiveRenderer:
    """Adaptive Sample-budget Dispatch + tRunk memoization, per scene.

    Wraps a fused-kernel PackedPlcore plus its SceneAux
    and renders tiles three-tier:

    * every ray classifies into a fine-sample budget class from the
      calibration stats along its frustum (``classify_rays``); callers
      coalesce rays by (scene, class) and dispatch each tile at its
      class's ``n_fine`` budget — a per-budget compiled program;
    * rays whose frustum is fully memo-resident AND provably empty enter
      the fused kernel as DEAD rows: the kernel's ERT skip drops their
      fine pass, and their pixels are reconstructed from the
      memoized trunk rows host-side (``_recon_fn`` — color branch + VRU
      only, no trunk matmuls);
    * a tile whose rays are ALL dead skips the kernel dispatch entirely.

    Counters (``report()``) feed the engine's ``sampling`` stats block.
    """

    def __init__(self, pp: "PackedPlcore", aux, budgets=None, *,
                 topup_voxels: int = 1024):
        if not (pp.use_kernel and pp.fuse_two_pass):
            raise ValueError("adaptive sampling rides the fused two-pass "
                             "kernel's dead-row skip — build the "
                             "PackedPlcore with use_kernel=True, "
                             "fuse_two_pass=True")
        from repro.core import sampling
        self.pp = pp
        self.aux = aux
        self.budgets = (tuple(int(b) for b in budgets) if budgets
                        else sampling.default_budget_classes(pp.cfg.n_fine))
        self.topup_voxels = int(topup_voxels)
        self.counters = {"tiles": 0, "rays": 0, "dead_rays": 0,
                         "full_dead_tiles": 0, "skipped_fine_samples": 0,
                         "topup_voxels": 0}
        self.budget_tiles = {b: 0 for b in self.budgets}
        self.budget_rays = {b: 0 for b in self.budgets}

    # ------------------------------------------------------------- classify
    def _frustum_pts(self, o: np.ndarray, d: np.ndarray) -> np.ndarray:
        t = self.aux.t_row
        return (o[:, None, :] + t[None, :, None] * d[:, None, :]).astype(
            np.float32)

    def classify_rays(self, o, d) -> np.ndarray:
        """Rays (R, 3)x2 -> budget-class index (R,) into ``budgets``."""
        o = np.asarray(o, np.float32)
        d = np.asarray(d, np.float32)
        return self.aux.stats.classify(self._frustum_pts(o, d),
                                       self.budgets)

    def dead_hint(self, o, d) -> np.ndarray:
        """Stats-only provisional deadness (R,) bool: every frustum voxel
        probed AND below empty_tau. Residency is NOT checked — the
        per-tile top-up makes hinted rows resident at dispatch — so the
        hint is cheap enough for schedulers to sort hinted-dead rays
        FIRST within a budget bucket. That clusters them into tiles that
        resolve fully dead and skip the kernel dispatch outright."""
        o = np.asarray(o, np.float32)
        d = np.asarray(d, np.float32)
        return self.aux.stats.empty_mask(
            self.aux.stats.voxel_ids(self._frustum_pts(o, d)))

    # ------------------------------------------------------------- dead rows
    def dead_and_rows(self, o: np.ndarray, d: np.ndarray):
        """Per-tile dead-row resolution: top up the memo (capped), then
        return (dead (R,) bool, vox (R, C) ids, sigma (R, C), feat
        (R, C, W)) with the memoized rows gathered for dead rays (zeros
        elsewhere). Hit/miss counters tick only for rows actually
        consumed (the dead rays' lookups)."""
        stats, memo = self.aux.stats, self.aux.memo
        pts = self._frustum_pts(o, d)
        vox = stats.voxel_ids(pts)
        flat = np.unique(vox)
        g = stats.grid.reshape(-1)[flat]
        p = stats.probed.reshape(-1)[flat]
        cand = flat[p & (g < stats.empty_tau)]
        pinned = np.zeros(0, np.int64)
        if cand.size:
            # pin THIS tile's candidate rows (resident + about-to-insert)
            # so the top-up's own LRU eviction can't drop rows the tile
            # is about to consume — pins release once the rows are read
            pinned = cand
            memo.pin("c", pinned)
            missing = cand[~memo.contains("c", cand)][:self.topup_voxels]
            if missing.size:
                rows = trunk_rows(self.pp, stats.voxel_centers(missing))
                memo.insert("c", missing, rows)
                self.counters["topup_voxels"] += int(missing.size)
        resident = memo.contains("c", vox.reshape(-1)).reshape(vox.shape)
        dead = resident.all(axis=1) & stats.empty_mask(vox)
        R, C = vox.shape
        W = self.pp.cfg.trunk_width
        sigma = np.zeros((R, C), np.float32)
        feat = np.zeros((R, C, W), np.float32)
        idx = np.nonzero(dead)[0]
        if idx.size:
            hit, rows = memo.lookup("c", vox[idx].reshape(-1))
            rows = rows.reshape(idx.size, C, 1 + W)
            sigma[idx] = rows[..., 0]
            feat[idx] = rows[..., 1:]
        if pinned.size:
            memo.unpin("c", pinned)
        return dead, vox, sigma, feat

    # -------------------------------------------------------------- render
    def render_tile(self, o_tile, d_tile, budget: Optional[int] = None,
                    ert_eps: Optional[float] = None,
                    resolve_dead: bool = True):
        """Render one (budget-pure) coalesced tile adaptively ->
        (rgb (R, 3) device array, info dict). The kernel dispatch carries
        the dead-row mask; dead pixels are overwritten by the memo
        reconstruction; an all-dead tile never reaches the kernel.
        ``resolve_dead=False`` skips the memo lookup outright — callers
        that pre-sorted rays by ``dead_hint`` pass it for tiles whose
        rays are all provably NON-empty (dead ⊆ hinted-dead, so the
        resolution could only return all-False there)."""
        o = np.asarray(o_tile, np.float32)
        d = np.asarray(d_tile, np.float32)
        R = o.shape[0]
        b = int(budget) if budget is not None else int(self.budgets[-1])
        if resolve_dead:
            dead, vox, sigma, feat = self.dead_and_rows(o, d)
        else:
            dead = np.zeros(R, bool)
            sigma = feat = None
        n_dead = int(dead.sum())
        info = {"rays": R, "dead": n_dead, "budget": b,
                "full_dead": bool(n_dead == R),
                "skipped_fine_samples": n_dead * b}
        recon = None
        if n_dead:
            # memoized sigma rows that relu to EXACTLY zero composite to
            # exactly the white background (w_i = 0, acc = 0) — the recon
            # program would return all-ones bit-for-bit, so skip the
            # dispatch outright. Only "tinted" empty space (sigma in
            # (0, tau)) pays for the compiled reconstruction.
            if bool((sigma[dead] <= 0.0).all()):
                recon = np.ones((R, 3), np.float32)
            else:
                t = np.broadcast_to(self.aux.t_row,
                                    (R, self.aux.t_row.size))
                recon = _recon_fn(self.pp.cfg)(
                    self.pp.params["coarse"],
                    (self.pp.quant or {}).get("coarse"),
                    jnp.asarray(sigma), jnp.asarray(feat),
                    jnp.asarray(d), jnp.asarray(np.ascontiguousarray(t)))
        if n_dead == R:
            rgb = recon
            self.counters["full_dead_tiles"] += 1
        else:
            alive = (jnp.asarray(~dead, jnp.float32)
                     if n_dead else None)
            rgb = self.pp.render_tile(jnp.asarray(o), jnp.asarray(d),
                                      ert_eps=ert_eps, budget=b,
                                      alive=alive)
            if n_dead:
                rgb = jnp.where(jnp.asarray(dead)[:, None], recon, rgb)
        self.counters["tiles"] += 1
        self.counters["rays"] += R
        self.counters["dead_rays"] += n_dead
        self.counters["skipped_fine_samples"] += info["skipped_fine_samples"]
        self.budget_tiles[b] = self.budget_tiles.get(b, 0) + 1
        self.budget_rays[b] = self.budget_rays.get(b, 0) + R
        return rgb, info

    def render_image(self, rays_o, rays_d, *,
                     rays_per_tile: Optional[int] = None) -> np.ndarray:
        """Full-image adaptive render: classify every ray, coalesce by
        budget class into fixed-shape tiles (pad tail tiles by repeating
        their last ray), dispatch each at its class budget, scatter the
        pixels back. The benchmark/PSNR entry point."""
        o = np.asarray(rays_o, np.float32)
        d = np.asarray(rays_d, np.float32)
        shape = o.shape[:-1]
        o = o.reshape(-1, 3)
        d = d.reshape(-1, 3)
        rt = int(rays_per_tile or self.pp.cfg.rays_per_tile)
        cls = self.classify_rays(o, d)
        hint = self.dead_hint(o, d)
        out = np.zeros((o.shape[0], 3), np.float32)
        for c, b in enumerate(self.budgets):
            idx = np.nonzero(cls == c)[0]
            if not idx.size:
                continue
            # hinted-dead rays first: they pack into all-dead tiles that
            # skip the kernel dispatch (stable, so output is deterministic)
            idx = idx[np.argsort(~hint[idx], kind="stable")]
            # minority classes shrink to the next power-of-two tile so a
            # 6-ray class doesn't pad to a full ``rt`` dispatch; shapes
            # stay canonical (bounded program-cache growth, <= 2x pad)
            rt_c = (rt if idx.size >= rt
                    else max(32, 1 << int(np.ceil(np.log2(idx.size)))))
            for s in range(0, idx.size, rt_c):
                span = idx[s:s + rt_c]
                pad = rt_c - span.size
                take = (np.concatenate([span, np.repeat(span[-1:], pad)])
                        if pad else span)
                rgb, _ = self.render_tile(
                    o[take], d[take], budget=b,
                    resolve_dead=bool(hint[take].any()))
                out[span] = np.asarray(rgb)[:span.size]
        return out.reshape(*shape, 3)

    # ------------------------------------------------------------- reports
    def report(self) -> dict:
        """The ``sampling`` stats block: budget histogram + memo traffic
        + dead-row/skipped-sample totals for this scene."""
        c = dict(self.counters)
        return {
            **c,
            "dead_ray_fraction": (round(c["dead_rays"] / c["rays"], 4)
                                  if c["rays"] else 0.0),
            "budgets": list(self.budgets),
            "budget_tiles": {str(b): n for b, n in
                             sorted(self.budget_tiles.items())},
            "budget_rays": {str(b): n for b, n in
                            sorted(self.budget_rays.items())},
            "memo": self.aux.memo.stats(),
        }
