"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernel body then runs in Python
on CPU — the validation mode this container uses); on a real TPU backend it
compiles through Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.nerf_icarus import NerfConfig
from repro.core import rmcm
from repro.kernels import fused_plcore as _fp
from repro.kernels import rmcm_matmul as _rm


def interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


def _interpret(cfg: NerfConfig, interpret: Optional[bool]) -> bool:
    """The call's own ``interpret``, else ``cfg.kernel_interpret``, else
    the platform default."""
    if interpret is not None:
        return interpret
    if cfg.kernel_interpret is not None:
        return cfg.kernel_interpret
    return interpret_default()


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m


# ------------------------------------------------------------ rmcm matmul --
def rmcm_matmul(x, packed: dict, *, bm: int = 128, bn: int = 128,
                bk: int = 256, interpret: Optional[bool] = None):
    """y = x @ W_rmcm for (..., K) inputs (leading dims flattened)."""
    it = interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    y = _rm.rmcm_matmul(x.reshape(-1, x.shape[-1]), packed,
                        bm=bm, bn=bn, bk=bk, interpret=it)
    return y.reshape(*lead, y.shape[-1])


# --------------------------------------------------- fused PLCore weights --
def _pack_signs(sign):
    """(K, N) bool -> (K/8, N) uint8 (K % 8 == 0)."""
    K = sign.shape[0]
    assert K % 8 == 0, K
    sp = sign.reshape(K // 8, 8, *sign.shape[1:]).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 8, *([1] * (sign.ndim - 1)))
    return jnp.sum(sp << shifts, axis=1).astype(jnp.uint8)


def _place_rows(src, rows: int):
    """Zero-pad a (k, n) array to (rows, n)."""
    return jnp.pad(src, ((0, rows - src.shape[0]), (0, 0)))


# Pack-call counter: PackedPlcore packs once per param set at load time;
# tests assert render calls never re-pack. Counts traces, not executions —
# a pack inside a jitted call re-executes its pad/stack ops every dispatch
# even though the counter only ticks at trace time, which is exactly why
# the serving path pre-packs. Registry-backed (process-global metrics
# registry) so the Prometheus/snapshot exporters see it; the accessor API
# is unchanged.
from repro.obs.metrics import global_registry as _obs_registry

_PACKS = _obs_registry().counter(
    "plcore_weight_packs_total",
    "stack_plcore_weights invocations (trace-time)")


def pack_count() -> int:
    return int(_PACKS.value)


# Kernel-dispatch counter (same trace-time semantics as pack_count): each
# pallas_call issued by the wrappers below ticks it once. The two-dispatch
# coarse/fine chain ticks twice per render; the fused two-pass chain must
# tick exactly ONCE — tests assert the C1 "one kernel per ray tile" claim
# through this counter.
_DISPATCHES = _obs_registry().counter(
    "plcore_kernel_dispatches_total",
    "pallas_call kernel launches issued (trace-time)")


def dispatch_count() -> int:
    return int(_DISPATCHES.value)


# Rays per inner-loop step of the last two-pass program built (set at
# trace time, like the counters above): which block the served kernel
# steps by.
_RAY_BLOCK = _obs_registry().gauge(
    "plcore_kernel_ray_block",
    "rays per inner-loop step of the last two-pass kernel traced")


def stack_plcore_weights(cfg: NerfConfig, params: dict,
                         quant: Optional[dict] = None) -> dict:
    """Kernel weight layout: trunk stacked (L, P, W) with per-layer row
    semantics (layer 0: PE rows; skip layer: [h | PE] rows; else: h rows);
    color0 row-padded to P2. P/P2 are 128-aligned for the MXU.

    quant != None -> RMCM layout: uint8 magnitudes + bit-packed signs +
    (1, out) scales for trunk/feat/color0 (MONB); sigma/rgb stay exact
    (SONB)."""
    _PACKS.inc()
    W, C = cfg.trunk_width, cfg.color_width
    pe, de = cfg.pos_enc_dim, cfg.dir_enc_dim
    L = cfg.trunk_layers
    P = _rup(W + pe, 128)
    P2 = _rup(W + de, 128)
    out = {}

    tb = jnp.stack([params["trunk"][f"l{i}"]["b"] for i in range(L)])
    out["trunk_b"] = tb.astype(jnp.float32)
    out["sigma_w"] = params["sigma"]["w"].astype(jnp.float32)
    out["sigma_b"] = params["sigma"]["b"].astype(jnp.float32)
    out["feat_b"] = params["feat"]["b"].astype(jnp.float32)
    out["color0_b"] = params["color0"]["b"].astype(jnp.float32)
    out["rgb_w"] = params["rgb"]["w"].astype(jnp.float32)
    out["rgb_b"] = params["rgb"]["b"].astype(jnp.float32)

    if quant is None:
        out["trunk_w"] = jnp.stack(
            [_place_rows(params["trunk"][f"l{i}"]["w"].astype(jnp.float32), P)
             for i in range(L)])
        out["feat_w"] = params["feat"]["w"].astype(jnp.float32)
        out["color0_w"] = _place_rows(
            params["color0"]["w"].astype(jnp.float32), P2)
        return out

    def q3(qd, rows):
        """One quantized matrix -> (mag (rows,n) u8, sgn (rows/8,n) u8,
        scale (1,n) f32)."""
        mag = _place_rows(qd["mag"], rows)
        sgn = _place_rows(qd["sign"], rows)
        return mag, _pack_signs(sgn), qd["scale"].astype(jnp.float32)

    mags, sgns, scls = [], [], []
    for i in range(L):
        m, s, sc = q3(quant["trunk"][f"l{i}"]["w"], P)
        mags.append(m), sgns.append(s), scls.append(sc)
    out["trunk_mag"] = jnp.stack(mags)
    out["trunk_sgn"] = jnp.stack(sgns)
    out["trunk_scl"] = jnp.stack(scls)
    out["feat_mag"], out["feat_sgn"], out["feat_scl"] = q3(
        quant["feat"]["w"], _rup(W, 8))
    out["color0_mag"], out["color0_sgn"], out["color0_scl"] = q3(
        quant["color0"]["w"], P2)
    return out


# ------------------------------------------------------------ fused render --
# VMEM model shared by the tile pickers and the compiler's scoped-VMEM
# limit (the kernels are compiled with ``vmem_limit_bytes`` = this model,
# so a model that under-counts fails to compile — tests/test_tpu_compile
# holds it to that at CONFIG). Mosaic lays every block and value out in
# (sublane, 128-lane) tiles, so a row of any width costs whole lanes.
def _row_bytes(width: int, itemsize: int = 4) -> int:
    return itemsize * _rup(width, 128)


def _tile_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of one array in (8 * 4 / itemsize, 128) tiles."""
    shape = tuple(shape)
    if len(shape) == 1:
        shape = (1,) + shape
    lead = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
    return lead * _rup(shape[-2], 32 // itemsize) * _row_bytes(shape[-1],
                                                                itemsize)


def kernel_weight_shapes(cfg: NerfConfig, quantized: bool) -> list:
    """(shape, itemsize) of each array of one network's packed layout as
    the kernels receive it (``stack_plcore_weights``; 1-D biases as
    (1, n) rows) — in ``fused_plcore._weight_order`` order."""
    W, C, L = cfg.trunk_width, cfg.color_width, cfg.trunk_layers
    P = _rup(W + cfg.pos_enc_dim, 128)
    P2 = _rup(W + cfg.dir_enc_dim, 128)
    heads = {"trunk_b": ((L, W), 4), "sigma_w": ((W, 1), 4),
             "sigma_b": ((1, 1), 4), "feat_b": ((1, W), 4),
             "color0_b": ((1, C), 4), "rgb_w": ((C, 3), 4),
             "rgb_b": ((1, 3), 4)}
    if quantized:
        Wf = _rup(W, 8)
        mats = {"trunk_mag": ((L, P, W), 1), "trunk_sgn": ((L, P // 8, W), 1),
                "trunk_scl": ((L, 1, W), 4), "feat_mag": ((Wf, W), 1),
                "feat_sgn": ((Wf // 8, W), 1), "feat_scl": ((1, W), 4),
                "color0_mag": ((P2, C), 1), "color0_sgn": ((P2 // 8, C), 1),
                "color0_scl": ((1, C), 4)}
    else:
        mats = {"trunk_w": ((L, P, W), 4), "feat_w": ((W, W), 4),
                "color0_w": ((P2, C), 4)}
    shapes = {**heads, **mats}
    return [shapes[k] for k in _fp._weight_order(quantized)]


def kernel_weight_vmem_bytes(cfg: NerfConfig, quantized: bool) -> int:
    """VMEM one network's packed layout occupies (single-buffered)."""
    return sum(_tile_bytes(s, i) for s, i in
               kernel_weight_shapes(cfg, quantized))


def _act_row_bytes(cfg: NerfConfig) -> int:
    """VMEM per sample row of one pass's live values: the PE, three
    trunk-width activations, the fused sigma|feat head, the colour
    branch before and after its ReLU, and five narrow per-sample columns
    (position, t, delta, RGB logits, RGB)."""
    W, C = cfg.trunk_width, cfg.color_width
    return (_row_bytes(cfg.pos_enc_dim) + 3 * _row_bytes(W)
            + _row_bytes(W + 1) + 2 * _row_bytes(C) + 5 * _row_bytes(3))


def _net_scratch_bytes(cfg: NerfConfig, n_samples: int) -> int:
    """Per network pass: the (W, W+1) sigma|feat head matrix the body
    builds, and the pass's (N, N) prefix-sum triangle."""
    W = cfg.trunk_width
    return (_rup(W, 8) * _row_bytes(W + 1)
            + _rup(n_samples, 8) * _row_bytes(n_samples))


# Sample rows one inner-loop step of the one-pass kernel works on: the
# compiled body (and its VMEM scratch) grows with it, the MXU's row
# utilization too.
_BLOCK_SAMPLE_ROWS = 512
# The same budget for the two-pass kernels: a block's rays times the rows
# of its widest pass (NeRF's fine pass over n_coarse + n_fine samples, a
# cone level's n_coarse intervals). On a v5e a 512-ray tile took 8.76 /
# 8.27 / 8.17 ms at NeRF blocks of 1 / 2 / 4 rays (192 / 384 / 768
# rows), and 7.78 / 6.93 / 7.32 ms at cone blocks of 1 / 4 / 8 (128 /
# 512 / 1024 rows): 768 keeps the best of each.
_TWO_PASS_BLOCK_ROWS = 768


def pick_ray_block(n_samples: int) -> int:
    """Rays per inner-loop step of the ONE-PASS kernel on the chip: the
    most whose samples fit ``_BLOCK_SAMPLE_ROWS`` rows — a power of two
    from 8 up, else ONE ray. Its (rt, N) t and delta blocks are wider
    than 128 lanes, and Mosaic loads a block of 2 or 4 rows at a dynamic
    offset only from arrays at most 128 lanes wide. The two-pass kernels
    have no such array and take ``pick_two_pass_block``."""
    g = _BLOCK_SAMPLE_ROWS // max(1, n_samples)
    if g < 8:
        return 1
    p = 8
    while 2 * p <= g:
        p *= 2
    return p


def _ray_block(rt: int, want: int) -> int:
    """The block the one-pass kernel's loop steps by: ``want`` (a power of
    two) halved until it divides rt, and 1 below 8 (its wide (rt, N)
    blocks, see ``pick_ray_block``)."""
    g = min(want, rt)
    while g > 1 and rt % g:
        g //= 2
    return g if g >= 8 or g == rt else 1


def pick_two_pass_block(cfg: NerfConfig, rt: int) -> int:
    """Rays per inner-loop step of the two-pass kernels on the chip: the
    largest power of two G dividing rt with G times the rows of the
    widest pass within ``_TWO_PASS_BLOCK_ROWS`` (1 if one ray's pass is
    wider). Every trunk weight load and every per-ray contraction against
    a constant matrix (prefix-sum triangles, row sums, the colour
    branch's direction half) then streams G rays' rows. Their per-ray
    blocks are at most 9 lanes wide (o, d, radius or mask in, the record
    out), so blocks of 2 and 4 load at a dynamic offset."""
    rows = cfg.n_coarse if cfg.cone else cfg.n_coarse + cfg.n_fine
    g = 1
    while rt % (2 * g) == 0 and 2 * g * rows <= _TWO_PASS_BLOCK_ROWS:
        g *= 2
    return g


def fused_vmem_bytes(cfg: NerfConfig, n_samples: int, rt: int, block: int,
                     quantized: bool = False) -> int:
    """Scoped VMEM of the one-pass kernel at ray tile ``rt``: one network
    single-buffered, the double-buffered per-ray blocks (rays, t, deltas,
    mask in; rgb, w, acc out), one block's pass."""
    io = 2 * rt * (5 * _row_bytes(3) + 3 * _row_bytes(n_samples))
    return (kernel_weight_vmem_bytes(cfg, quantized) + io
            + block * n_samples * _act_row_bytes(cfg)
            + _net_scratch_bytes(cfg, n_samples))


def two_pass_vmem_bytes(cfg: NerfConfig, rt: int, block: int,
                        quantized: bool = False) -> int:
    """Scoped VMEM of the two-pass kernel at ray tile ``rt``: BOTH
    networks single-buffered (their block never moves), the
    double-buffered per-ray blocks (o, d, mask in; the (rt, 9) record
    out), and one block's two passes — coarse and fine values both
    live — with the resample's (n_fine, n_coarse - 1) one-hots and the
    rank merge's (n, n_coarse + n_fine) ones per ray. Each sample row
    also carries four trunk-width and three narrow values beside
    ``_act_row_bytes``, for what that count leaves out (Mosaic's copies
    for the body's reshapes and broadcasts; with ERT or the alive mask
    the fine pass sits behind a ``lax.cond`` and shares no buffer with
    the coarse one): compiled for a v5e at CONFIG with both on, the
    widest variant, the kernel asks 10.9 / 14.6 / 22.5 MiB at blocks of
    1 / 2 / 4 rays, 3.7-4.0 MiB a ray, where this model gives 14.3 /
    18.4 / 26.5 (without the extra values 13.0 / 15.7 / 21.0, too few
    from 4 rays on). A cone config: the same per-ray blocks and
    ``_cone_vmem_bytes``."""
    Nc, Nf = cfg.n_coarse, cfg.n_fine
    io = 2 * rt * 4 * _row_bytes(9)
    if cfg.cone:
        return _cone_vmem_bytes(cfg, block, quantized) + io
    Nt = Nc + Nf
    resample = block * (3 * Nf * _row_bytes(Nc - 1)
                        + 2 * Nt * _row_bytes(Nt))
    row = (_act_row_bytes(cfg) + 4 * _row_bytes(cfg.trunk_width)
           + 3 * _row_bytes(3))
    return (2 * kernel_weight_vmem_bytes(cfg, quantized) + io
            + block * (Nc + Nt) * row + resample
            + _net_scratch_bytes(cfg, Nc) + _net_scratch_bytes(cfg, Nt))


def _cone_vmem_bytes(cfg: NerfConfig, block: int, quantized: bool) -> int:
    """The cone two-pass kernel's share of ``two_pass_vmem_bytes`` beside
    its per-ray blocks (o, d, radius in; the record out): the one
    network both levels read; the two (1, n) interval rows;
    one block's level at a time (the levels are trips of one loop), each
    sample row carrying its frustum Gaussian and IPE columns besides the
    NeRF pass's values; and the resample's (n, n) masks and selections
    per ray. At MIPNERF and one ray a block: 5.9 MiB, where the compiler
    for a v5e asks 4.5-5.0 MiB; at 4 rays, with the per-ray blocks of a
    512-ray tile, 13.3 MiB where it asks 9.3."""
    n = cfg.n_coarse
    cone_cols = 8 * _row_bytes(3)
    return (kernel_weight_vmem_bytes(cfg, quantized)
            + 2 * _tile_bytes((1, n))
            + block * n * (_act_row_bytes(cfg) + cone_cols)
            + block * 4 * _rup(n, 8) * _row_bytes(n)
            + _net_scratch_bytes(cfg, n))


def _budget(cfg: NerfConfig, vmem_budget_bytes: Optional[int]) -> int:
    if vmem_budget_bytes is None:
        return int(cfg.kernel_vmem_budget_mb * (1 << 20))
    return int(vmem_budget_bytes)


def _largest_tile(fits, cap: int) -> int:
    """Largest power-of-two rt in [8, cap] with fits(rt); 8 if none does
    (the kernel is then compiled with a limit above the budget)."""
    rt = cap
    while rt > 8 and not fits(rt):
        rt //= 2
    return rt


def pick_ray_tile(cfg: NerfConfig, n_samples: int,
                  vmem_budget_bytes: Optional[int] = None,
                  quantized: bool = False) -> int:
    """rt so the one-pass kernel's ``fused_vmem_bytes`` fits the VMEM
    budget (``cfg.kernel_vmem_budget_mb`` unless overridden)."""
    budget = _budget(cfg, vmem_budget_bytes)
    block = pick_ray_block(n_samples)
    return _largest_tile(
        lambda rt: fused_vmem_bytes(cfg, n_samples, rt,
                                    _ray_block(rt, block),
                                    quantized) <= budget, 128)


def fused_render(cfg: NerfConfig, params: Optional[dict], rays_o, rays_d, t,
                 deltas, *, quant: Optional[dict] = None,
                 packed: Optional[dict] = None, alive=None,
                 rt: Optional[int] = None,
                 vmem_budget_bytes: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Drop-in for the unfused pass: (rgb (R,3), {weights, acc}).

    ``packed``: a pre-built stack_plcore_weights layout (PackedPlcore caches
    one per param set at load time); when given, ``params``/``quant`` are
    ignored and no packing work lands in the traced program. ``alive``:
    optional (R,) mask for Cicero-style early ray termination — all-dead
    kernel tiles skip MLP+VRU work.
    """
    _DISPATCHES.inc()
    it = _interpret(cfg, interpret)
    R, N = t.shape
    if packed is None:
        packed = stack_plcore_weights(cfg, params, quant)
        quantized = quant is not None
    else:
        quantized = "trunk_mag" in packed
    rt = rt or pick_ray_tile(cfg, N, vmem_budget_bytes, quantized)
    rt = min(rt, _rup(R, 8))
    # the interpreter runs the tile as one block; on the chip the block
    # bounds the compiled body
    block = rt if it else _ray_block(rt, pick_ray_block(N))
    Rp = _rup(R, rt)
    if Rp != R:
        padn = Rp - R
        rays_o = jnp.concatenate([rays_o, rays_o[-1:].repeat(padn, 0)])
        rays_d = jnp.concatenate([rays_d, rays_d[-1:].repeat(padn, 0)])
        t = jnp.concatenate([t, t[-1:].repeat(padn, 0)])
        deltas = jnp.concatenate([deltas, deltas[-1:].repeat(padn, 0)])
        if alive is not None:   # padded rays are dead
            alive = jnp.concatenate(
                [alive, jnp.zeros((padn,), alive.dtype)])
    vmem = None if it else fused_vmem_bytes(cfg, N, rt, block, quantized)
    rgb, w, acc = _fp.fused_plcore_call(
        cfg, packed, rays_o, rays_d, t, deltas,
        rt=rt, quantized=quantized, alive=alive, interpret=it, block=block,
        vmem_limit_bytes=vmem)
    return rgb[:R], {"weights": w[:R], "acc": acc[:R]}


# ------------------------------------------------ one-kernel two-pass render --
def pick_ray_tile_two_pass(cfg: NerfConfig,
                           vmem_budget_bytes: Optional[int] = None,
                           quantized: bool = False) -> int:
    """rt for the single-dispatch two-pass kernel: the largest power of
    two (at most 512) whose ``two_pass_vmem_bytes`` fits the budget.
    BOTH networks' weight stacks occupy VMEM every grid step. Powers of
    two only, so any pow2 ray batch is tiled without padding."""
    budget = _budget(cfg, vmem_budget_bytes)
    return _largest_tile(
        lambda rt: two_pass_vmem_bytes(
            cfg, rt, pick_two_pass_block(cfg, rt), quantized) <= budget, 512)


def fused_render_two_pass(cfg: NerfConfig, packed: dict, rays_o, rays_d, *,
                          ert_eps: float = 0.0, rt: Optional[int] = None,
                          block: Optional[int] = None,
                          vmem_budget_bytes: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          alive=None, radii=None) -> dict:
    """The complete coarse -> importance -> fine render as ONE pallas_call
    per ray tile (deterministic/inference sampling; coarse weights never
    leave VMEM). ``packed``: {"coarse", "fine"} stack_plcore_weights
    layouts. ``ert_eps`` > 0 enables per-ray
    early termination inside the kernel. ``alive``: optional (R,) float
    mask — rows with 0 (adaptive trunk-memo hits) enter the kernel dead
    and skip their fine pass. ``rt``/``block``: ray tile per grid step
    and rays per inner-loop step (defaults: off the chip the whole batch,
    up to 2048 rays, as one block — the interpreter has no VMEM; on the
    chip the VMEM model's tile and ``pick_two_pass_block``). Returns
    {rgb, rgb_coarse, acc, acc_coarse, depth}, each trimmed to R rays;
    white background is the caller's composite.

    A cone config (Mip-NeRF) runs ``cone_two_pass_call`` instead, on
    ``radii`` ((R, 1) per-ray cone radius, required) and the one network
    under ``packed["coarse"]``; it takes no ERT or alive mask.
    """
    _DISPATCHES.inc()
    it = _interpret(cfg, interpret)
    from repro.core import sampling
    R = rays_o.shape[0]
    quantized = "trunk_mag" in packed["coarse"]
    if rt is None:
        rt = (min(_rup(R, 8), 2048) if it else
              pick_ray_tile_two_pass(cfg, vmem_budget_bytes, quantized))
    rt = min(rt, _rup(R, 8))
    if block is None:
        block = rt if it else pick_two_pass_block(cfg, rt)
    block = min(block, rt)
    _RAY_BLOCK.set(block)
    Rp = _rup(R, rt)
    if Rp != R:
        padn = Rp - R
        rays_o = jnp.concatenate([rays_o, rays_o[-1:].repeat(padn, 0)])
        rays_d = jnp.concatenate([rays_d, rays_d[-1:].repeat(padn, 0)])
        if alive is not None:
            # padded rows enter dead: their blocks skip the fine pass
            alive = jnp.concatenate(
                [alive, jnp.zeros((padn,), alive.dtype)])
        if radii is not None:
            radii = jnp.concatenate([radii, radii[-1:].repeat(padn, 0)])
    vmem = None if it else two_pass_vmem_bytes(cfg, rt, block, quantized)
    if cfg.cone:
        if ert_eps > 0.0 or alive is not None or radii is None:
            raise ValueError("the cone kernel takes the rays' radii and no "
                             "ERT or alive mask")
        t0_row, t1_row = sampling.cone_intervals(cfg.near, cfg.far,
                                                 cfg.n_coarse)
        out = _fp.cone_two_pass_call(
            cfg, packed["coarse"], rays_o, rays_d,
            radii.reshape(-1, 1).astype(jnp.float32), t0_row, t1_row,
            rt=rt, interpret=it, block=block, vmem_limit_bytes=vmem)
        return {"rgb": out[:R, 0:3], "rgb_coarse": out[:R, 3:6],
                "acc": out[:R, 6], "acc_coarse": out[:R, 7],
                "depth": out[:R, 8]}
    # deterministic coarse samples are ray-independent: ship ONE row
    t_row = sampling.stratified(cfg.near, cfg.far, cfg.n_coarse, (1,), None)
    rgb, rgb_c, acc, acc_c, depth = _fp.two_pass_plcore_call(
        cfg, packed["coarse"], packed["fine"], rays_o, rays_d, t_row,
        rt=rt, ert_eps=float(ert_eps), interpret=it, block=block,
        alive=alive, vmem_limit_bytes=vmem)
    return {"rgb": rgb[:R], "rgb_coarse": rgb_c[:R], "acc": acc[:R],
            "acc_coarse": acc_c[:R], "depth": depth[:R]}
