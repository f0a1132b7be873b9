"""Fused PLCore Pallas kernels — the whole NeRF pipeline in ONE kernel
(paper C1: "a PLCore takes in positions & directions and renders the
corresponding pixel colors without any intermediate data going off-chip").

Two kernels share one pass body (``_pass_body``: PEU double-angle
recurrence -> MLP engine out of VMEM-resident weights, RMCM 9-bit
dequantized in-register -> VRU in closed parallel-prefix form):

* ``fused_plcore_call`` — ONE sample set per call. Two of these per ray
  tile make the two-dispatch coarse/fine chain: the regression oracle,
  kept because the coarse weights it writes to HBM are exactly what the
  single-dispatch kernel must reproduce internally.
* ``two_pass_plcore_call`` — the paper's C1 restated literally: one
  ``pallas_call`` per ray tile runs coarse MLP+VRU, the deterministic
  inverse-CDF importance resample (the kernel-shareable forms in
  ``core.sampling``: ``importance_det`` + ``merge_sorted_ranks`` — the
  same code the host path tests against), then the fine MLP+VRU and the
  final composite. Coarse weights, sample positions and every activation
  stay in VMEM.
* ``cone_two_pass_call`` — the same economy for Mip-NeRF's cone rays
  (``plcore_two_pass_cone``): a per-ray radius column in, each interval
  cast to a frustum Gaussian and encoded by its IPE in the PEU, one
  pinned network read by both levels, the blurred-weight resample in
  mask form, the fine level on the resampled intervals alone, the VRU
  over finite intervals.

Ray blocks. Mosaic unrolls every vector op over the vregs of its operand,
so a body that works on a whole (rt * N, 256) activation compiles in time
and VMEM proportional to rt (the one-pass kernel at CONFIG: 49 s at 8 rays
per step, out of scoped VMEM at 16). Each kernel therefore walks its ray
tile in a ``fori_loop`` over blocks of ``block`` rays, with the per-sample
work of one block in registers and VMEM scratch: the compiled body, and
its VMEM, depend on ``block``, not on ``rt``. The block is also what each
MXU weight load feeds: a trunk layer streams block * N rows, and every
per-ray contraction against a constant matrix (the prefix-sum triangles,
the row sums, the colour branch's direction half) block rows. The
one-pass kernel steps by ``ops.pick_ray_block`` (one ray at CONFIG: its
(rt, N) sample blocks are too wide for a dynamic load of 2 or 4 rows);
the two-pass kernels, whose per-ray blocks are at most 9 lanes wide, by
``ops.pick_two_pass_block`` (4 rays at CONFIG and at MIPNERF: a row
budget over the widest pass). Off-TPU the same kernel runs under the
Pallas interpreter with one block per tile.

Per-ray early termination (Cicero, arXiv 2404.11852) inside the two-pass
kernel: after the coarse VRU, rays with transmittance < ert_eps keep the
coarse color/acc/depth, and a block whose rays are all dead skips the
importance resample and the fine pass (a ``lax.cond``). Blocks are a few
rays on the chip (4 at CONFIG), so the skip is close to per-ray there.

HBM traffic per ray (f32 words), N = n_coarse + n_fine samples:

  path                      in                       out
  ------------------------  -----------------------  -------------------
  unfused (Fig. 2a GPU)     rays (6) + t (N)         per-sample acts
                                                     O(N * (63+27+4*256))
  two-dispatch fused        rays (12) + t (N + Nc)   rgb+w+acc twice:
                            + w_c re-read (Nc)       (3 + N) + (3 + Nc) + 2
  two_pass (this kernel)    rays (6); t_c is one     rgb (3) + rgb_c (3)
                            pinned (1, Nc) row       + acc, acc_c, depth (3)

VMEM (``ops.two_pass_vmem_bytes``): BOTH networks' weight stacks stay
resident every grid step as the GATHERED working set (single-buffered:
their block never moves), plus the double-buffered per-ray in/out blocks
and one ray block's scratch. The same model sizes rt and is handed to the
compiler as its scoped-VMEM limit. Both entry points take GATHERED
(replicated) weight layouts: with mesh-sharded residency
(runtime.sharding) the pipeline all-gathers each trunk layer
just-in-time inside the same jitted program before the kernel launches —
sharding shrinks the per-device HBM-resident footprint
(``ops.plcore_resident_weight_bytes``), never this working set.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.nerf_icarus import NerfConfig
from repro.core import encoding, sampling
from repro.core.mlp import cone_heads
from repro.kernels.rmcm_matmul import _unpack_signs


def _pe_double_angle(x, n_freqs: int):
    """[x, sin(2^0 x), cos(2^0 x), ..., sin(2^{L-1} x), cos(2^{L-1} x)] via
    the PEU double-angle recurrence (one sin/cos pair total)."""
    s, c = jnp.sin(x), jnp.cos(x)
    feats = [x]
    for _ in range(n_freqs):
        feats.append(s)
        feats.append(c)
        s, c = 2.0 * s * c, 1.0 - 2.0 * s * s
    return jnp.concatenate(feats, axis=-1)


def _mm(a, b):
    """Every contraction in the kernel body: f32 operands at HIGHEST
    precision. Mosaic's default for f32 operands is not a contract of
    the API, and the prefix and row sums need each f32 term exact."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _row_sum(x):
    """Per-ray sum over the sample lanes, (G, N) -> (G, 1), as a
    contraction with a ones column: Mosaic refuses the lane reduction
    ``jnp.sum(w * t_all, -1)`` once t_all comes out of the rank merge
    ("Unsupported output implicit dimension")."""
    return _mm(x, jnp.ones((x.shape[-1], 1), jnp.float32))


def _dq(mag, sgn_bits, scale, rows_padded):
    m = mag.astype(jnp.int32).astype(jnp.float32)   # no u8 -> f32 in Mosaic
    sg = _unpack_signs(sgn_bits, rows_padded).astype(jnp.float32)
    return m * (1.0 - 2.0 * sg) * scale


def _weight_order(quantized: bool):
    """stack_plcore_weights key order as the kernel receives the refs."""
    if quantized:
        return ["trunk_mag", "trunk_sgn", "trunk_scl", "trunk_b",
                "sigma_w", "sigma_b", "feat_mag", "feat_sgn", "feat_scl",
                "feat_b", "color0_mag", "color0_sgn", "color0_scl",
                "color0_b", "rgb_w", "rgb_b"]
    return ["trunk_w", "trunk_b", "sigma_w", "sigma_b", "feat_w", "feat_b",
            "color0_w", "color0_b", "rgb_w", "rgb_b"]


def _net_arrays(cfg: NerfConfig, refs, quantized: bool, P: int, P2: int):
    """Read one network's weight refs into dense f32 values for one ray
    block (RMCM layers dequantized in-register). The 1-D biases arrive as
    (1, n) rows (``_kernel_weights``)."""
    W = cfg.trunk_width
    L = cfg.trunk_layers
    if quantized:
        (tw_mag, tw_sgn, tw_scl, tb, sw, sb, fw_mag, fw_sgn, fw_scl, fb,
         cw_mag, cw_sgn, cw_scl, cb, rw, rb) = refs
        tw = [_dq(tw_mag[i], tw_sgn[i], tw_scl[i], P) for i in range(L)]
        fw = _dq(fw_mag[...], fw_sgn[...], fw_scl[...], W)
        cw = _dq(cw_mag[...], cw_sgn[...], cw_scl[...], P2)
    else:
        (tw, tb, sw, sb, fw, fb, cw, cb, rw, rb) = refs
        tw = [tw[i] for i in range(L)]
        fw, cw = fw[...], cw[...]
    tbs = [tb[i:i + 1] for i in range(L)]               # (1, W) rows
    # sigma and feat both read h: ONE fused (W, W+1) matmul; feat first so
    # both column slices start lane-aligned
    sfw = jnp.concatenate([fw, sw[...]], axis=-1)
    return (tw, tbs, sfw, sb[...], fb[...], cw, cb[...], rw[...], rb[...])


def _kernel_weights(arrays):
    """1-D bias vectors -> (1, n) rows: a kernel block of rank 1 must be a
    multiple of 128 long, and the body broadcasts rows, not vectors."""
    return [a.reshape(1, -1) if a.ndim == 1 else a for a in arrays]


def _pass_body(cfg: NerfConfig, G: int, N: int, net, o, d, ts, deltas,
               ped=None):
    """One full PEU -> MLP -> VRU pass over the (G, N) sample set of a
    block of G rays with already-materialized rays/weights. Returns
    (rgb_pix (G, 3), w (G, N), T_next (G, N)); acc = 1 - T_next[:, N-1:].
    ``ped``: the per-ray direction encoding, precomputable once when
    several passes share the same rays (the two-pass kernel encodes
    directions ONCE where the host path does it per pass)."""
    T = G * N

    # ---- positions & PEU (double-angle) --------------------------------
    pts = (o[:, None, :] + ts[..., None] * d[:, None, :]).reshape(T, 3)
    pe = _pe_double_angle(pts, cfg.pos_freqs)          # (T, pe_dim)
    if ped is None:
        dn = d * jax.lax.rsqrt(jnp.sum(d * d, -1, keepdims=True))
        ped = _pe_double_angle(dn, cfg.dir_freqs)      # (G, de_dim)

    sigma, rgb = _mlp(cfg, G, N, net, pe, ped)
    return _vru(G, N, sigma, rgb, deltas)


def _mlp(cfg: NerfConfig, G: int, N: int, net, pe, ped):
    """The MLP engine over a block's (G * N, pe_dim) sample encodings and
    its (G, de_dim) per-ray direction encodings: (raw density (T, 1),
    sigmoid colour (G, N, 3))."""
    tw, tb, sfw, sb, fb, cw, cb, rw, rb = net
    W = cfg.trunk_width
    pe_dim, de_dim = cfg.pos_enc_dim, cfg.dir_enc_dim
    T = G * N

    # ---- MLP engine (MONB) ---------------------------------------------
    # skip layers run as SPLIT matmuls (h @ W_h + pe @ W_pe == the concat
    # matmul without materializing the (T, W+pe) buffer — same trick as
    # core.mlp._matmul_split)
    h = pe
    for i in range(cfg.trunk_layers):
        if i == 0:
            h = jax.nn.relu(_mm(pe, tw[i][:pe_dim]) + tb[i])
        elif i in cfg.skip_at:
            h = jax.nn.relu(_mm(h, tw[i][:W])
                            + _mm(pe, tw[i][W:W + pe_dim]) + tb[i])
        else:
            h = jax.nn.relu(_mm(h, tw[i][:W]) + tb[i])

    # ---- heads: sigma (SONB, exact), feature, color branch -------------
    # sigma and feat both read h: ONE fused (W, W+1) matmul instead of a
    # gemv + a gemm (one pass over the (T, W) activations)
    sf = _mm(h, sfw)
    feat = sf[:, :W] + fb
    sigma = sf[:, W:W + 1] + sb                         # (T, 1)
    # split color matmul: the direction part is PER-RAY (G rows), not
    # per-sample — N x less work than the (T, W+de) concat matmul
    C = cw.shape[-1]
    colf = _mm(feat, cw[:W])
    cold = _mm(ped, cw[W:W + de_dim])                   # (G, C)
    hc = jax.nn.relu(
        (colf.reshape(G, N, C) + cold[:, None, :]).reshape(T, C) + cb)
    rgb = jax.nn.sigmoid(_mm(hc, rw) + rb).reshape(G, N, 3)
    return sigma, rgb


def _vru(G: int, N: int, sigma, rgb, deltas):
    """VRU: closed-form parallel prefix over a block's (G, N) samples.
    Returns (rgb_pix (G, 3), w (G, N), T_next (G, N))."""
    # T_{i+1} = exp(prefix_sum_{j<=i} x_j); T_0 = 1; w_i = T_i - T_{i+1}.
    # Same math as eq.(5)'s recurrence, but one vectorized prefix sum
    # instead of N serial steps with a dynamic_update_slice each.
    x = -(jnp.maximum(sigma, 0.0).reshape(G, N)) * deltas
    T_next = jnp.exp(sampling.prefix_sum(x))            # (G, N): T_{i+1}
    T_i = jnp.concatenate([jnp.ones((G, 1), jnp.float32),
                           T_next[:, :-1]], axis=-1)
    w = T_i - T_next
    accum = jnp.sum(w[..., None] * rgb, axis=1)        # (G, 3)
    return accum, w, T_next


def _pinned(a):
    """Whole tensor resident every grid step (weight-stationary). Its
    block index never changes, so ONE buffer suffices: the default second
    pipeline buffer would double the weights' VMEM for nothing."""
    nd = a.ndim
    return pl.BlockSpec(a.shape, lambda i, nd=nd: (0,) * nd,
                        pipeline_mode=pl.Buffered(1))


def _rows(rt: int, width: int):
    """Per-ray block of an (R, width) array: rt rows per grid step."""
    return pl.BlockSpec((rt, width), lambda i: (i, 0))


def _compiler_params(vmem_limit_bytes: Optional[int]):
    if vmem_limit_bytes is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))


def _for_each_block(rt: int, block: int, fn):
    """fn(rows) for every ``block``-ray slice of the tile, as a real loop
    (one compiled body) rather than ``rt // block`` unrolled copies."""
    assert rt % block == 0, (rt, block)

    def step(b, carry):
        fn(pl.ds(pl.multiple_of(b * block, block), block))
        return carry

    jax.lax.fori_loop(0, rt // block, step, 0)


# ------------------------------------------------------ one-pass kernel ----
def _make_kernel(cfg: NerfConfig, rt: int, block: int, N: int, P: int,
                 P2: int, quantized: bool, ert: bool):
    nw = len(_weight_order(quantized))

    def kernel(o_ref, d_ref, t_ref, dl_ref, *refs):
        if ert:
            alive_ref, refs = refs[0], refs[1:]
        wrefs = refs[:nw]
        rgb_o, w_o, acc_o = refs[nw:]

        def ray_block(rows):
            net = _net_arrays(cfg, wrefs, quantized, P, P2)
            o = o_ref[rows, :].astype(jnp.float32)          # (block, 3)
            d = d_ref[rows, :].astype(jnp.float32)
            ts = t_ref[rows, :].astype(jnp.float32)         # (block, N)
            accum, w, T_next = _pass_body(cfg, block, N, net, o, d, ts,
                                          dl_ref[rows, :])
            rgb_o[rows, :] = accum.astype(rgb_o.dtype)
            w_o[rows, :] = w.astype(w_o.dtype)
            acc_o[rows, :] = (1.0 - T_next[:, N - 1:]).astype(acc_o.dtype)

        def compute():
            _for_each_block(rt, block, ray_block)

        if not ert:
            compute()
            return
        # ---- early-ray-termination fast path: skip dead tiles -----------
        any_alive = jnp.sum((alive_ref[...] > 0.0).astype(jnp.float32)) > 0

        @pl.when(any_alive)
        def _():
            compute()

        @pl.when(jnp.logical_not(any_alive))
        def _():
            rgb_o[...] = jnp.zeros(rgb_o.shape, rgb_o.dtype)
            w_o[...] = jnp.zeros(w_o.shape, w_o.dtype)
            acc_o[...] = jnp.zeros(acc_o.shape, acc_o.dtype)

    return kernel


def fused_plcore_call(cfg: NerfConfig, weights: dict, rays_o, rays_d, t,
                      deltas, *, rt: int, quantized: bool,
                      alive=None, interpret: bool = True,
                      block: Optional[int] = None,
                      vmem_limit_bytes: Optional[int] = None):
    """Low-level pallas_call. rays: (R, 3) with R % rt == 0; t/deltas (R, N).

    ``weights``: layout from ops.stack_plcore_weights (P/P2 row-padded,
    trunk stacked (L, P, W)). ``alive``: optional (R,) float mask; tiles
    whose rays are all dead (== 0) skip the MLP+VRU entirely and output
    zeros. ``block``: rays per inner-loop step (default: the whole tile);
    it must divide rt. Per-ray vectors cross the kernel boundary as
    (R, 1) columns (a rank-1 block must be a multiple of 128 long).
    ``vmem_limit_bytes``: the scoped-VMEM limit the compiler is given
    (None: its default). Returns (rgb (R,3), w (R,N), acc (R,)).
    """
    R, N = t.shape
    assert R % rt == 0, (R, rt)
    block = rt if block is None else block
    # row padding is derived from cfg, NOT read out of ``weights``: the
    # packed layout crosses jit boundaries as a traced pytree, and shapes
    # must stay concrete
    P = -(-(cfg.trunk_width + cfg.pos_enc_dim) // 128) * 128
    P2 = -(-(cfg.trunk_width + cfg.dir_enc_dim) // 128) * 128
    w_arrays = _kernel_weights(
        [weights[k] for k in _weight_order(quantized)])

    ert = alive is not None
    mask_in = [alive.astype(jnp.float32).reshape(R, 1)] if ert else []
    kernel = _make_kernel(cfg, rt, block, N, P, P2, quantized, ert)
    rgb, w, acc = pl.pallas_call(
        kernel,
        grid=(R // rt,),
        in_specs=[_rows(rt, 3), _rows(rt, 3), _rows(rt, N), _rows(rt, N)]
                 + ([_rows(rt, 1)] if ert else [])
                 + [_pinned(a) for a in w_arrays],
        out_specs=[_rows(rt, 3), _rows(rt, N), _rows(rt, 1)],
        out_shape=[jax.ShapeDtypeStruct((R, 3), jnp.float32),
                   jax.ShapeDtypeStruct((R, N), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
        name="plcore_pass",
    )(rays_o, rays_d, t, deltas, *mask_in, *w_arrays)
    return rgb, w, acc[:, 0]


# --------------------------------------------------- one-kernel two-pass ----
def _two_pass_rays(cfg: NerfConfig, G: int, Nc: int, Nf: int,
                   P: int, P2: int, qc: bool, qf: bool, ert_eps: float,
                   o, d, t_row, cw_refs, fw_refs, m=None):
    """The two-pass chain for one block of G rays: coarse -> in-VMEM
    importance resample -> fine -> composite.
    ``m``: optional (G, 1) float mask of externally-dead rows (trunk-memo
    hits in the adaptive path): rows with m == 0 join the ERT-dead set,
    so the SAME skip that drops terminated rays drops memoized ones —
    their fine-pass cost vanishes from tile latency (their outputs are
    overwritten host-side from the memo).

    Every per-ray quantity is a (G, 1) column and every slice is static:
    integer indexing of a value lowers to ``dynamic_slice``, which Mosaic
    refuses. Returns a (G, 9) record:
    [rgb (3) | rgb_coarse (3) | acc | acc_coarse | depth]."""
    Nt = Nc + Nf
    o = o.astype(jnp.float32)                          # (G, 3)
    d = d.astype(jnp.float32)                          # (G, 3)
    # deterministic coarse samples: one pinned (1, Nc) row, shared by
    # every ray of every tile — the only non-ray tensor crossing HBM
    t_c = jnp.broadcast_to(t_row.astype(jnp.float32), (G, Nc))
    dl_c = sampling.deltas_from_t(t_c)
    # direction encoding is per-ray, not per-sample: encode ONCE and
    # share it between the coarse and fine passes (the host path pays
    # for it twice, once per _eval_pass)
    dn = d * jax.lax.rsqrt(jnp.sum(d * d, -1, keepdims=True))
    ped = _pe_double_angle(dn, cfg.dir_freqs)          # (G, de_dim)

    # ---- pass 1: coarse, entirely in VMEM -------------------------------
    net_c = _net_arrays(cfg, cw_refs, qc, P, P2)
    rgb_c, w_c, Tn_c = _pass_body(cfg, G, Nc, net_c, o, d, t_c, dl_c, ped)
    acc_c = 1.0 - Tn_c[:, Nc - 1:]                     # (G, 1)
    depth_c = _row_sum(w_c * t_c)

    def fine(_):
        """In-VMEM importance resample (w_c never leaves the chip), then
        the fine pass: (rgb (3) | acc | depth), (G, 5)."""
        t_f = sampling.importance_det(t_c, w_c, Nf)    # (G, Nf)
        t_all = sampling.merge_sorted_ranks(t_c, t_f)  # (G, Nt)
        net_f = _net_arrays(cfg, fw_refs, qf, P, P2)
        r, w, Tn = _pass_body(cfg, G, Nt, net_f, o, d, t_all,
                              sampling.deltas_from_t(t_all), ped)
        return jnp.concatenate(
            [r, 1.0 - Tn[:, Nt - 1:], _row_sum(w * t_all)], axis=-1)

    if ert_eps > 0.0 or m is not None:
        alive = None
        if ert_eps > 0.0:
            alive = acc_c < 1.0 - ert_eps
        if m is not None:
            live = m.astype(jnp.float32) > 0.0
            alive = live if alive is None else jnp.logical_and(alive, live)
        coarse = jnp.concatenate([rgb_c, acc_c, depth_c], axis=-1)
        any_alive = jnp.sum(alive.astype(jnp.float32)) > 0.0
        rec = jax.lax.cond(any_alive, fine, lambda _: coarse, None)
        rec = jnp.where(alive, rec, coarse)
    else:
        rec = fine(None)
    return jnp.concatenate(
        [rec[:, 0:3], rgb_c, rec[:, 3:4], acc_c, rec[:, 4:5]], axis=-1)


def _make_two_pass_kernel(cfg: NerfConfig, rt: int, block: int, Nc: int,
                          Nf: int, P: int, P2: int, qc: bool, qf: bool,
                          ert_eps: float, has_mask: bool = False):
    nwc = len(_weight_order(qc))
    nwf = len(_weight_order(qf))

    def kernel(o_ref, d_ref, tc_ref, *refs):
        m_ref = None
        if has_mask:
            m_ref, refs = refs[0], refs[1:]
        cw_refs = refs[:nwc]
        fw_refs = refs[nwc:nwc + nwf]
        (out_o,) = refs[nwc + nwf:]

        def ray_block(rows):
            m = None if m_ref is None else m_ref[rows, :]
            out_o[rows, :] = _two_pass_rays(
                cfg, block, Nc, Nf, P, P2, qc, qf, ert_eps,
                o_ref[rows, :], d_ref[rows, :], tc_ref[...], cw_refs,
                fw_refs, m).astype(out_o.dtype)

        _for_each_block(rt, block, ray_block)

    return kernel


def two_pass_plcore_call(cfg: NerfConfig, packed_c: dict, packed_f: dict,
                         rays_o, rays_d, t_row, *, rt: int, ert_eps: float,
                         interpret: bool = True, block: Optional[int] = None,
                         alive=None, vmem_limit_bytes: Optional[int] = None):
    """ONE pallas_call per ray tile for the complete coarse -> importance
    -> fine chain. rays: (R, 3) with R % rt == 0; t_row: (1, n_coarse)
    deterministic coarse sample positions (identical for every ray —
    inference mode). ``packed_c``/``packed_f``: stack_plcore_weights
    layouts for the two networks, both pinned in VMEM simultaneously.

    ``block``: rays per inner-loop step (default: the whole tile); it
    must divide rt. Off-TPU (``interpret=True``) the same kernel runs
    under the Pallas interpreter; ERT's ``lax.cond`` block skips stay
    runtime-real there.

    ``alive``: optional (R,) float mask of externally-live rows (0 = the
    adaptive path already has this ray's pixel memoized): dead rows join
    the ERT-dead set and skip the fine MLP. ``vmem_limit_bytes``: the
    scoped-VMEM limit the compiler is given (None: its default).

    Returns (rgb (R,3), rgb_coarse (R,3), acc (R,), acc_coarse (R,),
    depth (R,)); the caller composites white background.
    """
    R = rays_o.shape[0]
    Nc = t_row.shape[-1]
    assert R % rt == 0, (R, rt)
    block = rt if block is None else block
    P = -(-(cfg.trunk_width + cfg.pos_enc_dim) // 128) * 128
    P2 = -(-(cfg.trunk_width + cfg.dir_enc_dim) // 128) * 128
    qc = "trunk_mag" in packed_c
    qf = "trunk_mag" in packed_f
    wc = _kernel_weights([packed_c[k] for k in _weight_order(qc)])
    wf = _kernel_weights([packed_f[k] for k in _weight_order(qf)])
    has_mask = alive is not None
    mask_in = [alive.astype(jnp.float32).reshape(R, 1)] if has_mask else []

    kernel = _make_two_pass_kernel(cfg, rt, block, Nc, cfg.n_fine, P, P2,
                                   qc, qf, float(ert_eps), has_mask)
    out = pl.pallas_call(
        kernel,
        grid=(R // rt,),
        in_specs=[_rows(rt, 3), _rows(rt, 3), _pinned(t_row)]
                 + ([_rows(rt, 1)] if has_mask else [])
                 + [_pinned(a) for a in wc] + [_pinned(a) for a in wf],
        out_specs=_rows(rt, 9),
        out_shape=jax.ShapeDtypeStruct((R, 9), jnp.float32),
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
        name="plcore_two_pass",
    )(rays_o, rays_d, t_row, *mask_in, *wc, *wf)
    return out[:, 0:3], out[:, 3:6], out[:, 6], out[:, 7], out[:, 8]


# ------------------------------------------- Mip-NeRF: the cone two-pass ----
def _cone_pass_body(cfg: NerfConfig, G: int, N: int, net, o, d, r, t0, t1,
                    ped, dnorm):
    """One pass over the (G, N) intervals [t0, t1) of a block of G cone
    rays: each frustum cast to a Gaussian, its IPE in the PEU
    (``encoding.integrated_pos_enc_recurrence``), the MLP, Mip-NeRF's
    heads, the VRU over the finite intervals. Returns
    (rgb_pix (G, 3), w (G, N), T_next (G, N))."""
    T = G * N
    t_mean, cov = encoding.conical_frustum_to_gaussian(d, t0, t1, r)
    mean = (o[:, None, :] + t_mean[..., None] * d[:, None, :]).reshape(T, 3)
    pe = encoding.integrated_pos_enc_recurrence(mean, cov.reshape(T, 3),
                                                cfg.pos_freqs)
    sigma, rgb = cone_heads(cfg, *_mlp(cfg, G, N, net, pe, ped))
    return _vru(G, N, sigma, rgb, (t1 - t0) * dnorm)


def _cone_two_pass_rays(cfg: NerfConfig, G: int, P: int, P2: int,
                        q: bool, o, d, r, t0_row, t1_row, w_refs):
    """Mip-NeRF's two levels for one block of G rays, in VMEM: the coarse
    pass over the pinned (1, n) interval rows, the blurred-weight
    resample to n new intervals (``sampling.mip_resample``, mask form),
    the fine pass over those alone. Returns the (G, 9) record
    [rgb (3) | rgb_coarse (3) | acc | acc_coarse | depth].

    Both levels read the one pinned network as two trips of one loop
    over the same pass body, so its weights are loaded once per level:
    written out twice, the compiler merges the second level's loads into
    the first's and keeps a copy of every matrix live across both
    (scoped VMEM at MIPNERF, compiled for a v5e: 14.2 MiB written out,
    4.5-5.0 MiB as the loop)."""
    n = cfg.n_coarse
    o = o.astype(jnp.float32)
    d = d.astype(jnp.float32)
    r = r.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(d * d, -1, keepdims=True))
    ped = _pe_double_angle(d * inv, cfg.dir_freqs)     # (G, de_dim)
    dnorm = 1.0 / inv

    def level(i, carry):
        """One level: the pass over the carried intervals, then (first
        level only) the resample to the next level's."""
        t0, t1, _, cur = carry
        net = _net_arrays(cfg, w_refs, q, P, P2)
        rgb, w, Tn = _cone_pass_body(cfg, G, n, net, o, d, r, t0, t1, ped,
                                     dnorm)
        nxt = jnp.concatenate([rgb, 1.0 - Tn[:, n - 1:],
                               _row_sum(w * (0.5 * (t0 + t1)))], axis=-1)

        def resample():
            return sampling.mip_resample(t0, t1, w, n, cfg.resample_padding)

        t0, t1 = jax.lax.cond(i == 0, resample, lambda: (t0, t1))
        return t0, t1, cur, nxt

    # the carry's first records from data: a constant's replicated layout
    # is one Mosaic cannot carry through the loop
    zero = 0.0 * jnp.concatenate([o, d[:, :2]], axis=-1)
    carry = (jnp.broadcast_to(t0_row.astype(jnp.float32), (G, n)),
             jnp.broadcast_to(t1_row.astype(jnp.float32), (G, n)),
             zero, zero)
    _, _, coarse, fine = jax.lax.fori_loop(0, 2, level, carry)
    return jnp.concatenate([fine[:, 0:3], coarse[:, 0:3], fine[:, 3:4],
                            coarse[:, 3:4], fine[:, 4:5]], axis=-1)


def _make_cone_kernel(cfg: NerfConfig, rt: int, block: int, P: int, P2: int,
                      q: bool):
    def kernel(o_ref, d_ref, r_ref, t0_ref, t1_ref, *refs):
        w_refs, out_o = refs[:-1], refs[-1]

        def ray_block(rows):
            out_o[rows, :] = _cone_two_pass_rays(
                cfg, block, P, P2, q, o_ref[rows, :], d_ref[rows, :],
                r_ref[rows, :], t0_ref[...], t1_ref[...],
                w_refs).astype(out_o.dtype)

        _for_each_block(rt, block, ray_block)

    return kernel


def cone_two_pass_call(cfg: NerfConfig, packed: dict, rays_o, rays_d,
                       radii, t0_row, t1_row, *, rt: int,
                       interpret: bool = True,
                       block: Optional[int] = None,
                       vmem_limit_bytes: Optional[int] = None):
    """ONE pallas_call per ray tile for Mip-NeRF's coarse -> resample ->
    fine chain (``plcore_two_pass_cone`` in HLO and device traces).
    rays: (R, 3) and radii (R, 1) with R % rt == 0; t0_row / t1_row:
    (1, n_coarse) coarse interval ends, the same for every ray.
    ``packed``: the config's one network, pinned once and read by both
    passes. Returns the (R, 9) record of ``two_pass_plcore_call``."""
    R = rays_o.shape[0]
    assert R % rt == 0, (R, rt)
    block = rt if block is None else block
    P = -(-(cfg.trunk_width + cfg.pos_enc_dim) // 128) * 128
    P2 = -(-(cfg.trunk_width + cfg.dir_enc_dim) // 128) * 128
    q = "trunk_mag" in packed
    w = _kernel_weights([packed[k] for k in _weight_order(q)])
    return pl.pallas_call(
        _make_cone_kernel(cfg, rt, block, P, P2, q),
        grid=(R // rt,),
        in_specs=[_rows(rt, 3), _rows(rt, 3), _rows(rt, 1), _pinned(t0_row),
                  _pinned(t1_row)] + [_pinned(a) for a in w],
        out_specs=_rows(rt, 9),
        out_shape=jax.ShapeDtypeStruct((R, 9), jnp.float32),
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
        name="plcore_two_pass_cone",
    )(rays_o, rays_d, radii, t0_row, t1_row, *w)
