"""Ray sampling — the paper's two-pass strategy (§5.1).

"for every pixel to render ... first generate 64 uniformly distributed
samples within the visible range, calculate density distribution along the
pixel ray, finally generate another 128 samples that are more close to the
surface of the object."

``stratified``  — pass 1: jittered-uniform t values in [near, far].
``importance``  — pass 2: inverse-CDF resampling of the coarse volume-
                  rendering weights (NeRF's sample_pdf), deterministic
                  midpoint mode for inference.

The deterministic variant is factored into a kernel-shareable form so the
fused two-pass PLCore kernel (kernels/fused_plcore.py) can run the exact
same resample in VMEM: ``importance_det`` restates ``searchsorted`` as a
comparison-count reduction and every gather as a one-hot contraction —
ops Mosaic can lower, bit-identical to the host path — and
``merge_sorted_ranks`` merges two sorted sample sets by rank arithmetic
instead of ``jnp.sort``. Both paths share ``_weights_to_cdf``/``det_u``
(and, through the CDF, ``prefix_sum``) so the CDF and the u-grid cannot
drift apart.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def stratified(near: float, far: float, n: int, shape=(),
               key: Optional[jax.Array] = None, lindisp: bool = False):
    """Jittered-uniform samples. Returns t: (*shape, n), sorted ascending."""
    edges = jnp.linspace(0.0, 1.0, n + 1)
    lo, hi = edges[:-1], edges[1:]
    if key is not None:
        u = jax.random.uniform(key, tuple(shape) + (n,))
    else:
        u = 0.5
    s = lo + (hi - lo) * u
    s = jnp.broadcast_to(s, tuple(shape) + (n,))
    if lindisp:
        return 1.0 / (1.0 / near * (1.0 - s) + 1.0 / far * s)
    return near + (far - near) * s


def prefix_sum(x):
    """Inclusive prefix sum over the last axis, as a contraction with the
    (N, N) upper-triangular 0/1 matrix. ``jnp.cumsum`` has no Mosaic
    lowering; this form runs unchanged inside the fused kernel and on
    the host. HIGHEST precision keeps every f32 term exact on the MXU
    (0/1 are exact in each bf16 pass); a one-pass bf16 contraction would
    round each term to 8 mantissa bits."""
    n = x.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tri = (row <= col).astype(x.dtype)
    return jnp.dot(x, tri, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=x.dtype)


def det_u(n: int):
    """The deterministic (inference-mode) u-grid as a (1, n) row: n evenly
    spaced points on [0, 1 - 1e-6], shared verbatim by the host sampler
    and the fused kernel's in-VMEM resampler. Built from a 2-D integer
    iota: a float iota and a 1-D vector do not lower in Mosaic."""
    k = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(jnp.float32)
    return k * ((1.0 - 1e-6) / max(n - 1, 1))


def _u_grid(n: int, batch_shape):
    """``det_u`` broadcast to (*batch_shape, n)."""
    u = det_u(n).reshape((1,) * len(batch_shape) + (n,))
    return jnp.broadcast_to(u, tuple(batch_shape) + (n,))


def _weights_to_cdf(weights, eps: float = 1e-5):
    """Coarse weights (..., M) -> CDF over the M-1 interior bins (..., M-1);
    pdf over the intervals between midpoints (drop edge weights, as NeRF)."""
    w = weights[..., 1:-1] + eps
    pdf = w / jnp.sum(w, axis=-1, keepdims=True)
    cdf = prefix_sum(pdf)
    return jnp.concatenate([jnp.zeros_like(cdf[..., :1]), cdf], axis=-1)


def importance(t_mid, weights, n: int, key: Optional[jax.Array] = None,
               eps: float = 1e-5):
    """Inverse-CDF sampling from piecewise-constant pdf over bins.

    t_mid: (..., M) bin midpoints (coarse sample positions);
    weights: (..., M) coarse volume-rendering weights (bins = gaps between
    midpoints, M-1 intervals). Returns (..., n) new t values, sorted.
    """
    cdf = _weights_to_cdf(weights, eps)

    if key is not None:
        u = jax.random.uniform(key, cdf.shape[:-1] + (n,))
    else:
        u = _u_grid(n, cdf.shape[:-1])

    idx = jnp.clip(jnp.searchsorted(cdf, u, side="right") - 1,
                   0, cdf.shape[-1] - 2) if cdf.ndim == 1 else \
        jnp.clip(_batched_searchsorted(cdf, u) - 1, 0, cdf.shape[-1] - 2)

    cdf_lo = jnp.take_along_axis(cdf, idx, axis=-1)
    cdf_hi = jnp.take_along_axis(cdf, idx + 1, axis=-1)
    t_lo = jnp.take_along_axis(t_mid[..., :-1], idx, axis=-1)
    t_hi = jnp.take_along_axis(t_mid[..., 1:], idx, axis=-1)
    denom = jnp.where(cdf_hi - cdf_lo < 1e-8, 1.0, cdf_hi - cdf_lo)
    frac = (u - cdf_lo) / denom
    return t_lo + frac * (t_hi - t_lo)


def _batched_searchsorted(cdf, u):
    """searchsorted over the last axis for arbitrary leading batch dims."""
    return jax.vmap(lambda c, q: jnp.searchsorted(c, q, side="right"),
                    in_axes=(0, 0))(cdf.reshape(-1, cdf.shape[-1]),
                                    u.reshape(-1, u.shape[-1])
                                    ).reshape(u.shape)


def importance_det(t_mid, weights, n: int, eps: float = 1e-5):
    """Kernel-shareable deterministic inverse-CDF: the exact math of
    ``importance(key=None)`` restated without ``searchsorted`` /
    ``take_along_axis`` (neither lowers inside a Pallas kernel).

    ``searchsorted(cdf, u, side="right")`` is the count of CDF entries
    <= u, so it becomes a comparison-count reduction; each gather becomes
    a one-hot contraction (exactly one 1.0 per row, so the sum reproduces
    the gathered value bit-for-bit). Bit-identical to the host path —
    tests/test_two_pass_fused.py asserts it.
    """
    cdf = _weights_to_cdf(weights, eps)                       # (..., M-1)
    M1 = cdf.shape[-1]
    u = _u_grid(n, cdf.shape[:-1])
    le = (cdf[..., None, :] <= u[..., :, None]).astype(jnp.int32)
    idx = jnp.clip(jnp.sum(le, axis=-1) - 1, 0, M1 - 2)       # (..., n)
    lanes = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (M1,), idx.ndim)
    oh = (idx[..., None] == lanes).astype(t_mid.dtype)        # (..., n, M-1)

    def take(v):          # v: (..., M-1) gathered at idx per output sample
        return jnp.sum(oh * v[..., None, :], axis=-1)

    cdf_lo = take(cdf)
    # idx+1 <= M1-1, so gathering the left-shifted vector at idx never
    # reads the (arbitrary) pad lane
    cdf_hi = take(jnp.concatenate([cdf[..., 1:], cdf[..., -1:]], axis=-1))
    t_lo = take(t_mid[..., :-1])
    t_hi = take(t_mid[..., 1:])
    denom = jnp.where(cdf_hi - cdf_lo < 1e-8, 1.0, cdf_hi - cdf_lo)
    frac = (u - cdf_lo) / denom
    return t_lo + frac * (t_hi - t_lo)


def merge_sorted(t_a, t_b):
    """Union of two sample sets along a ray, sorted (coarse + fine pass)."""
    return jnp.sort(jnp.concatenate([t_a, t_b], axis=-1), axis=-1)


def merge_sorted_ranks(t_a, t_b):
    """Kernel-shareable ``merge_sorted`` for two already-sorted sets: the
    merged position of each element is its own index plus the count of
    elements of the OTHER set strictly before it (ties break a-first, and
    in-set ties break by index, so every rank is distinct) — a comparison
    count plus a one-hot scatter instead of ``jnp.sort``. Same values as
    the sort-based merge for sorted inputs.
    """
    na, nb = t_a.shape[-1], t_b.shape[-1]
    T = na + nb
    ia = jax.lax.broadcasted_iota(jnp.int32, t_a.shape, t_a.ndim - 1)
    ib = jax.lax.broadcasted_iota(jnp.int32, t_b.shape, t_b.ndim - 1)
    lt = (t_b[..., None, :] < t_a[..., :, None]).astype(jnp.int32)
    rank_a = ia + jnp.sum(lt, axis=-1)                        # (..., na)
    le = (t_a[..., None, :] <= t_b[..., :, None]).astype(jnp.int32)
    rank_b = ib + jnp.sum(le, axis=-1)                        # (..., nb)
    lanes_a = jax.lax.broadcasted_iota(jnp.int32, rank_a.shape + (T,),
                                       rank_a.ndim)
    lanes_b = jax.lax.broadcasted_iota(jnp.int32, rank_b.shape + (T,),
                                       rank_b.ndim)
    oh_a = (rank_a[..., None] == lanes_a).astype(t_a.dtype)   # (..., na, T)
    oh_b = (rank_b[..., None] == lanes_b).astype(t_b.dtype)   # (..., nb, T)
    return (jnp.sum(oh_a * t_a[..., None], axis=-2)
            + jnp.sum(oh_b * t_b[..., None], axis=-2))


def deltas_from_t(t, far_cap: float = 1e10):
    """delta_i = t_{i+1} - t_i, final sample capped (paper eq. (4) note)."""
    d = t[..., 1:] - t[..., :-1]
    last = jnp.full_like(t[..., :1], far_cap)   # from t: correct even at N=1
    return jnp.concatenate([d, last], axis=-1)


# ------------------------------------------------- Mip-NeRF intervals ----
def cone_intervals(near: float, far: float, n: int):
    """The coarse pass's n intervals between n + 1 evenly spaced edges of
    [near, far] (Mip-NeRF's deterministic ``sample_along_rays``) as
    (t0, t1) rows, each (1, n): n + 1 edges fit no lane multiple, so the
    interval ends travel as two n-wide rows. From an integer iota, as
    ``det_u``, so the same code runs inside a kernel."""
    k = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(jnp.float32)
    s0, s1 = k / n, (k + 1.0) / n
    return near * (1.0 - s0) + far * s0, near * (1.0 - s1) + far * s1


def mip_resample(t0, t1, weights, n: int, padding: float,
                 eps: float = 1e-5):
    """Mip-NeRF's deterministic ``resample_along_rays``: blur the coarse
    weights (2-tap max, then 2-tap mean), add ``padding``, and draw n + 1
    new edges from the piecewise-constant PDF over the coarse intervals
    at u = linspace(0, 1 - eps_f32, n + 1), in the comparison-mask form
    of ``sorted_piecewise_constant_pdf``: a max / min over ``u >= cdf``,
    with no searchsorted and no gather, so it runs inside a kernel.

    t0, t1: (..., M) interval ends (rows broadcast); weights (..., M).
    Returns the new intervals' (t0, t1), each (..., n)."""
    w = weights
    w_prev = jnp.concatenate([w[..., :1], w[..., :-1]], axis=-1)
    w_next = jnp.concatenate([w[..., 1:], w[..., -1:]], axis=-1)
    w = 0.5 * (jnp.maximum(w_prev, w) + jnp.maximum(w, w_next)) + padding
    M = w.shape[-1]
    wsum = jnp.sum(w, axis=-1, keepdims=True)
    pad = jnp.maximum(0.0, eps - wsum)
    w = w + pad / M
    cdf = jnp.minimum(1.0, prefix_sum(w / (wsum + pad)))
    # the M + 1 CDF values [0, cdf_0 .. cdf_{M-2}, 1] as the lower and
    # upper ends of the M bins, beside the bins' edges t0 and t1
    c_lo = jnp.concatenate([jnp.zeros_like(cdf[..., :1]), cdf[..., :-1]],
                           axis=-1)
    c_hi = jnp.concatenate([cdf[..., :-1], jnp.ones_like(cdf[..., :1])],
                           axis=-1)
    k = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(jnp.float32)
    step = (1.0 - float(np.finfo(np.float32).eps)) / n

    def row(x):
        """(..., M) -> (..., 1, M): bins along the lanes."""
        return jnp.expand_dims(x, -2)

    def edges(u):
        """The new edge at each u: queries along axis -2, bins along -1.
        Bin 0 (cdf 0) always starts at or below u and the last bin (cdf
        1) always ends above it, so the max / min over the selected bins
        equal the reference's, whose unselected bins read as the first /
        last edge and cdf value."""
        q = jnp.expand_dims(u, -1)
        below = q >= row(c_lo)                   # bins whose start <= u
        above = q < row(c_hi)                    # bins whose end > u
        lo = jnp.full(below.shape, -jnp.inf, jnp.float32)
        hi = jnp.full(below.shape, jnp.inf, jnp.float32)
        x0 = jnp.max(jnp.where(below, row(t0), lo), axis=-1)
        x1 = jnp.min(jnp.where(above, row(t1), hi), axis=-1)
        c0 = jnp.max(jnp.where(below, row(c_lo), lo), axis=-1)
        c1 = jnp.min(jnp.where(above, row(c_hi), hi), axis=-1)
        den = c1 - c0
        f = jnp.clip(jnp.where(den > 0.0, (u - c0) / den, 0.0), 0.0, 1.0)
        return x0 + f * (x1 - x0)

    return edges(k * step), edges((k + 1.0) * step)


# ===================================================================== ASDR =
# Adaptive per-ray sample budgets + cross-ray trunk memoization. A cheap
# coarse-only probe at scene load calibrates a quantized-voxel density
# grid (``SampleStats``); at serve time each ray is classified into a
# fine-sample budget class from the stats along its frustum, and trunk
# outputs (sigma|feat — the position-only, view-independent half of the
# MLP engine) are memoized per voxel in a scene-keyed LRU (``TrunkMemo``)
# so rays from ANY viewpoint crossing already-probed voxels reuse them.
# Everything here is host-side bookkeeping (numpy); the device-side use
# lives in core.pipeline (AdaptiveRenderer) and kernels/ (dead-row mask).

def default_budget_classes(n_fine: int) -> Tuple[int, ...]:
    """The canonical budget ladder for a config: e.g. Nf=128 -> (8, 32, 64),
    the tiny Nf=16 test config -> (4, 8, 16). Sorted ascending, capped at
    n_fine, the top class always present so dense rays keep a real budget."""
    raw = (max(4, n_fine // 16), max(8, n_fine // 4), max(16, n_fine // 2))
    return tuple(sorted({min(n_fine, b) for b in raw}))


@dataclass
class SampleStats:
    """Per-scene quantized-voxel density statistics from the load-time
    coarse probe. ``grid`` holds the max coarse-trunk sigma observed per
    voxel (dense (G,G,G) f32 — a few hundred KB at G=48); ``edges`` are
    the per-scene score quantiles that split rays into budget classes.

    Rays are scored by the max grid value along their coarse frustum
    samples; empty-space rays score ~0 and land in the smallest budget
    class. ``empty_tau``: below this sigma a voxel is considered empty —
    a ray whose frustum is fully memo-resident AND fully empty can skip
    the fine pass entirely (it becomes a dead row in the fused kernel).
    """
    lo: np.ndarray                  # (3,) grid lower corner
    vsize: float                    # cubic voxel edge length
    grid: np.ndarray                # (G, G, G) f32, max sigma per voxel
    edges: np.ndarray               # (n_classes - 1,) score thresholds
    probed: np.ndarray              # (G, G, G) bool, voxel seen by probe
    empty_tau: float = 1e-2

    @property
    def res(self) -> int:
        return self.grid.shape[0]

    @property
    def nbytes(self) -> int:
        return int(self.grid.nbytes + self.probed.nbytes
                   + self.edges.nbytes + self.lo.nbytes)

    def voxel_ids(self, pts: np.ndarray) -> np.ndarray:
        """Points (..., 3) -> flat voxel ids (...,). Out-of-grid points
        clamp to the boundary shell (conservative: boundary voxels carry
        whatever the probe saw there)."""
        G = self.res
        ijk = np.floor((pts - self.lo) / self.vsize).astype(np.int64)
        ijk = np.clip(ijk, 0, G - 1)
        return (ijk[..., 0] * G + ijk[..., 1]) * G + ijk[..., 2]

    def voxel_centers(self, vox: np.ndarray) -> np.ndarray:
        """Flat voxel ids (...,) -> center positions (..., 3) — the
        quantized coarse sample positions the trunk memo is keyed on."""
        G = self.res
        k = vox % G
        j = (vox // G) % G
        i = vox // (G * G)
        ijk = np.stack([i, j, k], axis=-1).astype(np.float32)
        return self.lo + (ijk + 0.5) * self.vsize

    def ray_scores(self, pts: np.ndarray) -> np.ndarray:
        """Coarse sample points (R, N, 3) -> per-ray density score (R,):
        max calibrated sigma over the frustum's voxels."""
        flat = self.grid.reshape(-1)[self.voxel_ids(pts)]
        return flat.max(axis=-1)

    def classify(self, pts: np.ndarray,
                 budgets: Sequence[int]) -> np.ndarray:
        """Coarse sample points (R, N, 3) -> budget-class index (R,) into
        ``budgets`` (ascending). Scores past the last edge take the top
        class; with k classes only the first k-1 edges apply."""
        n = len(budgets)
        if n == 1:
            return np.zeros(pts.shape[0], dtype=np.int64)
        edges = self.edges[:n - 1]
        return np.minimum(np.digitize(self.ray_scores(pts), edges), n - 1)

    def empty_mask(self, vox: np.ndarray) -> np.ndarray:
        """Per-ray (R, N) voxel ids -> (R,) bool: every frustum voxel was
        probed AND reads below empty_tau (provably-empty ray)."""
        flat_g = self.grid.reshape(-1)[vox]
        flat_p = self.probed.reshape(-1)[vox]
        return (flat_p & (flat_g < self.empty_tau)).all(axis=-1)


def build_sample_stats(pts: np.ndarray, sigma: np.ndarray, *,
                       grid_res: int = 48, n_classes: int = 3,
                       empty_tau: float = 1e-2,
                       margin: float = 0.5) -> SampleStats:
    """Accumulate probe samples into a SampleStats record.

    pts: (M, N, 3) coarse sample positions of the probe rays; sigma:
    (M, N) raw trunk densities at those points. The grid bounds cover the
    probe cloud plus ``margin`` so serve-time rays from unseen viewpoints
    still land inside. The first budget-class edge is anchored at
    ``empty_tau`` so the smallest class is exactly the empty-space band
    (where the memo's dead-row machinery applies); the remaining edges
    are quantiles of the NON-empty probe scores — on a scene with both
    empty and dense regions every class is exercised by construction
    (plain all-score quantiles collapse to 0 on mostly-empty scenes,
    which would make the middle classes unreachable)."""
    flat = pts.reshape(-1, 3)
    lo = flat.min(axis=0) - margin
    hi = flat.max(axis=0) + margin
    vsize = float((hi - lo).max() / grid_res)
    stats = SampleStats(lo=lo.astype(np.float32), vsize=vsize,
                        grid=np.zeros((grid_res,) * 3, np.float32),
                        edges=np.zeros(max(0, n_classes - 1), np.float32),
                        probed=np.zeros((grid_res,) * 3, bool),
                        empty_tau=empty_tau)
    vox = stats.voxel_ids(flat)
    sig = np.maximum(np.asarray(sigma, np.float32).reshape(-1), 0.0)
    np.maximum.at(stats.grid.reshape(-1), vox, sig)
    stats.probed.reshape(-1)[vox] = True
    scores = stats.ray_scores(pts)
    if n_classes > 1:
        dense = scores[scores >= empty_tau]
        # mid edges sit in the BOTTOM half of the dense-score
        # distribution: only the faintest non-empty rays take reduced
        # budgets, everything from the median up renders at full n_fine.
        # Accuracy-first classing — a median split costs ~0.2 dB on a
        # dense trained scene, past the fig8 adaptive PSNR gate (0.1 dB)
        qs = np.linspace(0.0, 1.0, n_classes)[1:-1] * 0.5
        mid = (np.quantile(dense, qs) if dense.size
               else np.full(max(0, n_classes - 2), empty_tau))
        stats.edges = np.concatenate(
            [[empty_tau], np.maximum(np.atleast_1d(mid), empty_tau)]
        ).astype(np.float32)
    return stats


class TrunkMemo:
    """Scene-keyed LRU memo of trunk-MLP outputs.

    key: (namespace, voxel_id) — namespace separates the coarse and fine
    networks; value: one f32 row ``sigma|feat`` (1 + trunk_width,)
    evaluated at the voxel center. Capacity is byte-accounted against
    ``capacity_mb`` with LRU eviction; rows pinned by in-flight tiles are
    skipped by the evictor (a tile that resolved its lookups must not
    lose them mid-dispatch)."""

    def __init__(self, capacity_mb: float = 32.0):
        self.capacity_bytes = int(capacity_mb * 2 ** 20)
        # LRU bookkeeping: key -> storage slot. Row PAYLOADS live in the
        # per-net slot table ``_data`` so the hot serving-path lookup is
        # one vectorized gather (``_data[_slot[vox]]``), never a per-id
        # dict probe; the OrderedDict only orders keys for eviction.
        self._rows: "OrderedDict[Tuple[str, int], int]" = OrderedDict()
        self._resident: Dict[str, np.ndarray] = {}   # voxel id -> bool
        self._slot: Dict[str, np.ndarray] = {}       # voxel id -> slot|-1
        self._data: Dict[str, np.ndarray] = {}       # slot -> row (D,)
        self._free: Dict[str, List[int]] = {}        # reusable slots
        self._hiwater: Dict[str, int] = {}           # slots ever allocated
        self._pincnt: Dict[str, np.ndarray] = {}     # voxel id -> pin count
        self._rowbytes: Dict[str, int] = {}
        self.nbytes = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._rows)

    def _grow(self, net: str, need: int) -> None:
        """Grow the net's id-indexed arrays to cover voxel id ``need``."""
        bm = self._resident.get(net)
        if bm is None or bm.size <= need:
            size = max(need + 1, 1024, 2 * (bm.size if bm is not None else 0))
            grown = np.zeros(size, bool)
            slots = np.full(size, -1, np.int64)
            pins = np.zeros(size, np.int64)
            if bm is not None:
                grown[:bm.size] = bm
                slots[:bm.size] = self._slot[net]
                pins[:bm.size] = self._pincnt[net]
            self._resident[net] = grown
            self._slot[net] = slots
            self._pincnt[net] = pins

    def lookup(self, net: str, vox: np.ndarray):
        """Vectorized lookup. vox: (K,) int64 voxel ids -> (mask (K,) bool,
        rows (K, D) with zeros at misses; D=0 array if the memo is empty).
        Hits are counted; the LRU refresh (a per-unique-id pass) only runs
        once the memo is past half capacity — below that eviction order is
        never consulted, so the refresh would be pure overhead."""
        vox = np.asarray(vox, np.int64)
        mask = self.contains(net, vox)
        out = None
        if mask.any():
            data = self._data[net]
            idx = np.nonzero(mask)[0]
            out = np.zeros((len(vox), data.shape[1]), np.float32)
            out[idx] = data[self._slot[net][vox[idx]]]
            if 2 * self.nbytes >= self.capacity_bytes:
                for v in np.unique(vox[idx]):
                    self._rows.move_to_end((net, int(v)))
        self.hits += int(mask.sum())
        self.misses += int(len(vox) - mask.sum())
        if out is None:
            out = np.zeros((len(vox), 0), np.float32)
        return mask, out

    def contains(self, net: str, vox: np.ndarray) -> np.ndarray:
        """Residency test without LRU refresh or hit/miss accounting."""
        vox = np.asarray(vox, np.int64)
        bm = self._resident.get(net)
        if bm is None or not vox.size:
            return np.zeros(len(vox), bool)
        out = np.zeros(len(vox), bool)
        in_range = vox < bm.size
        out[in_range] = bm[vox[in_range]]
        return out

    def insert(self, net: str, vox: np.ndarray, rows: np.ndarray) -> None:
        """Insert rows (K, D) for voxel ids (K,); evicts LRU (unpinned)
        rows past capacity. O(new ids) — each voxel pays the Python-level
        slot assignment once per residency lifetime."""
        vox = np.asarray(vox, np.int64)
        rows = np.asarray(rows, np.float32)
        if not vox.size:
            return
        self._grow(net, int(vox.max()))
        bm, slots = self._resident[net], self._slot[net]
        rb = self._rowbytes.setdefault(net, int(rows[0].nbytes) + 64)
        data = self._data.get(net)
        if data is None or data.shape[1] != rows.shape[1]:
            data = self._data[net] = np.zeros((1024, rows.shape[1]),
                                              np.float32)
        free = self._free.setdefault(net, [])
        for k, v in enumerate(vox):
            key = (net, int(v))
            if key in self._rows:
                self._rows.move_to_end(key)
                continue
            if free:
                slot = free.pop()
            else:
                slot = self._hiwater[net] = self._hiwater.get(net, 0) + 1
                slot -= 1
                while slot >= data.shape[0]:
                    data = np.concatenate(
                        [data, np.zeros_like(data)], axis=0)
                    self._data[net] = data
            data[slot] = rows[k]
            slots[int(v)] = slot
            bm[int(v)] = True
            self._rows[key] = slot
            self.nbytes += rb
            self.inserts += 1
        while self.nbytes > self.capacity_bytes and self._rows:
            victim = next(
                (k for k in self._rows
                 if not self._pincnt[k[0]][k[1]]), None)
            if victim is None:
                break                         # everything pinned: overshoot
            vnet, vid = victim
            self._free[vnet].append(self._rows.pop(victim))
            self._slot[vnet][vid] = -1
            self._resident[vnet][vid] = False
            self.nbytes -= self._rowbytes[vnet]
            self.evictions += 1

    def pin(self, net: str, vox: np.ndarray) -> None:
        vox = np.asarray(vox, np.int64)
        if vox.size:
            self._grow(net, int(vox.max()))
            np.add.at(self._pincnt[net], vox, 1)

    def unpin(self, net: str, vox: np.ndarray) -> None:
        vox = np.asarray(vox, np.int64)
        if vox.size:
            cnt = self._pincnt[net]
            np.add.at(cnt, vox, -1)
            np.maximum(cnt, 0, out=cnt)

    @property
    def pinned_rows(self) -> int:
        return int(sum((c > 0).sum() for c in self._pincnt.values()))

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"rows": len(self._rows), "resident_mb":
                round(self.nbytes / 2 ** 20, 3),
                "capacity_mb": round(self.capacity_bytes / 2 ** 20, 3),
                "hits": self.hits, "misses": self.misses,
                "inserts": self.inserts, "evictions": self.evictions,
                "pinned_rows": self.pinned_rows,
                "hit_rate": round(self.hits / total, 4) if total else None}


@dataclass
class SceneAux:
    """The auxiliary per-scene residents that ride alongside the
    PackedPlcore in a SceneCache entry: calibration stats + trunk memo.
    ``nbytes`` is LIVE (the memo grows during serving) — the cache's
    capacity accounting reads it per eviction decision, not at insert."""
    stats: SampleStats
    memo: TrunkMemo
    t_row: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float32))

    @property
    def nbytes(self) -> int:
        return int(self.stats.nbytes + self.memo.nbytes + self.t_row.nbytes)
