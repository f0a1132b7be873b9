"""Unit tests for the ASDR primitives (adaptive per-ray sample budgets +
cross-ray trunk memoization).

Covers the host-side bookkeeping in ``core.sampling`` — the calibration
grid (``SampleStats`` / ``build_sample_stats``), the budget ladder, and
the slot-table LRU ``TrunkMemo`` (hit/miss accounting, capacity
eviction, pin protection, slot reuse, multi-net isolation) — plus the
``SceneCache`` aux-resident accounting and the constructor guards that
keep adaptive sampling off incompatible pipelines. End-to-end behavior
(bucket purity, bit-identity, parity) lives in test_properties.py and
test_parity_matrix.py.
"""
import numpy as np
import pytest

import jax

from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import (AdaptiveRenderer, PackedPlcore,
                                 build_scene_aux)
from repro.core.plcore import plcore_decls
from repro.core.sampling import (TrunkMemo, build_sample_stats,
                                 default_budget_classes)
from repro.data import rays as R
from repro.models.params import init_params
from repro.serving import RenderEngine, SceneCache


# ------------------------------------------------------------ budget ladder
def test_default_budget_classes():
    assert default_budget_classes(16) == (4, 8, 16)
    assert default_budget_classes(128) == (8, 32, 64)
    for nf in (4, 8, 16, 64, 128, 256):
        b = default_budget_classes(nf)
        assert b == tuple(sorted(set(b)))          # ascending, distinct
        assert all(x <= nf for x in b)             # capped at n_fine
        assert b[0] >= 4


# ------------------------------------------------------- calibration stats
def _probe_cloud():
    """Synthetic probe: 48 rays x 8 samples, spatially split into an
    empty band (x < -0.2), a faint band and a dense band (x > 0.2)."""
    rng = np.random.default_rng(0)
    n, m = 16, 8
    def band(x0, x1):
        pts = rng.uniform(-1.0, 1.0, (n, m, 3)).astype(np.float32)
        pts[..., 0] = rng.uniform(x0, x1, (n, m))
        return pts
    pts = np.concatenate([band(-1.0, -0.2),    # empty
                          band(0.2, 0.55),     # faint
                          band(0.65, 1.0)])    # dense
    sigma = np.concatenate([np.zeros((n, m), np.float32),
                            np.full((n, m), 0.05, np.float32),
                            np.full((n, m), 5.0, np.float32)])
    return pts, sigma


def test_build_sample_stats_edges_and_classes():
    pts, sigma = _probe_cloud()
    stats = build_sample_stats(pts, sigma, grid_res=8, n_classes=3,
                               empty_tau=1e-2)
    # the first edge is ANCHORED at empty_tau (class 0 == the empty band)
    assert stats.edges.shape == (2,)
    assert stats.edges[0] == np.float32(1e-2)
    assert stats.edges[1] >= stats.edges[0]
    budgets = (4, 8, 16)
    cls = stats.classify(pts, budgets)
    assert (cls[:16] == 0).all()                 # empty rays -> min budget
    assert (cls[32:] == 2).all()                 # dense rays -> full budget
    assert cls.min() >= 0 and cls.max() <= 2
    # single-budget config: classification degenerates to all-zero
    assert (stats.classify(pts, (16,)) == 0).all()


def test_sample_stats_empty_mask_and_probed():
    pts, sigma = _probe_cloud()
    stats = build_sample_stats(pts, sigma, grid_res=8, n_classes=3,
                               empty_tau=1e-2)
    vox = stats.voxel_ids(pts)
    em = stats.empty_mask(vox)
    assert em[:16].all()                         # probed empty band
    assert not em[32:].any()                     # dense band never empty
    # a ray through UNPROBED space is never provably empty: far-away
    # points clamp to the (unprobed) boundary shell
    far = np.full((1, 4, 3), 50.0, np.float32)
    assert not stats.empty_mask(stats.voxel_ids(far)).any()


def test_voxel_id_center_roundtrip():
    pts, sigma = _probe_cloud()
    stats = build_sample_stats(pts, sigma, grid_res=8)
    ids = np.unique(stats.voxel_ids(pts.reshape(-1, 3)))
    centers = stats.voxel_centers(ids)
    np.testing.assert_array_equal(stats.voxel_ids(centers), ids)


# ------------------------------------------------------------- trunk memo
def _rows(ids, d=4, salt=0.0):
    """Deterministic distinct row payloads for voxel ids."""
    ids = np.asarray(ids, np.float32)
    return (ids[:, None] * 10.0 + np.arange(d, dtype=np.float32)
            + salt).astype(np.float32)


def test_memo_insert_lookup_counters():
    memo = TrunkMemo(capacity_mb=1.0)
    ids = np.array([3, 7, 2000], np.int64)       # forces bitmap growth
    memo.insert("c", ids, _rows(ids))
    assert len(memo) == 3 and memo.inserts == 3
    mask, rows = memo.lookup("c", np.array([3, 5, 2000], np.int64))
    np.testing.assert_array_equal(mask, [True, False, True])
    np.testing.assert_array_equal(rows[0], _rows([3])[0])
    np.testing.assert_array_equal(rows[2], _rows([2000])[0])
    assert (rows[1] == 0).all()
    assert memo.hits == 2 and memo.misses == 1
    st = memo.stats()
    assert st["rows"] == 3 and st["hit_rate"] == round(2 / 3, 4)
    for k in ("resident_mb", "capacity_mb", "inserts", "evictions",
              "pinned_rows"):
        assert k in st


def test_memo_capacity_eviction_lru_and_refresh():
    # room for exactly 2 rows (rowbytes = 4*4 + 64 = 80)
    memo = TrunkMemo(capacity_mb=200 / 2 ** 20)
    memo.insert("c", np.array([1]), _rows([1]))
    memo.insert("c", np.array([2]), _rows([2]))
    assert memo.evictions == 0
    # past half capacity the lookup refreshes LRU order: id 1 becomes MRU
    memo.lookup("c", np.array([1]))
    memo.insert("c", np.array([3]), _rows([3]))
    assert memo.evictions == 1
    assert memo.nbytes <= memo.capacity_bytes
    mask, _ = memo.lookup("c", np.array([1, 2, 3]))
    np.testing.assert_array_equal(mask, [True, False, True])  # 2 was LRU


def test_memo_slot_reuse_keeps_rows_bit_identical():
    memo = TrunkMemo(capacity_mb=200 / 2 ** 20)
    memo.insert("c", np.array([1, 2]), _rows([1, 2]))
    memo.insert("c", np.array([3]), _rows([3]))  # evicts 1 (LRU)
    assert not memo.contains("c", np.array([1]))[0]
    memo.insert("c", np.array([4]), _rows([4], salt=0.5))  # reuses slot
    _, rows = memo.lookup("c", np.array([3, 4]))
    np.testing.assert_array_equal(rows[0], _rows([3])[0])
    np.testing.assert_array_equal(rows[1], _rows([4], salt=0.5)[0])


def test_memo_pins_block_eviction():
    memo = TrunkMemo(capacity_mb=200 / 2 ** 20)
    memo.insert("c", np.array([1, 2]), _rows([1, 2]))
    memo.pin("c", np.array([1, 2]))
    assert memo.pinned_rows == 2
    memo.insert("c", np.array([3]), _rows([3]))
    # both pinned rows survive; the evictor takes the only unpinned row
    mask, _ = memo.lookup("c", np.array([1, 2]))
    assert mask.all()
    memo.unpin("c", np.array([1, 2]))
    memo.insert("c", np.array([4]), _rows([4]))
    assert memo.nbytes <= memo.capacity_bytes
    # unpin floors at zero — an unbalanced extra unpin must not go negative
    memo.unpin("c", np.array([1, 1, 2]))
    assert memo.pinned_rows == 0
    assert (memo._pincnt["c"] >= 0).all()


def test_memo_nets_are_isolated():
    memo = TrunkMemo(capacity_mb=1.0)
    memo.insert("c", np.array([5]), _rows([5]))
    memo.insert("f", np.array([5]), _rows([5], salt=9.0))
    assert len(memo) == 2
    _, rc = memo.lookup("c", np.array([5]))
    _, rf = memo.lookup("f", np.array([5]))
    np.testing.assert_array_equal(rc[0], _rows([5])[0])
    np.testing.assert_array_equal(rf[0], _rows([5], salt=9.0)[0])
    assert not memo.contains("f", np.array([6]))[0]


# ------------------------------------------------------ SceneCache + aux
class _DummyAux:
    def __init__(self, nbytes):
        self.nbytes = nbytes


@pytest.fixture(scope="module")
def scene_setup():
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                         "float32")
    return cfg, params


def _cache(cfg, params, capacity_mb):
    return SceneCache(lambda sid: PackedPlcore(cfg, params),
                      capacity_mb=capacity_mb)


def test_ensure_aux_requires_resident_scene(scene_setup):
    cache = _cache(*scene_setup, capacity_mb=64.0)
    with pytest.raises(Exception, match="load it before"):
        cache.ensure_aux("s0", lambda pp: _DummyAux(1024))


def test_ensure_aux_builds_once_and_counts(scene_setup):
    cache = _cache(*scene_setup, capacity_mb=64.0)
    cache.get("s0")
    base = cache.resident_bytes
    calls = []
    builder = lambda pp: (calls.append(pp), _DummyAux(1 << 20))[1]
    a1 = cache.ensure_aux("s0", builder)
    a2 = cache.ensure_aux("s0", builder)
    assert a1 is a2 and len(calls) == 1
    assert isinstance(calls[0], PackedPlcore)    # builder sees the weights
    assert cache.aux_bytes == 1 << 20
    assert cache.resident_bytes == base + (1 << 20)  # aux is accounted
    st = cache.stats()
    assert st["aux_scenes"] == 1 and st["aux_mb"] == 1.0
    assert cache.discard("s0")
    assert cache.aux("s0") is None and cache.aux_bytes == 0


def test_eviction_drops_aux_and_pins_protect(scene_setup):
    cfg, params = scene_setup
    cache = _cache(cfg, params, capacity_mb=2.0)
    cache.get("s0")
    cache.ensure_aux("s0", lambda pp: _DummyAux(int(1.5 * 2 ** 20)))
    cache.pin("s0")
    cache.get("s1")                              # over capacity, s0 pinned
    assert "s0" in cache and cache.aux("s0") is not None
    cache.unpin("s0")
    cache.get("s2")                              # now s0 is evictable
    assert "s0" not in cache
    assert cache.aux("s0") is None               # aux went with the scene


# ------------------------------------------------------------------ guards
def test_adaptive_renderer_requires_fused_kernel(scene_setup):
    cfg, params = scene_setup
    pp = PackedPlcore(cfg, params)               # plain XLA path
    with pytest.raises(ValueError, match="fuse_two_pass"):
        AdaptiveRenderer(pp, None)


def test_engine_guards_reject_incompatible_modes(scene_setup):
    cache = _cache(*scene_setup, capacity_mb=64.0)
    with pytest.raises(ValueError, match="degrade_on_overload"):
        RenderEngine(cache, adaptive_sampling=True,
                     degrade_on_overload=True)


# ------------------------------------------- full-dead tile reconstruction
def test_full_dead_tile_skips_kernel_and_is_exact_white(scene_setup):
    """A scene whose probe finds ONLY empty space renders hinted tiles
    without any kernel dispatch, producing the exact white background
    (relu(sigma<=0) -> zero weights -> acc 0 -> 1.0, bit-for-bit)."""
    cfg, params = scene_setup
    params = jax.tree.map(lambda a: a, params)   # shallow copy per-net ok
    params = {n: dict(p) for n, p in params.items()}
    for n in params:
        sig = dict(params[n]["sigma"])
        sig["b"] = sig["b"] - 5.0                # drive density negative
        params[n] = {**params[n], "sigma": sig}
    pp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
    aux = build_scene_aux(pp, grid_res=12, probe_hw=6, memo_mb=8.0)
    ar = AdaptiveRenderer(pp, aux)
    o, d = R.camera_rays(R.pose_spherical(30.0, -25.0, 4.0), 8, 8, 7.2)
    o = np.asarray(o).reshape(-1, 3)
    d = np.asarray(d).reshape(-1, 3)
    # a hint-pure tile, as the scheduler's dead bucket would coalesce it
    # (frame-edge rays exit the probed volume and are never hinted)
    hint = ar.dead_hint(o, d)
    assert hint.sum() >= 32
    o, d = o[hint], d[hint]
    rgb, info = ar.render_tile(o, d)
    assert info["full_dead"] and info["dead"] == o.shape[0]
    np.testing.assert_array_equal(np.asarray(rgb),
                                  np.ones((o.shape[0], 3), np.float32))
    rep = ar.report()
    assert rep["full_dead_tiles"] == 1
    assert rep["dead_ray_fraction"] == 1.0
    assert rep["memo"]["hits"] > 0               # recon read memoized rows
    assert rep["skipped_fine_samples"] == o.shape[0] * ar.budgets[-1]
