"""Property-based invariants over the rendering core.

Runs under REAL hypothesis when the package is installed (adaptive
search + shrinking) and under the deterministic ``_hypothesis_shim``
fixed-seed sweep on bare containers — the conftest installs whichever
is available, and these tests use only the shared API slice (keyword
``given``, ``settings``, floats/integers/arrays strategies).

Three invariant families the example-based suites can't sweep:

* Ray-order permutation invariance: the PLCore treats every ray
  independently, so permuting a tile's rays permutes the output rows and
  changes NOTHING else — bit for bit (each row's fp reduction order is
  internal to the row).
* Tail-pad no-leak: the tile program's padded lanes (ray count not a
  multiple of the tile size) must be unable to influence real lanes —
  rendering the same real rays next to two DIFFERENT garbage tails
  yields bit-identical real rows.
* Sampling monotonicity/exactness: ``importance_det`` returns
  nondecreasing samples inside the ``t_mid`` span for any weight
  profile (including degenerate single-bin pdfs), and
  ``merge_sorted_ranks`` equals the sort-based merge bit-for-bit on
  arbitrary sorted inputs with ties.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings
import hypothesis.strategies as st
from hypothesis.extra.numpy import arrays

from repro.configs.nerf_icarus import tiny
from repro.core import sampling
from repro.core.plcore import flatten_pad_rays, plcore_decls, render_rays
from repro.models.params import init_params

N_RAYS = 16


@pytest.fixture(scope="module")
def scene():
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                         "float32")
    k = jax.random.PRNGKey(1)
    o = jax.random.uniform(k, (N_RAYS, 3), minval=-0.5, maxval=0.5)
    d = jax.random.uniform(jax.random.PRNGKey(2), (N_RAYS, 3),
                           minval=0.2, maxval=1.0)
    return cfg, params, np.asarray(o), np.asarray(d)


# ------------------------------------------------ permutation invariance ---
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=(1 << 16)))
def test_ray_order_permutation_invariance(scene, seed):
    cfg, params, o, d = scene
    perm = np.random.default_rng(seed).permutation(N_RAYS)
    base = np.asarray(render_rays(cfg, params, jnp.asarray(o),
                                  jnp.asarray(d))["rgb"])
    shuf = np.asarray(render_rays(cfg, params, jnp.asarray(o[perm]),
                                  jnp.asarray(d[perm]))["rgb"])
    np.testing.assert_array_equal(shuf, base[perm])


# -------------------------------------------------------- tail-pad no-leak -
@settings(max_examples=10, deadline=None)
@given(n_real=st.integers(min_value=1, max_value=N_RAYS - 1),
       tail=arrays(np.float32, (N_RAYS, 3),
                   elements=st.floats(min_value=0.125, max_value=1.0,
                                   width=32)))
def test_tail_pad_cannot_leak_into_real_rays(scene, n_real, tail):
    """Two renders of the same real rays with different garbage tails:
    the real rows must be bit-identical (per-ray independence is what
    makes flatten_pad_rays' zero-pad safe)."""
    cfg, params, o, d = scene
    for garbage in (tail, tail[::-1] + 0.25):
        assert np.isfinite(garbage).all()
    outs = []
    for garbage in (tail, tail[::-1] + 0.25):
        o_pad = np.concatenate([o[:n_real], garbage[n_real:]], axis=0)
        d_pad = np.concatenate([d[:n_real], garbage[n_real:]], axis=0)
        rgb = np.asarray(render_rays(cfg, params, jnp.asarray(o_pad),
                                     jnp.asarray(d_pad))["rgb"])
        outs.append(rgb[:n_real])
    np.testing.assert_array_equal(outs[0], outs[1])


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=1, max_value=200),
       batch=st.integers(min_value=1, max_value=64))
def test_flatten_pad_rays_structure(n, batch):
    """The shared tiler: true ray count preserved, first-n rows exact,
    tile count minimal, padded direction rows never zero-norm."""
    rng = np.random.default_rng(n * 1000 + batch)
    H = n
    ro = rng.uniform(-1, 1, (H, 1, 3)).astype(np.float32)
    rd = rng.uniform(0.2, 1, (H, 1, 3)).astype(np.float32)
    o_t, d_t, n_out = flatten_pad_rays(jnp.asarray(ro), jnp.asarray(rd),
                                       batch)
    assert n_out == n
    T = -(-n // batch)
    assert o_t.shape == d_t.shape == (T, batch, 3)
    np.testing.assert_array_equal(
        np.asarray(o_t).reshape(-1, 3)[:n], ro.reshape(-1, 3))
    np.testing.assert_array_equal(
        np.asarray(d_t).reshape(-1, 3)[:n], rd.reshape(-1, 3))
    norms = np.linalg.norm(np.asarray(d_t).reshape(-1, 3), axis=-1)
    assert (norms > 0).all()


# -------------------------------------------------- sampling monotonicity --
@settings(max_examples=10, deadline=None)
@given(w=arrays(np.float32, (4, 17),
                elements=st.floats(min_value=0.0, max_value=1.0,
                                   width=32)),
       lo=st.floats(min_value=0.5, max_value=2.0),
       span=st.floats(min_value=0.1, max_value=4.0))
def test_importance_det_monotone_and_in_span(w, lo, span):
    t_mid = jnp.linspace(lo, lo + span, 17)[None, :].repeat(4, axis=0)
    out = np.asarray(sampling.importance_det(t_mid, jnp.asarray(w), 12))
    assert out.shape == (4, 12)
    assert (np.diff(out, axis=-1) >= 0).all(), "samples must be sorted"
    assert (out >= lo - 1e-5).all() and (out <= lo + span + 1e-5).all()
    # bit-identity with the host searchsorted/gather path, any weights
    np.testing.assert_array_equal(
        out, np.asarray(sampling.importance(t_mid, jnp.asarray(w), 12,
                                            key=None)))


@settings(max_examples=10, deadline=None)
@given(a=arrays(np.float32, (3, 10),
                elements=st.floats(min_value=0.0, max_value=1.0,
                                   width=32)),
       b=arrays(np.float32, (3, 14),
                elements=st.floats(min_value=0.0, max_value=1.0,
                                   width=32)))
def test_merge_sorted_ranks_matches_sort(a, b):
    """Rank-merge == jnp.sort merge on arbitrary sorted inputs; ties
    forced by quantizing to 1/8 steps within AND across the sets."""
    t_a = jnp.sort(jnp.asarray(np.round(a * 8) / 8), axis=-1)
    t_b = jnp.sort(jnp.asarray(np.round(b * 8) / 8), axis=-1)
    merged = np.asarray(sampling.merge_sorted_ranks(t_a, t_b))
    assert (np.diff(merged, axis=-1) >= 0).all(), "merge must be sorted"
    np.testing.assert_array_equal(
        merged, np.asarray(sampling.merge_sorted(t_a, t_b)))


# ---------------------------------------------------- adaptive sampling ---
@pytest.fixture(scope="module")
def adaptive_scene():
    """Fused-kernel pipeline over a mixed empty-space scene (biased sigma
    head) plus its calibration aux — shared by the ASDR properties."""
    from repro.core.pipeline import (AdaptiveRenderer, PackedPlcore,
                                     build_scene_aux)
    cfg = tiny()
    params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0),
                         "float32")
    for net in params:
        params[net]["sigma"]["b"] = params[net]["sigma"]["b"] - 0.5
    pp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
    aux = build_scene_aux(pp, grid_res=16, probe_hw=6, memo_mb=8.0)
    return pp, AdaptiveRenderer(pp, aux)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=(1 << 16)))
def test_adaptive_bucket_purity(adaptive_scene, seed):
    """Every adaptive tile the scheduler coalesces is BUDGET-PURE: all
    its rays classify into the class whose n_fine the tile renders at,
    and dead-bucket tiles carry only hinted-dead (provably-empty) rays —
    no ray is ever over/under-sampled by its tile-mates."""
    from repro.serving import RenderEngine, RenderRequest, SceneCache
    pp, _ = adaptive_scene
    rng = np.random.default_rng(seed)
    cache = SceneCache(lambda sid: pp, capacity_mb=64.0)
    eng = RenderEngine(cache, tile_rays=64, adaptive_sampling=True,
                       memo_mb=8.0, adaptive_grid_res=16,
                       adaptive_probe_hw=6)
    seen = []
    orig = eng.adaptive.account
    eng.adaptive.account = (
        lambda tile, info, stats: (seen.append((tile, info)),
                                   orig(tile, info, stats))[1])
    for _ in range(2):
        eng.submit(RenderRequest("s0", hw=12,
                                 theta=float(rng.uniform(0, 360)),
                                 phi=float(rng.uniform(-35, -15))))
    eng.drain()
    assert seen, "no adaptive tiles dispatched"
    ar = eng.adaptive.renderer("s0", pp)
    for tile, info in seen:
        cls = ar.classify_rays(tile.rays_o, tile.rays_d)
        hint = ar.dead_hint(tile.rays_o, tile.rays_d)
        if tile.dead_bucket:
            assert hint.all(), "dead-bucket tile holds a non-hinted ray"
        else:
            assert not hint.any(), "hinted-dead ray leaked into a class tile"
            c = ar.budgets.index(tile.budget)
            assert (cls == c).all(), (tile.budget, np.unique(cls))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=(1 << 16)))
def test_memo_hit_rows_bit_identical(adaptive_scene, seed):
    """Rows consumed from the trunk memo equal a fresh trunk evaluation
    at the SAME voxel centers bit-for-bit — memoization is a cache, not
    an approximation (the dead-ray recon consumes exactly what the
    kernel's trunk would have produced)."""
    from repro.core.pipeline import trunk_rows
    pp, ar = adaptive_scene
    rng = np.random.default_rng(seed)
    o = rng.uniform(-0.3, 0.3, (8, 3)).astype(np.float32)
    d = rng.uniform(0.2, 1.0, (8, 3)).astype(np.float32)
    dead, vox, sigma, feat = ar.dead_and_rows(o, d)
    idx = np.nonzero(dead)[0]
    if not idx.size:
        return
    fresh = trunk_rows(pp, ar.aux.stats.voxel_centers(
        vox[idx].reshape(-1)))
    got = np.concatenate([sigma[idx].reshape(-1, 1),
                          feat[idx].reshape(fresh.shape[0], -1)], axis=1)
    np.testing.assert_array_equal(got, fresh)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=(1 << 16)))
def test_adaptive_per_bucket_permutation_invariance(adaptive_scene, seed):
    """Permuting the rays of an adaptive tile permutes its output rows
    and changes nothing else — bit for bit, across the mixed dead/alive
    path (memo warmed first so both orders see identical residency)."""
    _, ar = adaptive_scene
    rng = np.random.default_rng(seed)
    o = rng.uniform(-0.4, 0.4, (N_RAYS, 3)).astype(np.float32)
    d = rng.uniform(0.2, 1.0, (N_RAYS, 3)).astype(np.float32)
    ar.render_tile(o, d, budget=int(ar.budgets[0]))      # warm the memo
    base = np.asarray(ar.render_tile(o, d, budget=int(ar.budgets[0]))[0])
    perm = rng.permutation(N_RAYS)
    shuf = np.asarray(ar.render_tile(o[perm], d[perm],
                                     budget=int(ar.budgets[0]))[0])
    np.testing.assert_array_equal(shuf, base[perm])
