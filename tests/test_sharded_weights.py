"""Mesh-sharded packed PLCore weights (runtime.sharding + core.pipeline).

Two layers of coverage:

* In-process (1 CPU device): the pack -> unstack reconstruction is a
  bit-exact inverse for both the f32 and RMCM layouts, the residency
  model is self-consistent, and a 1-device mesh degrades gracefully to
  replicated while still rendering bit-identically through the sharded
  code path.
* Subprocess (the conftest ``fake_devices`` fixture — 8 fake CPU
  devices, configured before jax initializes): on a REAL
  8-way layer shard, image (XLA), kernel (one-pass + two-pass fused),
  RMCM and engine modes all render bit-identical pixels vs the
  replicated path; per-device resident bytes shrink ~1/8; the SceneCache
  holds proportionally more sharded scenes at fixed capacity; and the
  per-layer gather counter pins the just-in-time collective structure.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.nerf_icarus import tiny
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.kernels import ops as kops
from repro.models.params import init_params
from repro.runtime import sharding as rsh


# ------------------------------------------------------------ in-process ---
def _params(cfg, seed=0):
    return init_params(plcore_decls(cfg), jax.random.PRNGKey(seed),
                       "float32")


def test_unstack_is_exact_inverse_f32():
    cfg = tiny()
    params = _params(cfg)["coarse"]
    packed = kops.stack_plcore_weights(cfg, params)
    trunk, quant_t = kops.unstack_trunk_params(cfg, packed)
    assert quant_t is None
    for i in range(cfg.trunk_layers):
        w0 = np.asarray(params["trunk"][f"l{i}"]["w"], np.float32)
        assert np.array_equal(np.asarray(trunk[f"l{i}"]["w"]), w0)
        assert np.array_equal(np.asarray(trunk[f"l{i}"]["b"]),
                              np.asarray(params["trunk"][f"l{i}"]["b"],
                                         np.float32))


def test_unstack_is_exact_inverse_rmcm():
    cfg = tiny()
    params = _params(cfg)["coarse"]
    quant = rmcm.quantize_tree(params)
    packed = kops.stack_plcore_weights(cfg, params, quant)
    trunk, quant_t = kops.unstack_trunk_params(cfg, packed)
    for i in range(cfg.trunk_layers):
        q0 = quant["trunk"][f"l{i}"]["w"]
        q1 = quant_t[f"l{i}"]["w"]
        assert np.array_equal(np.asarray(q1["mag"]), np.asarray(q0["mag"]))
        assert np.array_equal(np.asarray(q1["sign"]), np.asarray(q0["sign"]))
        assert np.array_equal(np.asarray(q1["scale"]),
                              np.asarray(q0["scale"], np.float32))
        assert "w" not in trunk[f"l{i}"]  # RMCM trunk never stacks raw f32


def test_resident_bytes_model():
    cfg = tiny()
    # n_shards=1 is exactly the replicated f32 packed layout
    packed = kops.stack_plcore_weights(cfg, _params(cfg)["coarse"])
    assert (kops.plcore_resident_weight_bytes(cfg, 1)
            == sum(a.nbytes for a in packed.values()))
    full = kops.plcore_resident_weight_bytes(cfg, 1)
    W, L = cfg.trunk_width, cfg.trunk_layers
    P = -(-(W + cfg.pos_enc_dim) // 128) * 128
    trunk = 4 * (L * P * W + L * W)
    for k in (2, 4):
        assert (kops.plcore_resident_weight_bytes(cfg, k)
                == full - trunk + trunk // k)


def test_single_device_mesh_degrades_to_replicated():
    cfg = tiny()
    mesh = rsh.plcore_mesh()
    assert rsh.plcore_shard_count(mesh, cfg.trunk_layers) == 1
    params = _params(cfg)
    from repro.data import rays as R
    ro, rd = R.camera_rays(R.pose_spherical(30.0, -25.0, 4.0), 8, 8, 7.2)
    base = PackedPlcore(cfg, params)
    shard = PackedPlcore(cfg, params, shard_mesh=mesh)
    a = np.asarray(base.render_image(ro, rd, rays_per_batch=64))
    b = np.asarray(shard.render_image(ro, rd, rays_per_batch=64))
    assert np.array_equal(a, b)
    # sharded residency drops the raw trunk copies even on one device
    assert all("trunk" not in shard.params[n] for n in ("coarse", "fine"))


# ------------------------------------------------- 8-device subprocess -----
_SNIPPET = r"""
import numpy as np
from dataclasses import replace
import jax
from repro.configs.nerf_icarus import tiny
from repro.core import rmcm
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.data import rays as R
from repro.models.params import init_params
from repro.runtime import sharding as rsh
from repro.serving.engine import RenderEngine, RenderRequest
from repro.serving.scene_cache import SceneCache, device_nbytes, \
    plcore_nbytes

cfg = replace(tiny(), trunk_layers=8, skip_at=(4,))
L = cfg.trunk_layers
params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32")
mesh = rsh.plcore_mesh()
assert len(jax.devices()) == 8
assert rsh.plcore_shard_count(mesh, L) == 8, "8 layers -> 8-way shard"
ro, rd = R.camera_rays(R.pose_spherical(45.0, -25.0, 4.0), 16, 16, 14.4)

# ---- image mode (XLA path): bit-identity + per-layer gather count -------
base = PackedPlcore(cfg, params)
shard = PackedPlcore(cfg, params, shard_mesh=mesh)
g0 = rsh.plcore_gather_count()
img_s = np.asarray(shard.render_image(ro, rd, rays_per_batch=128))
# one all-gather per layer per stacked array: (trunk_w, trunk_b) x 2 nets
assert rsh.plcore_gather_count() - g0 == 2 * 2 * L, \
    rsh.plcore_gather_count() - g0
img_r = np.asarray(base.render_image(ro, rd, rays_per_batch=128))
assert np.array_equal(img_r, img_s), "sharded XLA image != replicated"
# cached program: a repeat render re-traces (and re-counts) nothing
img_s2 = np.asarray(shard.render_image(ro, rd, rays_per_batch=128))
assert rsh.plcore_gather_count() - g0 == 2 * 2 * L
assert np.array_equal(img_s, img_s2)
print("ok image-mode bit-identity + gather count")

# ---- per-device residency: trunk shards at 1/8, cache bytes shrink ------
tw = shard.packed["coarse"]["trunk_w"]
assert device_nbytes(tw) * 8 == tw.size * tw.dtype.itemsize
assert all("trunk" not in shard.params[n] for n in ("coarse", "fine"))
# non-kernel residents keep ONLY the trunk stacks packed: the XLA path
# renders heads from the retained raw params, so packed heads would be
# dead resident weight
assert set(shard.packed["coarse"]) == {"trunk_w", "trunk_b"}
repl_kb = PackedPlcore(cfg, params, use_kernel=True)
shard_kb = PackedPlcore(cfg, params, use_kernel=True, shard_mesh=mesh)
assert plcore_nbytes(shard_kb) < plcore_nbytes(repl_kb) / 3, \
    (plcore_nbytes(shard_kb), plcore_nbytes(repl_kb))
print("ok per-device residency")

# ---- kernel modes: one-pass chain and two-pass fused --------------------
a = np.asarray(repl_kb.render_image(ro, rd, rays_per_batch=128))
b = np.asarray(shard_kb.render_image(ro, rd, rays_per_batch=128))
assert np.array_equal(a, b), "sharded kernel image != replicated"
repl_tp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True)
shard_tp = PackedPlcore(cfg, params, use_kernel=True, fuse_two_pass=True,
                        shard_mesh=mesh)
a = np.asarray(repl_tp.render_image(ro, rd, rays_per_batch=128))
b = np.asarray(shard_tp.render_image(ro, rd, rays_per_batch=128))
assert np.array_equal(a, b), "sharded two-pass fused != replicated"
print("ok kernel-mode bit-identity")

# ---- RMCM: quantized stacks gather 4 arrays per net ---------------------
quant = {n: rmcm.quantize_tree(params[n]) for n in ("coarse", "fine")}
repl_q = PackedPlcore(cfg, params, quant=quant)
shard_q = PackedPlcore(cfg, params, quant=quant, shard_mesh=mesh)
g1 = rsh.plcore_gather_count()
b = np.asarray(shard_q.render_image(ro, rd, rays_per_batch=128))
assert rsh.plcore_gather_count() - g1 == 2 * 4 * L  # mag/sgn/scl/b x 2 nets
a = np.asarray(repl_q.render_image(ro, rd, rays_per_batch=128))
assert np.array_equal(a, b), "sharded RMCM image != replicated"
print("ok rmcm bit-identity + gather count")

# ---- engine mode: sharded SceneCache residents, coalesced tiles ---------
def loader(shard_mesh):
    def load(sid):
        p = init_params(plcore_decls(cfg), jax.random.PRNGKey(int(sid[1:])),
                       "float32")
        return PackedPlcore(cfg, p, shard_mesh=shard_mesh)
    return load

reqs = [RenderRequest("s0", hw=12), RenderRequest("s1", hw=16),
        RenderRequest("s0", hw=16)]
imgs = {}
for name, m in (("repl", None), ("shard", mesh)):
    eng = RenderEngine(SceneCache(loader(m), capacity_mb=64.0),
                       tile_rays=128)
    rids = [eng.submit(r) for r in reqs]
    eng.drain()
    imgs[name] = [eng.take(rid).image for rid in rids]
for ir, is_ in zip(imgs["repl"], imgs["shard"]):
    assert np.array_equal(ir, is_), "engine images differ under sharding"
    assert not np.isnan(is_).any()
print("ok engine-mode bit-identity")

# ---- cache capacity scales with the mesh --------------------------------
per_repl = plcore_nbytes(PackedPlcore(
    cfg, init_params(plcore_decls(cfg), jax.random.PRNGKey(0), "float32"),
    use_kernel=True))
cap_mb = 2.5 * per_repl / (1 << 20)          # room for 2 replicated scenes
def kloader(shard_mesh):
    def load(sid):
        p = init_params(plcore_decls(cfg), jax.random.PRNGKey(int(sid[1:])),
                       "float32")
        return PackedPlcore(cfg, p, use_kernel=True, shard_mesh=shard_mesh)
    return load
c_repl = SceneCache(kloader(None), capacity_mb=cap_mb)
c_shard = SceneCache(kloader(mesh), capacity_mb=cap_mb)
for i in range(6):
    c_repl.get(f"s{i}")
    c_shard.get(f"s{i}")
assert len(c_repl) == 2, c_repl.stats()
assert len(c_shard) == 6, c_shard.stats()   # ~4.9x smaller residents
assert c_shard.evictions == 0
print("ok sharded cache capacity")
print("ALL OK")
"""


@pytest.mark.slow
def test_sharded_weights_multidevice(fake_devices):
    fake_devices(_SNIPPET)
