"""The served PLCore kernels compile for a TPU v5e at the published widths.

Each case lowers a kernel entry point at ``CONFIG`` (8x256 trunk, skip at
4, 128-wide colour branch, L=10/4, 64 + 128 samples), or for the cone
kernel at ``MIPNERF`` (one shared 8x256 network, IPE L=16, 128 + 128
intervals), with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler installed here — no chip needed. That
is what Mosaic refuses and interpret mode accepts: rank-1 blocks, ops
without a Mosaic lowering, unaligned dynamic slices, and scoped VMEM
beyond the limit the kernel is given (the VMEM model's own estimate, so
a model that under-counts fails here), at the ray block the two-pass
kernels step by on the chip (``pick_two_pass_block``). Nothing runs, so
no result is checked; the CPU parity tests hold the same kernels to the
reference.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.nerf_icarus import CONFIG, MIPNERF
from repro.core import rmcm
from repro.core.plcore import plcore_decls
from repro.kernels import ops as kops
from repro.models.params import init_params

N_RAYS = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around
    these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _packed(quantized: bool, sharding, cfg=CONFIG):
    """Shapes of the networks' packed layouts (both at CONFIG, the one
    shared network at MIPNERF; no weights are materialized: the pack is
    traced abstractly)."""
    def build():
        params = init_params(plcore_decls(cfg), jax.random.PRNGKey(0))
        return {net: kops.stack_plcore_weights(
                    cfg, params[net],
                    rmcm.quantize_tree(params[net]) if quantized else None)
                for net in params}
    return _abstract(jax.eval_shape(build), sharding)


def _two_pass(quantized: bool, ert: bool):
    def case(sharding):
        rays = jax.ShapeDtypeStruct((N_RAYS, 3), jnp.float32,
                                    sharding=sharding)
        args = [_packed(quantized, sharding), rays, rays]
        if ert:
            args.append(jax.ShapeDtypeStruct((N_RAYS,), jnp.float32,
                                             sharding=sharding))
            fn = lambda pk, o, d, alive: kops.fused_render_two_pass(
                CONFIG, pk, o, d, ert_eps=1e-3, alive=alive,
                interpret=False)
        else:
            fn = lambda pk, o, d: kops.fused_render_two_pass(
                CONFIG, pk, o, d, interpret=False)
        return fn, args
    return case


def _cone_two_pass(sharding):
    """Mip-NeRF's cone two-pass kernel: IPE, the mask-form resample, one
    pinned network read by both passes, the (rt, 1) radius column."""
    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    fn = lambda pk, o, d, r: kops.fused_render_two_pass(
        MIPNERF, pk, o, d, radii=r, interpret=False)
    return fn, [_packed(False, sharding, MIPNERF), arr(N_RAYS, 3),
                arr(N_RAYS, 3), arr(N_RAYS, 1)]


def _one_pass(n_samples: int, ert: bool):
    """One of the two dispatches of the oracle rung (coarse at n_coarse
    samples, fine at n_coarse + n_fine with the ERT mask)."""
    def case(sharding):
        def arr(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32,
                                        sharding=sharding)
        args = [_packed(False, sharding)["fine"], arr(N_RAYS, 3),
                arr(N_RAYS, 3), arr(N_RAYS, n_samples),
                arr(N_RAYS, n_samples)]
        if ert:
            args.append(arr(N_RAYS))
        fn = lambda pk, o, d, t, dl, *alive: kops.fused_render(
            CONFIG, None, o, d, t, dl, packed=pk,
            alive=alive[0] if alive else None, interpret=False)
        return fn, args
    return case


CASES = {
    "two_pass_f32": _two_pass(quantized=False, ert=False),
    "two_pass_rmcm": _two_pass(quantized=True, ert=False),
    "two_pass_alive_ert": _two_pass(quantized=False, ert=True),
    "two_pass_cone_mipnerf": _cone_two_pass,
    "one_pass_coarse": _one_pass(CONFIG.n_coarse, ert=False),
    "one_pass_fine_alive": _one_pass(CONFIG.n_samples, ert=True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, args = CASES[name](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.startswith("two_pass"):
        # built at the two-pass rule's block (4 rays at CONFIG and at
        # MIPNERF), with the VMEM model's figure at that block as limit
        cfg = MIPNERF if "cone" in name else CONFIG
        rt = kops.pick_ray_tile_two_pass(cfg, quantized="rmcm" in name)
        assert kops._RAY_BLOCK.value == kops.pick_two_pass_block(cfg, rt)


def test_vmem_model_counts_the_packed_layout():
    """The VMEM model's per-array shapes are the packed layout's own
    (biases as (1, n) rows), for both weight formats."""
    cfg = CONFIG
    params = jax.eval_shape(
        lambda: init_params(plcore_decls(cfg), jax.random.PRNGKey(0)))
    from repro.kernels import fused_plcore as fp
    for quantized in (False, True):
        packed = jax.eval_shape(lambda p: kops.stack_plcore_weights(
            cfg, p, rmcm.quantize_tree(p) if quantized else None),
            params["fine"])
        got = [(tuple(packed[k].shape) if packed[k].ndim > 1
                else (1,) + tuple(packed[k].shape),
                np.dtype(packed[k].dtype).itemsize)
               for k in fp._weight_order(quantized)]
        assert got == [(tuple(s), i) for s, i in
                       kops.kernel_weight_shapes(cfg, quantized)]
