"""RMCM dequant-fused matmul Pallas kernel (paper §4.3 -> TPU).

y = x @ W where W is stored in the 9-bit RMCM format (uint8 approximated
magnitudes + bit-packed signs + per-output-channel fp32 scales,
1.125 B/weight). The kernel unpacks and dequantizes INSIDE VMEM and feeds
the MXU — the TPU restatement of the paper's shift-add MCM array: weight
bytes cross the HBM->VMEM boundary in packed form, so the memory-side cost
of the weight matrix is ~1.8x smaller than bf16 and ~3.6x smaller than f32.
That is the term that matters for memory-bound decode (EXPERIMENTS.md
§Roofline).

Tiling: grid (M/bm, N/bn, K/bk); the fp32 accumulator lives in the output
block (revisited across the k axis — standard Pallas accumulation
pattern); bm/bn/bk default to MXU-aligned 128 (bk to 256 = 32 packed sign
bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unpack_signs(bits, bk: int):
    """(bk//8, bn) uint8 -> (bk, bn) {0,1} int32. Bit j of byte i = row
    8i+j. Unpacks in int32 from an in-register iota: Mosaic has no 8-bit
    vector shifts, and a kernel may not capture an array constant."""
    b = bits.astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, b.shape[-1]), 1)
    expanded = (b[:, None, :] >> shifts) & 1
    return expanded.reshape(bk, bits.shape[-1])


def _kernel(x_ref, mag_ref, sgn_ref, scale_ref, o_ref, *, bk: int,
            n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                     # (bm, bk)
    mag = mag_ref[...].astype(jnp.float32)                 # (bk, bn)
    sgn = _unpack_signs(sgn_ref[...], bk).astype(jnp.float32)
    w = mag * (1.0 - 2.0 * sgn)                            # signed magnitude
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _scale():
        # per-output-channel scale applied once, after full-K accumulation
        o_ref[...] = ((o_ref[...] + acc) *
                      scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(k < n_k - 1)
    def _acc():
        o_ref[...] += acc


def rmcm_matmul(x, packed: dict, *, bm: int = 128, bn: int = 128,
                bk: int = 256, interpret: bool = True):
    """x: (M, K) float; packed: rmcm.pack() of a (K, N) weight.

    Returns (M, N) in x.dtype. The output block is an fp32 accumulator
    (revisited across k); the cast to x.dtype happens host-side after the
    call. Pads every axis to the block size; K-padding rows are
    zero-magnitude so they contribute 0.
    """
    mag, sgn, scale = packed["mag"], packed["sign_bits"], packed["scale"]
    M, K = x.shape
    Kw, N = mag.shape
    assert K == packed["k"] == Kw, (K, packed["k"], mag.shape)

    bm, bn, bk = min(bm, _rup(M, 8)), min(bn, _rup(N, 8)), min(bk, _rup(K, 8))
    Mp, Np, Kp = _rup(M, bm), _rup(N, bn), _rup(K, bk)
    x_p = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    mag_p = jnp.pad(mag, ((0, Kp - K), (0, Np - N)))
    sgn_p = jnp.pad(sgn, ((0, Kp // 8 - sgn.shape[0]), (0, Np - N)))
    scale_p = jnp.pad(scale.reshape(1, N), ((0, 0), (0, Np - N)))

    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // 8, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),  # fp32 accum
        interpret=interpret,
    )(x_p, mag_p, sgn_p, scale_p)
    return out[:M, :N].astype(x.dtype)


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m
