"""PEU — positional encoding unit (paper §4.2, Fig. 4).

Three frequency-matrix modes behind one API, exactly the paper's "universal
PEU":

* ``nerf_fixed``  — the NeRF encoding: gamma(x) = [x, sin(2^k x), cos(2^k x)]
  for k = 0..L-1 (octave-spaced fixed frequencies).
* ``rff_iso``     — isotropic random Fourier features: A ~ N(0, sigma^2 I),
  phi(x) = [cos(A^T x), sin(A^T x)] (implicit geometry / SDF encoding).
* ``rff_aniso``   — anisotropic RFF: per-axis sigmas (neural image-based
  rendering of implicit geometries).

The paper's CORDIC 'double-angle' trick (§4.2: for fixed NeRF frequencies the
input series doubles one after another, so sin/cos(2^{k+1} x) come from
sin/cos(2^k x) with 2 muls + 1 add instead of a fresh transcendental) is
implemented as ``double_angle=True`` — it is also how the fused PLCore kernel
(kernels/fused_plcore.py) computes the encoding without re-materializing the
frequency matrix.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# -------------------------------------------------------- frequency matrix --
def make_frequency_matrix(mode: str, in_dim: int, n_features: int,
                          key: Optional[jax.Array] = None,
                          sigma: float = 10.0,
                          sigmas: Optional[np.ndarray] = None) -> jnp.ndarray:
    """A (in_dim, n_features) — Fig. 4(a)'s three frequency patterns."""
    if mode == "nerf_fixed":
        # octave-spaced axis-aligned frequencies: n_features = in_dim * L
        L = n_features // in_dim
        A = np.zeros((in_dim, in_dim * L), np.float32)
        for k in range(L):
            for a in range(in_dim):
                A[a, k * in_dim + a] = 2.0 ** k
        return jnp.asarray(A)
    if mode == "rff_iso":
        assert key is not None
        return sigma * jax.random.normal(key, (in_dim, n_features))
    if mode == "rff_aniso":
        assert key is not None and sigmas is not None
        s = jnp.asarray(sigmas, jnp.float32).reshape(in_dim, 1)
        return s * jax.random.normal(key, (in_dim, n_features))
    raise ValueError(f"unknown encoding mode {mode!r}")


def fourier_features(x, A):
    """phi(x; A) = [cos(A^T x), sin(A^T x)]  (paper eq. (1)).

    x: (..., in_dim); A: (in_dim, F) -> (..., 2F).
    """
    z = x @ A
    return jnp.concatenate([jnp.cos(z), jnp.sin(z)], axis=-1)


# ----------------------------------------------------------- NeRF encoding --
def nerf_encoding(x, n_freqs: int, include_input: bool = True):
    """gamma(x) = [x, sin(2^0 x), cos(2^0 x), ..., sin(2^{L-1} x), cos(...)].

    x: (..., D) -> (..., D*(2*n_freqs) [+ D]). Frequency-major layout
    (all D channels of octave k contiguous) to match the PEU's streaming
    order and the fused kernel.
    """
    scales = 2.0 ** jnp.arange(n_freqs, dtype=x.dtype)          # (L,)
    xb = x[..., None, :] * scales[:, None]                       # (..., L, D)
    enc = jnp.concatenate([jnp.sin(xb), jnp.cos(xb)], axis=-1)   # (..., L, 2D)
    enc = enc.reshape(*x.shape[:-1], -1)
    if include_input:
        enc = jnp.concatenate([x, enc], axis=-1)
    return enc


def nerf_encoding_double_angle(x, n_freqs: int, include_input: bool = True):
    """Same output as ``nerf_encoding`` via the PEU double-angle recurrence.

    sin(2a) = 2 sin(a) cos(a); cos(2a) = 1 - 2 sin^2(a). One transcendental
    pair total, then 2 muls + 1 add per octave (paper §4.2).
    """
    s = jnp.sin(x)
    c = jnp.cos(x)

    def octave(carry, _):
        s, c = carry
        return (2.0 * s * c, 1.0 - 2.0 * s * s), (s, c)

    (_, _), (ss, cc) = jax.lax.scan(octave, (s, c), None, length=n_freqs)
    # ss/cc: (L, ..., D) -> (..., L, 2D) frequency-major
    ss = jnp.moveaxis(ss, 0, -2)
    cc = jnp.moveaxis(cc, 0, -2)
    enc = jnp.concatenate([ss, cc], axis=-1).reshape(*x.shape[:-1], -1)
    if include_input:
        enc = jnp.concatenate([x, enc], axis=-1)
    return enc


# ------------------------------------------------------------ universal PEU -
class PEU:
    """The universal positional-encoding unit.

    Configured once (mode + frequency matrix), applied to streamed
    positions/directions — mirrors Fig. 4(b): frequency matrix held in local
    memory, coordinates streamed through the MAC array, sin/cos applied to
    the product.
    """

    def __init__(self, mode: str, in_dim: int, *, n_freqs: int = 0,
                 n_features: int = 0, key=None, sigma: float = 10.0,
                 sigmas=None, include_input: bool = True,
                 double_angle: bool = False):
        self.mode = mode
        self.in_dim = in_dim
        self.n_freqs = n_freqs
        self.include_input = include_input
        self.double_angle = double_angle
        if mode == "nerf_fixed":
            assert n_freqs > 0
            self.A = make_frequency_matrix(mode, in_dim, in_dim * n_freqs)
            self.out_dim = in_dim * 2 * n_freqs + (in_dim if include_input else 0)
        else:
            assert n_features > 0
            self.A = make_frequency_matrix(mode, in_dim, n_features, key=key,
                                           sigma=sigma, sigmas=sigmas)
            self.out_dim = 2 * n_features + (in_dim if include_input else 0)

    def __call__(self, x):
        if self.mode == "nerf_fixed":
            fn = nerf_encoding_double_angle if self.double_angle else nerf_encoding
            return fn(x, self.n_freqs, self.include_input)
        enc = fourier_features(x, self.A.astype(x.dtype))
        if self.include_input:
            enc = jnp.concatenate([x, enc], axis=-1)
        return enc


# ------------------------------------------- Mip-NeRF: cones and the IPE ----
def conical_frustum_to_gaussian(d, t0, t1, radius):
    """Mean and diagonal covariance of the conical frustum between
    distances t0 and t1 along each ray (Mip-NeRF eq. 7-8, the stable
    form of ``internal/mip.py``), as a Gaussian.

    d: (..., 3) directions; t0, t1: (..., N); radius: (..., 1), the
    cone's radius per unit of t. Returns (t_mean (..., N), cov_diag
    (..., N, 3)): the sample's mean is ``o + t_mean * d``."""
    mu = 0.5 * (t0 + t1)
    hw = 0.5 * (t1 - t0)
    mu2, hw2 = mu * mu, hw * hw
    den = 3.0 * mu2 + hw2
    t_mean = mu + 2.0 * mu * hw2 / den
    t_var = hw2 / 3.0 - (4.0 / 15.0) * (hw2 * hw2 * (12.0 * mu2 - hw2)
                                         / (den * den))
    r_var = (radius * radius) * (mu2 / 4.0 + (5.0 / 12.0) * hw2
                                 - (4.0 / 15.0) * hw2 * hw2 / den)
    dd = d * d
    null = 1.0 - dd / jnp.maximum(1e-10, jnp.sum(dd, -1, keepdims=True))
    cov = (t_var[..., None] * dd[..., None, :]
           + r_var[..., None] * null[..., None, :])
    return t_mean, cov


def _octaves(x, lo: int, hi: int, base: float):
    """[base^lo x, ..., base^(hi-1) x] along the last axis, octave-major."""
    return jnp.concatenate([(base ** l) * x for l in range(lo, hi)], axis=-1)


def integrated_pos_enc(mean, var, n_freqs: int):
    """Mip-NeRF's IPE of a diagonal Gaussian (``integrated_pos_enc``):
    with y = [2^0 mean, ..., 2^(L-1) mean] and v = [4^0 var, ...,
    4^(L-1) var], [sin(y) exp(-v/2), cos(y) exp(-v/2)] — Mip-NeRF's own
    layout, all sines then all cosines, no identity: (..., 3) ->
    (..., 6L)."""
    y = _octaves(mean, 0, n_freqs, 2.0)
    a = jnp.exp(-0.5 * _octaves(var, 0, n_freqs, 4.0))
    return jnp.concatenate([jnp.sin(y) * a, jnp.cos(y) * a], axis=-1)


def integrated_pos_enc_recurrence(mean, var, n_freqs: int,
                                  block: int = 4):
    """``integrated_pos_enc`` the PEU's way, the form the fused kernel
    runs. Octaves go in blocks of ``block``, each one lane-contiguous
    array, so a step works on every octave of the block at once: the
    first block's sin/cos directly, each next block's from the last by
    ``block`` double-angle steps (sin 2x = 2 sin x cos x, cos 2x =
    1 - 2 sin^2 x), which keeps every argument small. The attenuation
    takes one exp per block, exp(-4^l var / 2) from the block's exactly
    scaled variances: the per-octave recurrence a_{l+1} = a_l^4 from
    a_0 = exp(-var / 2) would carry var only to the f32 spacing of
    numbers near 1 (~1.2e-7), which is most of a pixel footprint's
    variance."""
    blk = min(block, n_freqs)
    y = _octaves(mean, 0, blk, 2.0)
    s, c = jnp.sin(y), jnp.cos(y)
    v = _octaves(var, 0, blk, 4.0)
    sins, coss = [], []
    for k in range(-(-n_freqs // blk)):
        a = jnp.exp((-0.5 * 4.0 ** (k * blk)) * v)
        sins.append(s * a)
        coss.append(c * a)
        for _ in range(blk):
            s, c = 2.0 * s * c, 1.0 - 2.0 * s * s
    n = 3 * n_freqs
    return jnp.concatenate([jnp.concatenate(sins, axis=-1)[..., :n],
                            jnp.concatenate(coss, axis=-1)[..., :n]],
                           axis=-1)
