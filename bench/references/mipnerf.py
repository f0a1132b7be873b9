"""Plain reference of the Mip-NeRF renderer served by mipnerf-blender.

Written from the published model (Barron et al., "Mip-NeRF",
arXiv:2103.13415, sections 3-4; ``google/mipnerf`` ``internal/mip.py``,
``internal/models.py`` and ``configs/blender.gin``) in straightforward
``jax.numpy``, with no kernel, cache or batching of its own. It imports
nothing of the system under test. One network renders both levels: 128
intervals between 129 evenly spaced edges of [near, far], each conical
frustum cast to a Gaussian and encoded by its integrated positional
encoding (2^0 .. 2^15, sines then cosines), density softplus(raw - 1),
colour sigmoid * 1.002 - 0.001, the coarse weights blurred (2-tap max,
2-tap mean), padded by 0.01 and resampled to 129 new edges by
``sorted_piecewise_constant_pdf``, the fine level on those alone, the
volume integral over the finite intervals, composited onto white.
Departures from mipnerf, all of them conventions of the system that the
reference has to share to compare pixels:

- unit directions, with t the distance along the ray (mipnerf's
  directions have z = -1, so its t is a depth): the pixel radius is the
  distance between the unit directions of the pixel and of the one a
  row below it (past the image for the last row; mipnerf copies the
  row above), times 2 / sqrt(12);
- focal length 0.9 hw and the orbit camera of this repo's scenes;
- the order of feature rows: the direction encoding is
  [d, sin(2^0 d), cos(2^0 d), ..., sin(2^3 d), cos(2^3 d)] (mipnerf:
  [d, all sines, all cosines]); the trunk and colour inputs concatenate
  [h, encoding] as mipnerf does;
- deterministic (test-time) sampling: u = linspace(0, 1 - eps_f32, 129),
  no noise.

``precision`` selects the arithmetic of every matrix product: ``highest``
is float32; ``high`` is three bfloat16 passes; ``bfloat16`` rounds the
operands to bfloat16 once and accumulates in float32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------- shapes ------
def layer_shapes(arch: dict) -> dict:
    """{net layer name: (rows, cols)} of the one network."""
    W = arch["trunk_width"]
    pe = 6 * arch["pos_freqs"]
    de = 3 + 6 * arch["dir_freqs"]
    shapes = {}
    din = pe
    for i in range(arch["trunk_layers"]):
        if i in arch["skip_at"]:
            din = W + pe
        shapes[f"trunk.l{i}"] = (din, W)
        din = W
    shapes["sigma"] = (W, 1)
    shapes["feat"] = (W, W)
    shapes["color0"] = (W + de, arch["color_width"])
    shapes["rgb"] = (arch["color_width"], 3)
    return shapes


# ---------------------------------------------------------- weights ------
def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    key = jax.random.key(0)
    s = int(seed)
    while True:
        key = jax.random.fold_in(key, s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            return key


# Density head bias of the random weights. With softplus(raw - 1) and no
# 1e10 last interval, +3 leaves a ray about 1e-3 of its light at the far
# plane (accumulated opacity 0.9986-0.9992 over 64 rays of an 800 and a
# 100 px frame, CPU), so nearly every ray is absorbed, as one that meets
# an object is; +1, nerf-icarus's bias, leaves 12-16% to the background.
SIGMA_BIAS = 3.0


@partial(jax.jit, static_argnums=(0,))
def _init(shapes: tuple, key):
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (r, c)) in zip(keys, shapes):
        bias = SIGMA_BIAS if name == "sigma" else 0.0
        out[name] = {"w": jax.random.normal(k, (r, c), jnp.float32)
                     * math.sqrt(2.0 / r),
                     "b": jnp.full((c,), bias, jnp.float32)}
    return out


def init_weights(arch: dict, key) -> dict:
    """{"coarse", "fine"} -> the one network {layer name: {"w", "b"}}
    (the same under both names: Mip-NeRF's levels share it), made on the
    device in one call: He-scaled normal weights and zero biases, except
    the density head's (``SIGMA_BIAS``)."""
    net = _init(tuple(sorted(layer_shapes(arch).items())), key)
    return {"coarse": net, "fine": net}


def served_weights(weights: dict, fmt: str) -> dict:
    """The weights as the configuration serves them (``float32`` only)."""
    if fmt != "float32":
        raise ValueError(f"mipnerf serves float32 weights, not {fmt!r}")
    return weights


# ------------------------------------------------------------- rays ------
def camera_rays(theta: float, phi: float, radius: float, hw: int,
                pixels: np.ndarray):
    """Origins (n, 3) and [unit direction, pixel radius] (n, 4), float32,
    of the given row-major pixel indices of an hw x hw frame seen from a
    camera on a sphere looking at the origin (OpenGL axes, focal length
    0.9 hw). Computed in float64."""
    th, ph = math.radians(theta), math.radians(phi)
    pos = np.array([radius * math.cos(ph) * math.sin(th),
                    radius * math.sin(ph),
                    radius * math.cos(ph) * math.cos(th)])
    fwd = -pos / np.linalg.norm(pos)
    right = np.cross(fwd, [0.0, 1.0, 0.0])
    right /= max(np.linalg.norm(right), 1e-8)
    up = np.cross(right, fwd)
    rot = np.stack([right, up, -fwd], axis=1)          # camera -> world
    j, i = np.divmod(np.asarray(pixels, np.int64), hw)
    focal = 0.9 * hw

    def unit(row):
        cam = np.stack([(i + 0.5 - hw / 2) / focal,
                        -(row + 0.5 - hw / 2) / focal,
                        -np.ones(len(i))], axis=-1)
        return cam / np.linalg.norm(cam, axis=-1, keepdims=True)

    u = unit(j)
    r = np.linalg.norm(u - unit(j + 1), axis=-1) * (2.0 / math.sqrt(12.0))
    d = u @ rot.T
    o = np.broadcast_to(pos, d.shape)
    return (o.astype(np.float32),
            np.concatenate([d, r[:, None]], -1).astype(np.float32))


# ----------------------------------------------------------- render ------
def _dot(a, b, precision: str):
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    p = {"highest": jax.lax.Precision.HIGHEST,
         "high": jax.lax.Precision.HIGH}[precision]
    return jnp.matmul(a, b, precision=p)


def pos_enc(x, n_freqs: int):
    """[x, sin(2^0 x), cos(2^0 x), ..., sin(2^(L-1) x), cos(2^(L-1) x)]."""
    out = [x]
    for k in range(n_freqs):
        out += [jnp.sin(2.0 ** k * x), jnp.cos(2.0 ** k * x)]
    return jnp.concatenate(out, axis=-1)


def integrated_pos_enc(mean, var, n_freqs: int):
    """[sin(y), cos(y)] * exp(-v / 2), y = [2^l mean]_l, v = [4^l var]_l."""
    y = jnp.concatenate([2.0 ** k * mean for k in range(n_freqs)], -1)
    v = jnp.concatenate([4.0 ** k * var for k in range(n_freqs)], -1)
    a = jnp.exp(-0.5 * v)
    return jnp.concatenate([jnp.sin(y) * a, jnp.cos(y) * a], -1)


def cast_cones(t, o, d, r):
    """Each interval [t_k, t_k+1) of every ray's cone as a Gaussian:
    (means (R, N, 3), diagonal covariances (R, N, 3))."""
    t0, t1 = t[:, :-1], t[:, 1:]
    mu, hw = (t0 + t1) / 2, (t1 - t0) / 2
    den = 3 * mu ** 2 + hw ** 2
    t_mean = mu + 2 * mu * hw ** 2 / den
    t_var = hw ** 2 / 3 - (4 / 15) * (hw ** 4 * (12 * mu ** 2 - hw ** 2)
                                      / den ** 2)
    r_var = r ** 2 * (mu ** 2 / 4 + (5 / 12) * hw ** 2
                      - (4 / 15) * hw ** 4 / den)
    dd = d ** 2
    null = 1 - dd / jnp.maximum(1e-10, jnp.sum(dd, -1, keepdims=True))
    mean = o[:, None, :] + t_mean[..., None] * d[:, None, :]
    cov = t_var[..., None] * dd[:, None, :] + r_var[..., None] * null[
        :, None, :]
    return mean, cov


def mlp(arch, net, x, dir_enc, precision):
    """Encoded samples (R, N, 6L) and per-ray direction encodings
    (R, de) -> raw rgb (R, N, 3), raw density (R, N)."""
    dot = partial(_dot, precision=precision)
    h = x
    for i in range(arch["trunk_layers"]):
        if i in arch["skip_at"]:
            h = jnp.concatenate([h, x], axis=-1)
        layer = net[f"trunk.l{i}"]
        h = jax.nn.relu(dot(h, layer["w"]) + layer["b"])
    density = (dot(h, net["sigma"]["w"]) + net["sigma"]["b"])[..., 0]
    bottleneck = dot(h, net["feat"]["w"]) + net["feat"]["b"]
    cond = jnp.broadcast_to(dir_enc[:, None, :],
                            bottleneck.shape[:-1] + dir_enc.shape[-1:])
    hc = jax.nn.relu(dot(jnp.concatenate([bottleneck, cond], axis=-1),
                         net["color0"]["w"]) + net["color0"]["b"])
    return dot(hc, net["rgb"]["w"]) + net["rgb"]["b"], density


def composite(rgb, density, t, d):
    """Mip-NeRF's ``volumetric_rendering`` over finite intervals:
    (colour on white (R, 3), weights (R, N))."""
    delta = (t[:, 1:] - t[:, :-1]) * jnp.linalg.norm(d, axis=-1,
                                                     keepdims=True)
    dd = density * delta
    trans = jnp.exp(-jnp.concatenate(
        [jnp.zeros_like(dd[:, :1]), jnp.cumsum(dd[:, :-1], axis=-1)], -1))
    w = (1 - jnp.exp(-dd)) * trans
    acc = jnp.sum(w, axis=-1, keepdims=True)
    return jnp.sum(w[..., None] * rgb, axis=1) + (1 - acc), w


def resample(t, w, padding: float):
    """Blur the weights, pad them, and draw len(t) new edges from the
    piecewise-constant PDF (``sorted_piecewise_constant_pdf``)."""
    w = jnp.concatenate([w[:, :1], w, w[:, -1:]], axis=-1)
    w = jnp.maximum(w[:, :-1], w[:, 1:])
    w = 0.5 * (w[:, :-1] + w[:, 1:]) + padding
    eps = 1e-5
    wsum = jnp.sum(w, axis=-1, keepdims=True)
    pad = jnp.maximum(0.0, eps - wsum)
    w = w + pad / w.shape[-1]
    pdf = w / (wsum + pad)
    cdf = jnp.minimum(1.0, jnp.cumsum(pdf[:, :-1], axis=-1))
    cdf = jnp.concatenate([jnp.zeros_like(cdf[:, :1]), cdf,
                           jnp.ones_like(cdf[:, :1])], axis=-1)
    u = jnp.linspace(0.0, 1.0 - float(np.finfo(np.float32).eps),
                     t.shape[-1])
    mask = u[None, None, :] >= cdf[:, :, None]

    def interval(x):
        x0 = jnp.max(jnp.where(mask, x[:, :, None], x[:, :1, None]), -2)
        x1 = jnp.min(jnp.where(~mask, x[:, :, None], x[:, -1:, None]), -2)
        return x0, x1

    t0, t1 = interval(t)
    c0, c1 = interval(cdf)
    f = jnp.clip(jnp.nan_to_num((u - c0) / (c1 - c0), nan=0.0), 0.0, 1.0)
    return t0 + f * (t1 - t0)


def render_rays(arch: dict, weights: dict, rays_o, rays_d4,
                precision: str = "highest"):
    """(R, 3) origins and (R, 4) [direction, radius] -> (R, 3) colours of
    the fine level."""
    d, r = rays_d4[:, :3], rays_d4[:, 3:]
    n = arch["n_coarse"]
    s = jnp.linspace(0.0, 1.0, n + 1)
    t = jnp.broadcast_to(arch["near"] * (1.0 - s) + arch["far"] * s,
                         (rays_o.shape[0], n + 1))
    dir_enc = pos_enc(d / jnp.linalg.norm(d, axis=-1, keepdims=True),
                      arch["dir_freqs"])
    pad = arch["rgb_padding"]
    for level in ("coarse", "fine"):
        if level == "fine":
            t = resample(t, w, arch["resample_padding"])
        mean, cov = cast_cones(t, rays_o, d, r)
        raw_rgb, raw_density = mlp(
            arch, weights[level],
            integrated_pos_enc(mean, cov, arch["pos_freqs"]), dir_enc,
            precision)
        rgb = jax.nn.sigmoid(raw_rgb) * (1 + 2 * pad) - pad
        density = jax.nn.softplus(raw_density + arch["density_bias"])
        colour, w = composite(rgb, density, t, d)
    return colour


@partial(jax.jit, static_argnums=(0, 4))
def _render_block(arch_items, weights, rays_o, rays_d4, precision):
    arch = {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in arch_items}
    return render_rays(arch, weights, rays_o, rays_d4, precision)


def render(arch: dict, weights: dict, rays_o: np.ndarray,
           rays_d4: np.ndarray, precision: str = "highest",
           block: int = 1024) -> np.ndarray:
    """Render (R, 3) rays with their (R, 4) [direction, radius] one block
    of ``block`` rays at a time (one compiled shape; a block's
    activations bound the memory); returns (R, 3) float32 on the host."""
    R = rays_o.shape[0]
    pad = (-R) % block
    o = np.concatenate([rays_o, np.repeat(rays_o[-1:], pad, 0)])
    d = np.concatenate([rays_d4, np.repeat(rays_d4[-1:], pad, 0)])
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in arch.items()))
    out = [_render_block(items, weights, o[i:i + block], d[i:i + block],
                         precision) for i in range(0, len(o), block)]
    return np.concatenate([np.asarray(x) for x in out])[:R]
