"""Plain reference of the NeRF renderer served by nerf-icarus.

Written from the published description (Mildenhall et al., "NeRF",
arXiv:2003.08934, sections 4-5 and appendix A) in straightforward
``jax.numpy``, with no kernel, cache or batching of its own. It imports
nothing of the system under test. Departures from the paper, all of
them conventions of the system that the reference has to share to
compare pixels:

- the skip layer and the colour branch concatenate ``[h, encoding]`` in
  that order (the paper draws the encoding first); the order only names
  which weight rows meet which inputs;
- deterministic ("inference") sampling: coarse samples sit at the bin
  midpoints of [near, far]; the fine samples invert the coarse weights'
  CDF at evenly spaced points of [0, 1 - 1e-6], with the bins edged at
  the coarse samples themselves (the paper edges them at the midpoints
  between coarse samples);
- the density is ``relu(sigma)`` inside the volume integral, the last
  interval is 1e10 long, and the colour is composited onto white.

RMCM weights (ICARUS, arXiv:2203.01414, section 4.3) are the f32 weights
of the hidden layers rounded to 9-bit signed magnitude with one absmax
scale per output column, each 4-bit nibble of the magnitude snapped to
a value in {o << s : o in 1,3,5,7} (9, 11, 13, 15 snap down). The
density and RGB heads stay exact.

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

HIDDEN = ("trunk", "feat", "color0")    # the layers RMCM quantizes


# ----------------------------------------------------------- shapes ------
def layer_shapes(arch: dict) -> dict:
    """{net layer name: (rows, cols)} of one network."""
    W = arch["trunk_width"]
    pe = 3 + 6 * arch["pos_freqs"]
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


SIGMA_BIAS = 1.0      # density head bias: a medium that absorbs every ray


@partial(jax.jit, static_argnums=(0,))
def _init(shapes: tuple, key):
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (r, c)) in zip(keys, shapes):
        bias = SIGMA_BIAS if name.endswith("/sigma") else 0.0
        out[name] = {"w": jax.random.normal(k, (r, c), jnp.float32)
                     * math.sqrt(2.0 / r),
                     "b": jnp.full((c,), bias, jnp.float32)}
    return out


def init_weights(arch: dict, key) -> dict:
    """{"coarse", "fine"} -> {layer name: {"w", "b"}}, made on the device
    in one call: He-scaled normal weights (variance 2 / fan-in, which
    keeps the activations of a ReLU stack of this depth at a trained
    network's scale) and zero biases, except the density head's, +1. The
    scene is then a dense random medium that absorbs every ray well
    before the far plane, as a ray that meets an object does. (At
    variance 1 / fan-in with no bias the trunk's activations shrink
    sixteenfold, the scene is a faint fog that renders some seeds nearly
    white, and a pixel jumps by half its value wherever the density at
    its last sample, whose interval is 1e10 long, changes sign on
    rounding.)"""
    shapes = tuple(sorted(layer_shapes(arch).items()))
    both = tuple((f"{net}/{n}", s) for net in ("coarse", "fine")
                 for n, s in shapes)
    flat = _init(both, key)
    out = {"coarse": {}, "fine": {}}
    for name, v in flat.items():
        net, layer = name.split("/")
        out[net][layer] = v
    return out


_NIBBLE = np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 8, 10, 10, 12, 12, 14, 14],
                   np.int32)


def rmcm_dequantized(w):
    """f32 (K, N) -> the f32 values its 9-bit RMCM form stands for."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 255.0,
                        1e-20)
    m = jnp.clip(jnp.round(jnp.abs(w) / scale), 0, 255).astype(jnp.int32)
    table = jnp.asarray(_NIBBLE)
    m = (table[(m >> 4) & 0xF] << 4) | table[m & 0xF]
    return jnp.where(w < 0, -1.0, 1.0) * m.astype(jnp.float32) * scale


def served_weights(weights: dict, fmt: str) -> dict:
    """The weights as the configuration serves them (``float32`` or
    ``rmcm9``)."""
    if fmt == "float32":
        return weights
    if fmt != "rmcm9":
        raise ValueError(f"unknown weight format {fmt!r}")
    return {net: {name: ({"w": rmcm_dequantized(v["w"]), "b": v["b"]}
                         if name.split(".")[0] in HIDDEN else v)
                  for name, v in layers.items()}
            for net, layers in weights.items()}


# ------------------------------------------------------------- rays ------
def camera_rays(theta: float, phi: float, radius: float, hw: int,
                pixels: np.ndarray):
    """Origins and unit directions (float32) of the given row-major pixel
    indices of an hw x hw frame seen from a camera on a sphere looking at
    the origin (OpenGL axes, focal length 0.9 hw). Computed in float64."""
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
    cam = np.stack([(i + 0.5 - hw / 2) / focal, -(j + 0.5 - hw / 2) / focal,
                    -np.ones(len(i))], axis=-1)
    d = cam @ rot.T
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    o = np.broadcast_to(pos, d.shape)
    return o.astype(np.float32), d.astype(np.float32)


# ----------------------------------------------------------- render ------
def _dot(a, b, precision: str):
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    p = {"highest": jax.lax.Precision.HIGHEST,
         "high": jax.lax.Precision.HIGH}[precision]
    return jnp.matmul(a, b, precision=p)


def encode(x, n_freqs: int):
    """[x, sin(2^0 x), cos(2^0 x), ..., sin(2^(L-1) x), cos(2^(L-1) x)]."""
    out = [x]
    for k in range(n_freqs):
        out += [jnp.sin(2.0 ** k * x), jnp.cos(2.0 ** k * x)]
    return jnp.concatenate(out, axis=-1)


def mlp(arch, net, pts, dirs, precision):
    """pts (R, N, 3), unit dirs (R, 3) -> sigma (R, N), rgb (R, N, 3)."""
    dot = partial(_dot, precision=precision)
    pe = encode(pts, arch["pos_freqs"])
    h = pe
    for i in range(arch["trunk_layers"]):
        if i in arch["skip_at"]:
            h = jnp.concatenate([h, pe], axis=-1)
        layer = net[f"trunk.l{i}"]
        h = jax.nn.relu(dot(h, layer["w"]) + layer["b"])
    sigma = (dot(h, net["sigma"]["w"]) + net["sigma"]["b"])[..., 0]
    feat = dot(h, net["feat"]["w"]) + net["feat"]["b"]
    de = encode(dirs, arch["dir_freqs"])[:, None, :]
    de = jnp.broadcast_to(de, feat.shape[:-1] + de.shape[-1:])
    hc = jax.nn.relu(dot(jnp.concatenate([feat, de], axis=-1),
                         net["color0"]["w"]) + net["color0"]["b"])
    rgb = jax.nn.sigmoid(dot(hc, net["rgb"]["w"]) + net["rgb"]["b"])
    return sigma, rgb


def composite(sigma, rgb, t):
    """Volume rendering integral: (colour (R, 3), weights (R, N))."""
    delta = jnp.concatenate(
        [t[:, 1:] - t[:, :-1], jnp.full_like(t[:, :1], 1e10)], axis=-1)
    x = -jax.nn.relu(sigma) * delta
    trans = jnp.concatenate([jnp.ones_like(x[:, :1]),
                             jnp.exp(jnp.cumsum(x, axis=-1)[:, :-1])],
                            axis=-1)
    w = trans * (1.0 - jnp.exp(x))
    return jnp.sum(w[..., None] * rgb, axis=1), w


def resample(t_c, w, n: int):
    """Deterministic inverse-CDF sampling of n points from the coarse
    weights over the bins [t_c[k], t_c[k+1]]."""
    pdf = w[:, 1:-1] + 1e-5
    pdf = pdf / jnp.sum(pdf, axis=-1, keepdims=True)
    cdf = jnp.concatenate([jnp.zeros_like(pdf[:, :1]),
                           jnp.cumsum(pdf, axis=-1)], axis=-1)
    u = jnp.arange(n, dtype=jnp.float32) * ((1.0 - 1e-6) / max(n - 1, 1))
    idx = jax.vmap(lambda c: jnp.searchsorted(c, u, side="right"))(cdf)
    idx = jnp.clip(idx - 1, 0, cdf.shape[-1] - 2)
    c_lo = jnp.take_along_axis(cdf, idx, axis=-1)
    c_hi = jnp.take_along_axis(cdf, idx + 1, axis=-1)
    t_lo = jnp.take_along_axis(t_c, idx, axis=-1)
    t_hi = jnp.take_along_axis(t_c, idx + 1, axis=-1)
    span = jnp.where(c_hi - c_lo < 1e-8, 1.0, c_hi - c_lo)
    return t_lo + (u - c_lo) / span * (t_hi - t_lo)


def render_rays(arch: dict, weights: dict, rays_o, rays_d,
                precision: str = "highest"):
    """(R, 3) rays -> (R, 3) pixel colours, coarse then fine."""
    nc, nf = arch["n_coarse"], arch["n_fine"]
    near, far = arch["near"], arch["far"]
    R = rays_o.shape[0]
    dirs = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    t_c = near + (far - near) * (jnp.arange(nc, dtype=jnp.float32) + 0.5) / nc
    t_c = jnp.broadcast_to(t_c, (R, nc))

    def points(t):
        return rays_o[:, None, :] + t[..., None] * rays_d[:, None, :]

    sigma, rgb = mlp(arch, weights["coarse"], points(t_c), dirs, precision)
    _, w_c = composite(sigma, rgb, t_c)
    t_f = resample(t_c, w_c, nf)
    t_all = jnp.sort(jnp.concatenate([t_c, t_f], axis=-1), axis=-1)
    sigma, rgb = mlp(arch, weights["fine"], points(t_all), dirs, precision)
    colour, w = composite(sigma, rgb, t_all)
    return colour + (1.0 - jnp.sum(w, axis=-1, keepdims=True))


@partial(jax.jit, static_argnums=(0, 4))
def _render_block(arch_items, weights, rays_o, rays_d, precision):
    arch = {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in arch_items}
    return render_rays(arch, weights, rays_o, rays_d, precision)


def render(arch: dict, weights: dict, rays_o: np.ndarray,
           rays_d: np.ndarray, precision: str = "highest",
           block: int = 1024) -> np.ndarray:
    """Render (R, 3) rays one block of ``block`` rays at a time (one
    compiled shape; a block's activations bound the memory); returns
    (R, 3) float32 on the host."""
    R = rays_o.shape[0]
    pad = (-R) % block
    o = np.concatenate([rays_o, np.repeat(rays_o[-1:], pad, 0)])
    d = np.concatenate([rays_d, np.repeat(rays_d[-1:], pad, 0)])
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in arch.items()))
    out = [_render_block(items, weights, o[i:i + block], d[i:i + block],
                         precision) for i in range(0, len(o), block)]
    return np.concatenate([np.asarray(x) for x in out])[:R]
