"""Pure-jnp oracles for the Pallas kernels.

``fused_render_ref`` composes the already-tested core modules (PEU ->
MLP engine -> VRU streaming recurrence) — the kernel must match it
elementwise. ``mipnerf_render_ref`` is a plain Mip-NeRF forward pass for
the cone configs, written from ``google/mipnerf`` without the render
paths' helpers. ``rmcm_matmul_ref`` unpacks the 9-bit storage format and does
the dense matmul in fp32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.nerf_icarus import NerfConfig
from repro.core import rmcm, volume
from repro.core.encoding import nerf_encoding
from repro.core.mlp import nerf_mlp_apply


def fused_render_ref(cfg: NerfConfig, params: dict, rays_o, rays_d, t,
                     deltas, quant: Optional[dict] = None):
    """(rays_o/rays_d (R,3), t/deltas (R,N)) -> (rgb (R,3), aux).

    Exactly the math the fused PLCore kernel implements: encode positions
    (and directions) from the ray parametrization, run the NeRF MLP on
    every sample, volume-render with the eq.(5) recurrence.
    """
    pts = rays_o[..., None, :] + t[..., None] * rays_d[..., None, :]
    pe_pos = nerf_encoding(pts, cfg.pos_freqs)
    dirs = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    pe_dir = nerf_encoding(dirs, cfg.dir_freqs)[..., None, :]   # (R,1,de)
    sigma, rgb = nerf_mlp_apply(cfg, params, pe_pos, pe_dir, quant=quant)
    out, aux = volume.render_scan(sigma, rgb, deltas)
    return out, {"weights": aux["weights"], "acc": aux["acc"]}


def rmcm_matmul_ref(x, packed: dict):
    """y = x @ dequantize(unpack(packed)), fp32 accumulate."""
    q = rmcm.unpack(packed)
    w = rmcm.dequantize(q, jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


# ------------------------------------------------------------- Mip-NeRF ----
def _mip_encode(x, var, n_freqs: int):
    """Mip-NeRF's ``integrated_pos_enc`` (``var`` None: ``pos_enc``
    without identity): y = [2^l x]_l, [sin y, cos y] * exp(-v / 2)."""
    y = jnp.concatenate([(2.0 ** l) * x for l in range(n_freqs)], -1)
    enc = jnp.concatenate([jnp.sin(y), jnp.cos(y)], -1)
    if var is None:
        return enc
    v = jnp.concatenate([(4.0 ** l) * var for l in range(n_freqs)], -1)
    return enc * jnp.exp(-0.5 * jnp.concatenate([v, v], -1))


def _mip_pdf_edges(bins, weights, n: int):
    """Mip-NeRF's deterministic ``sorted_piecewise_constant_pdf``: n
    samples of the piecewise-constant PDF ``weights`` over ``bins``."""
    pdf = weights / jnp.sum(weights, -1, keepdims=True)
    cdf = jnp.minimum(1.0, jnp.cumsum(pdf[..., :-1], -1))
    cdf = jnp.concatenate([jnp.zeros_like(cdf[..., :1]), cdf,
                           jnp.ones_like(cdf[..., :1])], -1)
    u = jnp.linspace(0.0, 1.0 - float(np.finfo(np.float32).eps), n)
    mask = u[None, None, :] >= cdf[..., :, None]

    def interval(x):
        x0 = jnp.max(jnp.where(mask, x[..., None], x[..., :1, None]), -2)
        x1 = jnp.min(jnp.where(~mask, x[..., None], x[..., -1:, None]), -2)
        return x0, x1

    b0, b1 = interval(bins)
    c0, c1 = interval(cdf)
    t = jnp.clip(jnp.nan_to_num((u - c0) / (c1 - c0), nan=0.0), 0.0, 1.0)
    return b0 + t * (b1 - b0)


def mipnerf_render_ref(cfg: NerfConfig, params: dict, rays_o, rays_d,
                       radii) -> dict:
    """Plain f32 Mip-NeRF forward pass (``google/mipnerf``
    ``internal/models.py``, ``mip.py``), independent of the render
    paths' own helpers, at HIGHEST matmul precision. rays (R, 3) with
    ``radii`` (R, 1) -> {rgb, rgb_coarse, acc} on white; both levels
    read the one network under ``params["coarse"]``. Written with
    the system's conventions: unit directions with t as distance, the
    network's [h | encoding] concatenations, and the direction encoding
    in ``nerf_encoding``'s order."""
    with jax.default_matmul_precision("highest"):
        n = cfg.n_coarse
        s = jnp.linspace(0.0, 1.0, n + 1)
        t = jnp.broadcast_to(cfg.near * (1.0 - s) + cfg.far * s,
                             rays_o.shape[:-1] + (n + 1,))
        viewdirs = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
        dir_enc = nerf_encoding(viewdirs, cfg.dir_freqs)
        net = params["coarse"]
        out = []
        for level in ("coarse", "fine"):
            if level == "fine":
                w = jnp.concatenate([w[..., :1], w, w[..., -1:]], -1)
                w = jnp.maximum(w[..., :-1], w[..., 1:])
                w = 0.5 * (w[..., :-1] + w[..., 1:]) + cfg.resample_padding
                t = _mip_pdf_edges(t, w, n + 1)
            t0, t1 = t[..., :-1], t[..., 1:]
            mu, hw = (t0 + t1) / 2, (t1 - t0) / 2
            den = 3 * mu ** 2 + hw ** 2
            t_mean = mu + 2 * mu * hw ** 2 / den
            t_var = hw ** 2 / 3 - (4 / 15) * (hw ** 4 * (12 * mu ** 2
                                                         - hw ** 2)) / den ** 2
            r_var = radii ** 2 * (mu ** 2 / 4 + (5 / 12) * hw ** 2
                                  - (4 / 15) * hw ** 4 / den)
            dd = rays_d ** 2
            null = 1 - dd / jnp.sum(dd, -1, keepdims=True)
            mean = rays_o[..., None, :] + t_mean[..., None] * rays_d[
                ..., None, :]
            cov = (t_var[..., None] * dd[..., None, :]
                   + r_var[..., None] * null[..., None, :])
            x = _mip_encode(mean, cov, cfg.pos_freqs)
            h = x
            for i in range(cfg.trunk_layers):
                if i in cfg.skip_at:
                    h = jnp.concatenate([h, x], -1)
                h = jax.nn.relu(h @ net["trunk"][f"l{i}"]["w"]
                                + net["trunk"][f"l{i}"]["b"])
            raw_density = (h @ net["sigma"]["w"] + net["sigma"]["b"])[..., 0]
            bottleneck = h @ net["feat"]["w"] + net["feat"]["b"]
            cond = jnp.broadcast_to(dir_enc[..., None, :],
                                    bottleneck.shape[:-1]
                                    + dir_enc.shape[-1:])
            hc = jax.nn.relu(jnp.concatenate([bottleneck, cond], -1)
                             @ net["color0"]["w"] + net["color0"]["b"])
            rgb = jax.nn.sigmoid(hc @ net["rgb"]["w"] + net["rgb"]["b"])
            rgb = rgb * (1 + 2 * cfg.rgb_padding) - cfg.rgb_padding
            density = jax.nn.softplus(raw_density + cfg.density_bias)
            delta = (t1 - t0) * jnp.linalg.norm(rays_d, axis=-1,
                                                keepdims=True)
            dd_ = density * delta
            trans = jnp.exp(-jnp.concatenate(
                [jnp.zeros_like(dd_[..., :1]),
                 jnp.cumsum(dd_[..., :-1], -1)], -1))
            w = (1 - jnp.exp(-dd_)) * trans
            acc = jnp.sum(w, -1)
            comp = jnp.sum(w[..., None] * rgb, -2) + (1 - acc[..., None])
            out.append((comp, acc))
        return {"rgb": out[1][0], "rgb_coarse": out[0][0],
                "acc": out[1][1]}
