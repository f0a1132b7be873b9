"""Ray/data pipeline for the NeRF side.

No dataset downloads in this environment, so scenes are *procedural
analytic volumes* (Gaussian emission blobs + a solid sphere) rendered to
ground-truth images by dense ray-marching the analytic density/color fields
through the same VRU math the model uses. This gives a real train/eval
loop: NeRF fits the analytic plenoptic function and PSNR numbers are
meaningful (benchmarks/fig8_rmcm_psnr.py relies on it).

Conventions: OpenGL-style camera (looks down -z), c2w 4x4 pose matrices,
rays returned unnormalized-origin + unit directions.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sampling, volume


# ------------------------------------------------------------- cameras ------
def pose_spherical(theta_deg: float, phi_deg: float, radius: float) -> jnp.ndarray:
    """c2w for a camera on a sphere looking at the origin."""
    th, ph = math.radians(theta_deg), math.radians(phi_deg)
    cam_pos = np.array([radius * math.cos(ph) * math.sin(th),
                        radius * math.sin(ph),
                        radius * math.cos(ph) * math.cos(th)], np.float32)
    fwd = -cam_pos / np.linalg.norm(cam_pos)               # look at origin
    up = np.array([0.0, 1.0, 0.0], np.float32)
    right = np.cross(fwd, up)
    right /= max(np.linalg.norm(right), 1e-8)
    true_up = np.cross(right, fwd)
    c2w = np.eye(4, dtype=np.float32)
    c2w[:3, 0], c2w[:3, 1], c2w[:3, 2], c2w[:3, 3] = right, true_up, -fwd, cam_pos
    return jnp.asarray(c2w)


def camera_rays(c2w, H: int, W: int, focal: float):
    """Pixel-center rays. Returns (rays_o (H,W,3), rays_d (H,W,3) unit)."""
    i, j = jnp.meshgrid(jnp.arange(W, dtype=jnp.float32) + 0.5,
                        jnp.arange(H, dtype=jnp.float32) + 0.5, indexing="xy")
    dirs = jnp.stack([(i - W / 2) / focal, -(j - H / 2) / focal,
                      -jnp.ones_like(i)], axis=-1)
    rays_d = dirs @ c2w[:3, :3].T
    rays_d = rays_d / jnp.linalg.norm(rays_d, axis=-1, keepdims=True)
    rays_o = jnp.broadcast_to(c2w[:3, 3], rays_d.shape)
    return rays_o, rays_d


@functools.lru_cache(maxsize=16)
def pixel_radii(H: int, W: int, focal: float) -> np.ndarray:
    """Mip-NeRF's per-pixel cone radius (``generate_rays``) for the
    unit directions of ``camera_rays``: the distance from each pixel's
    direction to that of the pixel one row below (one past the image for
    the last row), times 2 / sqrt(12), halfway between the cones
    inscribed in and circumscribed about the pixel. A rotation keeps the
    distance, so it is computed in camera space, in float64, and depends
    on the frame's size alone: one read-only array per (H, W, focal),
    kept for the next frame of that size. Returns (H * W,) float32 in
    row-major pixel order: the cone's radius per unit of distance along
    the ray."""
    x = ((np.arange(W) + 0.5 - W / 2) / focal)[None, :]
    y = (-(np.arange(H) + 0.5 - H / 2) / focal)[:, None]
    y_next = y - 1.0 / focal
    n0 = np.sqrt(x * x + y * y + 1.0)
    n1 = np.sqrt(x * x + y_next * y_next + 1.0)
    dx = np.sqrt((x / n0 - x / n1) ** 2 + (y / n0 - y_next / n1) ** 2
                 + (1.0 / n0 - 1.0 / n1) ** 2)
    r = (dx * (2.0 / math.sqrt(12.0))).astype(np.float32).reshape(-1)
    r.flags.writeable = False
    return r


# ------------------------------------------------------ analytic scenes -----
@dataclass(frozen=True)
class Scene:
    name: str
    density: Callable  # pts (..., 3) -> sigma (...,)
    color: Callable    # (pts (..., 3), dirs (..., 3)) -> rgb (..., 3)
    near: float = 2.0
    far: float = 6.0
    radius: float = 4.0


def blob_scene(n_blobs: int = 5, seed: int = 0, view_dep: float = 0.15) -> Scene:
    """Gaussian emission blobs with mildly view-dependent colors."""
    rng = np.random.RandomState(seed)
    centers = jnp.asarray(rng.uniform(-0.7, 0.7, (n_blobs, 3)), jnp.float32)
    colors = jnp.asarray(rng.uniform(0.2, 1.0, (n_blobs, 3)), jnp.float32)
    scales = jnp.asarray(rng.uniform(0.12, 0.3, (n_blobs,)), jnp.float32)
    amps = jnp.asarray(rng.uniform(8.0, 20.0, (n_blobs,)), jnp.float32)

    def density(pts):
        d2 = jnp.sum((pts[..., None, :] - centers) ** 2, axis=-1)
        return jnp.sum(amps * jnp.exp(-0.5 * d2 / scales ** 2), axis=-1)

    def color(pts, dirs):
        d2 = jnp.sum((pts[..., None, :] - centers) ** 2, axis=-1)
        w = amps * jnp.exp(-0.5 * d2 / scales ** 2) + 1e-8
        base = (w[..., None] * colors).sum(-2) / w.sum(-1, keepdims=True)
        # simple view-dependence: tint by direction (keeps GT in [0,1])
        tint = 0.5 * (dirs + 1.0)
        return jnp.clip(base * (1 - view_dep) + tint * view_dep, 0.0, 1.0)

    return Scene("blobs", density, color)


def sphere_scene(radius: float = 0.6, sharp: float = 40.0) -> Scene:
    """Solid matte sphere (hard surface — stresses importance sampling)."""
    def density(pts):
        r = jnp.linalg.norm(pts, axis=-1)
        return 50.0 * jax.nn.sigmoid(sharp * (radius - r))

    def color(pts, dirs):
        n = pts / jnp.maximum(jnp.linalg.norm(pts, axis=-1, keepdims=True), 1e-8)
        lam = jnp.clip((n * jnp.asarray([0.57, 0.57, 0.57])).sum(-1), 0, 1)
        base = jnp.asarray([0.8, 0.3, 0.2])
        return jnp.clip(base * (0.3 + 0.7 * lam[..., None]), 0.0, 1.0)

    return Scene("sphere", density, color, near=2.5, far=5.5)


SCENES = {"blobs": blob_scene, "sphere": sphere_scene}


# ------------------------------------------------------- GT ray-marching ----
def render_gt(scene: Scene, rays_o, rays_d, n_samples: int = 256,
              white_bkgd: bool = True):
    """Dense-march the analytic fields: the ground-truth 'photograph'."""
    t = sampling.stratified(scene.near, scene.far, n_samples,
                            rays_o.shape[:-1])
    pts = rays_o[..., None, :] + t[..., None] * rays_d[..., None, :]
    sig = scene.density(pts)
    dirs = jnp.broadcast_to(rays_d[..., None, :], pts.shape)
    rgb = scene.color(pts, dirs)
    out, aux = volume.render_parallel(sig, rgb, sampling.deltas_from_t(t))
    if white_bkgd:
        out = volume.white_background(out, aux["acc"])
    return out


def make_dataset(scene: Scene, n_views: int, H: int, W: int,
                 focal: float | None = None, chunk: int = 8192):
    """Render n_views GT images; flatten to a ray dataset.

    Returns dict of arrays {rays_o, rays_d, rgb} with leading dim
    n_views*H*W.
    """
    focal = focal or 0.9 * W
    render = jax.jit(lambda o, d: render_gt(scene, o, d))
    oL, dL, cL = [], [], []
    for v in range(n_views):
        theta = 360.0 * v / n_views
        phi = -25.0 + 15.0 * math.sin(2 * math.pi * v / n_views)
        c2w = pose_spherical(theta, phi, scene.radius)
        ro, rd = camera_rays(c2w, H, W, focal)
        ro, rd = ro.reshape(-1, 3), rd.reshape(-1, 3)
        rgb = jnp.concatenate([render(ro[i:i + chunk], rd[i:i + chunk])
                               for i in range(0, ro.shape[0], chunk)])
        oL.append(ro), dL.append(rd), cL.append(rgb)
    return {"rays_o": jnp.concatenate(oL), "rays_d": jnp.concatenate(dL),
            "rgb": jnp.concatenate(cL)}


def ray_batches(dataset: dict, batch_size: int, key) -> Iterator[dict]:
    """Infinite shuffled ray batches (host-side sampler)."""
    n = dataset["rays_o"].shape[0]
    while True:
        key, sub = jax.random.split(key)
        idx = jax.random.randint(sub, (batch_size,), 0, n)
        yield {k: v[idx] for k, v in dataset.items()}


def holdout_view(scene: Scene, H: int, W: int, focal: float | None = None,
                 theta: float = 33.0, phi: float = -20.0):
    """A view NOT in the training trajectory, for eval PSNR."""
    focal = focal or 0.9 * W
    c2w = pose_spherical(theta, phi, scene.radius)
    ro, rd = camera_rays(c2w, H, W, focal)
    gt = render_gt(scene, ro.reshape(-1, 3), rd.reshape(-1, 3)).reshape(H, W, 3)
    return ro, rd, gt
