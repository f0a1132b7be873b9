"""Nominal work of a Mip-NeRF render (the ``mipnerf`` reference's
configurations), from the configuration's shapes alone.

As ``bench/flops.py`` counts NeRF: two FLOP per weight and bias of the
network for every sample it evaluates. Mip-NeRF evaluates its one
network at ``n_coarse`` intervals and again at ``n_fine`` resampled ones
(no merge); its trunk reads the 6 L-wide integrated positional encoding
(no identity) at the first layer and beside h at the skip layer. The
frustum Gaussians, the encoding, the resample and the compositing are
left out: under 2% of the MLP's.

At mipnerf-blender's published widths: 1,225,480 FLOP per sample,
313,722,880 per ray.
"""
from __future__ import annotations


def net_params(arch: dict) -> tuple:
    """(weights, biases) of the one network."""
    W, C = arch["trunk_width"], arch["color_width"]
    pe = 6 * arch["pos_freqs"]
    de = 3 + 6 * arch["dir_freqs"]
    weights = biases = 0
    din = pe
    for i in range(arch["trunk_layers"]):
        if i in arch["skip_at"]:
            din = W + pe
        weights += din * W
        biases += W
        din = W
    for rows, cols in ((W, 1), (W, W), (W + de, C), (C, 3)):
        weights += rows * cols
        biases += cols
    return weights, biases


def flops_per_sample(arch: dict) -> int:
    return 2 * sum(net_params(arch))


def samples_per_ray(arch: dict) -> int:
    return arch["n_coarse"] + arch["n_fine"]


def flops_per_ray(arch: dict) -> int:
    return flops_per_sample(arch) * samples_per_ray(arch)


def flops_per_call(arch: dict, rays: int) -> int:
    return flops_per_ray(arch) * rays


def kernel_bytes(arch: dict, rays: int) -> float:
    """HBM bytes one call of the cone kernel must move for ``rays`` rays:
    the one network's float32 weights and biases once; origin,
    direction and pixel radius in and a 9-float record out per ray."""
    return 4.0 * sum(net_params(arch)) + rays * (3 + 3 + 1 + 9) * 4.0
