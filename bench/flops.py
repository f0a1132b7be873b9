"""Nominal work of a render, from the configuration's shapes alone.

The nominal work is fixed by the configuration, whatever implements the
render: two FLOP per weight and bias of a network for every sample it
evaluates (the coarse network at ``n_coarse`` samples, the fine one at
``n_coarse + n_fine``). Padding rays, skipped samples or a cheaper
colour branch do not change it, so a share of a peak built on it can
only rise by doing the same render in less time. The sampling and
compositing arithmetic is left out: it is under 1% of the MLP's.

At nerf-icarus's published widths this is 1,191,688 FLOP per sample and
network, 305,072,128 FLOP per ray (``benchmarks/table1_energy.py``
counts the same from the program's parameter declarations).
"""
from __future__ import annotations

WEIGHT_BYTES = {"float32": 4.0, "rmcm9": 9.0 / 8.0}


def net_params(arch: dict) -> tuple:
    """(weights, biases) of one network."""
    W, C = arch["trunk_width"], arch["color_width"]
    pe = 3 + 6 * arch["pos_freqs"]
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
    """FLOP of one network evaluated at one sample."""
    return 2 * sum(net_params(arch))


def samples_per_ray(arch: dict) -> int:
    """Network evaluations per ray: the coarse pass, then the fine pass
    over the coarse and importance samples together."""
    return 2 * arch["n_coarse"] + arch["n_fine"]


def flops_per_ray(arch: dict) -> int:
    return flops_per_sample(arch) * samples_per_ray(arch)


def kernel_bytes(arch: dict, weight_format: str, rays: int) -> float:
    """HBM bytes one call of the fused two-pass kernel must move for
    ``rays`` rays: both networks' weights once (hidden layers in the
    served format, heads and biases in float32), origin and direction in,
    a 9-float record out per ray."""
    W, C = arch["trunk_width"], arch["color_width"]
    weights, biases = net_params(arch)
    heads = W * 1 + C * 3
    per_net = ((weights - heads) * WEIGHT_BYTES[weight_format]
               + (heads + biases) * 4.0)
    return 2 * per_net + rays * (6 + 9) * 4.0
