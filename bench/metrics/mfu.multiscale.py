"""Whole render (Mip-NeRF): nominal FLOP of the rays dispatched in the
window over the chips' bf16 peak for the window."""
from bench import mip_flops


def read(run):
    rays = run.stats["rays_rendered"]
    if not rays or not run.peak:
        return None
    work = rays * mip_flops.flops_per_ray(run.arch)
    return 100.0 * work / (run.chips * run.peak["bf16_flops_per_s"]
                           * run.window.seconds)
