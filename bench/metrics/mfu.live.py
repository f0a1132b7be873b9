"""Whole render: nominal FLOP of the delivered requests over the bf16
peak for the sum of their service times (first ray tiled to last pixel
scattered)."""
from bench import flops


def read(run):
    done = [r for r in run.window.records if r.due is not None and r.delivered]
    service = sum(r.complete - r.service_start for r in done)
    if not done or not run.peak or service <= 0:
        return None
    work = sum(r.spec.hw ** 2 for r in done) * flops.flops_per_ray(run.arch)
    return 100.0 * work / (run.peak["bf16_flops_per_s"] * service)
