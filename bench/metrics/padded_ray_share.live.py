"""Scheduler: share of the rays dispatched that were padding."""


def read(run):
    real, pad = run.stats["rays_rendered"], run.stats["padded_rays"]
    return 100.0 * pad / (real + pad) if real + pad else None
