"""Scheduler: median wait from a request's due time until the
scheduler handed its first ray to a tile (``service_start_s``)."""
import numpy as np


def read(run):
    waits = [r.service_start - r.due for r in run.window.records
             if r.due is not None and r.delivered]
    return 1e3 * float(np.median(waits)) if waits else None
