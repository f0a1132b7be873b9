"""Readings shared by metrics that differ only in the cells they serve
(``<metric>.batch`` moves ``rays_per_s``, ``<metric>.live`` moves
``latency_p95_ms``). Each returns ``None`` where the run has nothing to
read, and never 0 in place of a share it could not measure."""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import flops, roofline

HOST_TILE_SPANS = ("tile.coalesce", "tile.scatter")


def host_ms_per_tile(run) -> Optional[float]:
    """Host time of the tile path (coalesce + scatter engine spans), mean
    per tile over the window."""
    tiles = sum(1 for n, _, _ in run.spans if n == "tile.coalesce")
    if not tiles:
        return None
    busy = sum(t1 - t0 for n, t0, t1 in run.spans if n in HOST_TILE_SPANS)
    return 1e3 * busy / tiles


def kernel_roofline(run) -> Optional[float]:
    """The fused kernel's share of its roofline: the nominal FLOP and
    bytes of the rays its calls processed (every call renders one tile
    of ``tile_rays`` rays, padding included) over the device time of its
    calls, all chips together."""
    t = run.trace
    if t is None or not run.peak or not sum(t.kernel_calls.values()):
        return None
    calls = sum(t.kernel_calls.values())
    rays = calls * run.tile_rays
    work = rays * flops.flops_per_ray(run.arch)
    nbytes = calls * flops.kernel_bytes(run.arch, run.weight_format,
                                        run.tile_rays)
    share, _ = roofline.roofline_share(work, nbytes,
                                       sum(t.kernel_s.values()), run.peak)
    return share


def idle_share(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, mean over the chips used."""
    t = run.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)


def mfu_window(run) -> Optional[float]:
    """Nominal FLOP of the rays delivered in the window over the chips'
    bf16 peak for the window."""
    rays = run.stats["rays_rendered"]
    if not rays or not run.peak:
        return None
    work = rays * flops.flops_per_ray(run.arch)
    return 100.0 * work / (run.chips * run.peak["bf16_flops_per_s"]
                           * run.window.seconds)


def latency_ms(run, q: float) -> Optional[float]:
    """The q-th percentile of the latency of every request due in the
    window, timed from its due time; undelivered ones count as waiting
    until the run gave up on them."""
    from bench.loadgen import latencies_s
    if not any(r.due is not None for r in run.window.records):
        return None
    lat = latencies_s(run.window, run.gave_up_at)
    return 1e3 * float(np.percentile(lat, q))
