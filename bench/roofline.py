"""The table of peaks, and shares of them.

Shares are reported as they read and never clipped: a share over 100%
means the work is counted too high or the time leaves part of it out.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name} (known: {sorted(table)})")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(percent of the roofline, bound) for work that took ``seconds``:
    the least time the chip could take, the larger of FLOP over peak
    FLOP/s and bytes over peak bytes/s, over the time it took."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
