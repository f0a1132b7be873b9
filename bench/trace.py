"""From a profiler trace to device busy time, kernel time and a breakdown.

``load`` reads the newest ``.xplane.pb`` under a profile directory into
plain events: the operations each TPU ran (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the benchmark's own host spans (names
starting ``bench.``, written with ``jax.profiler.TraceAnnotation``).
Both sit on the profiler's one clock. ``reduce`` works on those plain
events only, so it is tested on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Summary:
    """The traced window reduced. Times in seconds; per chip where keyed
    by chip index."""
    window_s: float
    busy_s: Dict[int, float]
    kernel_s: Dict[int, float]
    kernel_calls: Dict[int, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(1, len(self.busy_s))


def load(profile_dir: str) -> dict:
    """{"device": {chip: [(name, start_ns, dur_ns)]},
        "host": [(name, start_ns, dur_ns)]} from the newest trace."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device: Dict[int, list] = defaultdict(list)
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device[int(m.group(1))].extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)
            elif not m:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": dict(device), "host": host}


OP_NAME = re.compile(r"^(%\S+) = \S+ ([\w-]+)\(")


def short_name(op: str) -> str:
    """``%run.1 custom-call`` for the HLO text a TPU trace names its
    operations by."""
    m = OP_NAME.match(op)
    return f"{m.group(1)} {m.group(2)}" if m else op[:80]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(start, dur, lo, hi):
    s, e = max(start, lo), min(start + dur, hi)
    return (s, e) if e > s else None


def _label(gap, spans) -> str:
    """The innermost benchmark span covering most of a gap; ``idle``
    where none does."""
    best, best_cover, best_len = "idle", 0.0, float("inf")
    for name, s, d in spans:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover <= 0 or name == WINDOW_SPAN:
            continue
        if cover > best_cover or (cover == best_cover and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def reduce(events: dict, is_kernel, top: int = 10) -> Summary:
    """Reduce ``load``'s events over the ``bench.window`` span.
    ``is_kernel(op name)`` picks the kernel's operations."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    spans = [h for h in events["host"] if h[1] < hi and h[1] + h[2] > lo]
    busy, kern, calls = {}, {}, {}
    per_op: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for chip, evs in sorted(events["device"].items()):
        ivs, ks, kc = [], 0.0, 0
        for name, s, d in evs:
            c = _clip(s, d, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            per_op[name] += (c[1] - c[0]) * 1e-9
            if is_kernel(name):
                ks += (c[1] - c[0]) * 1e-9
                kc += 1
        merged = _merge(ivs)
        busy[chip] = sum(e - s for s, e in merged) * 1e-9
        kern[chip], calls[chip] = ks, kc
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle[_label((s, e), spans)] += (e - s) * 1e-9
    short: Dict[str, float] = defaultdict(float)
    for name, t in per_op.items():
        short[short_name(name)] += t
    rank = sorted(short.items(), key=lambda kv: -kv[1])
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy, kernel_s=kern,
        kernel_calls=calls, device_ops=rank[:top],
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:top])
