"""The program's own spans: per-tile and per-request host time from the
engine's span ring, and the chip's idle time credited span by span.

The engine's ``SpanTracer`` (``repro.obs.trace``) records its spans in a
ring on ``time.perf_counter`` (``Run.spans`` holds those inside the
window) and, while a JAX profile is active, mirrors each ``span`` into
the profile as a ``TraceAnnotation`` of the same name, on the device
trace's clock. The readings here return ``None`` where the program
records no such span, as a program from before the spans does.

``idle_by_span`` splits each idle gap of the device at the edges of the
host spans that overlap it and credits each piece to the innermost
(shortest) span covering it, ``idle`` where none does. ``bench/trace.py``
labels a whole gap with one ``bench.*`` span instead, and keeps no
program span.

The profile puts the device's operations on the host's clock only up to
an offset, which differs from run to run and can move within one: on a
TPU v5 lite kernels have read as starting 0.3 to 1.5 ms before the host
enqueued them, and one run's offset moved by 4 ms. ``clock_offset``
bounds it from the program's own spans
(a kernel cannot start before the ``plcore.dispatch`` that launched it
began, nor end after the ``tile.wait`` for it ended), and
``idle_by_span`` moves the device's operations by the middle of the
bounds before it credits the gaps. Run as a script, this module makes
one traced run of a cell through the harness and prints the offset and
the breakdown before the result line:

    python3 -m bench.program_spans --workload icarus.batch --seed 7 \\
        --seconds 51
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace

# Name prefixes of the program's spans, beside trace.HOST_PREFIX.
PROGRAM_PREFIXES = ("engine.", "request.", "tile.", "plcore.", "cache.")


# ------------------------------------------------------- from the ring ----
def _total(run, name: str) -> Tuple[int, float]:
    n, s = 0, 0.0
    for nm, t0, t1 in run.spans:
        if nm == name:
            n += 1
            s += t1 - t0
    return n, s


def step_host_ms_per_tile(run) -> Optional[float]:
    """Host time of the engine's steps with the waits on the chip left
    out (``engine.step`` minus ``tile.wait``), mean ms per tile
    (``tile.coalesce``) over the window."""
    tiles, _ = _total(run, "tile.coalesce")
    steps, step_s = _total(run, "engine.step")
    if not tiles or not steps:
        return None
    _, wait_s = _total(run, "tile.wait")
    return 1e3 * (step_s - wait_s) / tiles


def submit_ms_per_request(run) -> Optional[float]:
    """Mean ``engine.submit`` (admission and the request's camera rays)
    per request submitted in the window, ms."""
    n, s = _total(run, "engine.submit")
    return 1e3 * s / n if n else None


# ---------------------------------------------------- from the profile ----
def load(profile_dir: str) -> dict:
    """``trace.load``'s events, the host spans holding the program's
    (``PROGRAM_PREFIXES``) beside the benchmark's."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    keep = (trace.HOST_PREFIX,) + PROGRAM_PREFIXES
    device: Dict[int, list] = defaultdict(list)
    host: list = []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace.OPS_LINE:
                device[int(m.group(1))].extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)
            elif not m:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name.startswith(keep))
    return {"device": dict(device), "host": host}


def _credit(gaps, spans) -> Dict[str, float]:
    """ns of ``gaps`` [(start, end)] under the innermost of ``spans``
    [(name, start, dur)] covering each instant; ``idle`` under none. One
    sweep over every edge, holding the spans open at each instant."""
    marks = []
    for name, s, d in spans:
        marks.append((s, 1, (d, name)))
        marks.append((s + d, -1, (d, name)))
    for a, b in gaps:
        marks.append((a, 2, None))
        marks.append((b, -2, None))
    marks.sort(key=lambda m: m[0])
    open_spans: Counter = Counter()
    out: Dict[str, float] = defaultdict(float)
    in_gap, prev = 0, None
    for t, kind, key in marks:
        if in_gap and t > prev:
            inner = min(open_spans, default=None)
            out["idle" if inner is None else inner[1]] += t - prev
        prev = t
        if kind == 1:
            open_spans[key] += 1
        elif kind == -1:
            open_spans[key] -= 1
            if not open_spans[key]:
                del open_spans[key]
        else:
            in_gap += kind // 2
    return out


Piece = Tuple[int, int, int]       # (from device ns, lowest, highest)


def clock_offset(events: dict, is_kernel) -> Optional[List[Piece]]:
    """What to add to the device's timestamps to put them on the host
    spans' clock, as pieces ``(from device ns, lowest, highest)``: a
    kernel starts after the start of the ``plcore.dispatch`` that
    launched it and ends before the end of the ``tile.wait`` for it.

    Each wait pairs with the dispatch before it and with the kernel whose
    end lies nearest its own once moved by the offset so far (at first,
    the median of those gaps): one kernel per tile, one tile in flight
    (pipeline depth 1, as every cell runs the engine), on one chip. The
    profile can lose events under load, and its device clock can move:
    a kernel with no wait stays unpaired, and the offset is piecewise
    constant, a new piece starting where a pair's bounds no longer meet
    the piece's but lie within half a kernel of them. ``None`` where the
    trace holds no waits, dispatches or kernels."""
    kernels = sorted((s, s + d) for evs in events["device"].values()
                     for n, s, d in evs if is_kernel(n))
    waits = sorted((s, s + d) for n, s, d in events["host"]
                   if n == "tile.wait")
    dispatches = sorted(s for n, s, _ in events["host"]
                        if n == "plcore.dispatch")
    if not kernels or not waits or not dispatches:
        return None
    ends = [e for _, e in kernels]

    def nearest(t: float) -> int:
        i = bisect.bisect_left(ends, t)
        return min((j for j in (i - 1, i) if 0 <= j < len(ends)),
                   key=lambda j: abs(ends[j] - t))

    gaps = sorted(w1 - ends[nearest(w1)] for _, w1 in waits)
    ref = gaps[len(gaps) // 2]            # where the offset is expected
    pieces: List[list] = []
    for w0, w1 in waits:
        d = bisect.bisect_left(dispatches, w0) - 1
        if d < 0:
            continue
        k0, k1 = kernels[nearest(w1 - ref)]
        low, high = dispatches[d] - k0, w1 - k1
        if low > high:                    # not this wait's kernel
            continue
        if pieces and max(pieces[-1][1], low) <= min(pieces[-1][2], high):
            pieces[-1][1] = max(pieces[-1][1], low)
            pieces[-1][2] = min(pieces[-1][2], high)
        elif abs(high - ref) < (k1 - k0) / 2:
            # the clock moved; pairing a wait with a neighbour's kernel
            # (its own lost from the profile) would move it a kernel's
            # length or more
            pieces.append([k0, low, high])
        else:
            continue
        ref = pieces[-1][2]
    return [tuple(p) for p in pieces] or None


def _moved(evs, pieces: Optional[List[Piece]]):
    """``evs`` [(name, start, dur)] moved by the middle of the piece each
    starts in (the first piece before it begins)."""
    if not pieces:
        return evs
    froms = [p[0] for p in pieces]
    out = []
    for n, s, d in evs:
        p = pieces[max(0, bisect.bisect_right(froms, s) - 1)]
        out.append((n, s + (p[1] + p[2]) / 2, d))
    return out


def idle_by_span(events: dict, pieces: Optional[List[Piece]] = None
                 ) -> List[Tuple[str, float]]:
    """The device's idle time in the ``bench.window`` span, in seconds
    summed over the chips, credited piece by piece to the innermost host
    span covering it (``bench.window`` itself left out), largest first.
    The device's operations are first moved by ``clock_offset``'s
    ``pieces``; without them the total is ``trace.reduce``'s."""
    windows = [(s, s + d) for n, s, d in events["host"]
               if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    spans = [h for h in events["host"]
             if h[0] != trace.WINDOW_SPAN and h[1] < hi and h[1] + h[2] > lo]
    credit: Dict[str, float] = defaultdict(float)
    for evs in events["device"].values():
        busy = trace._merge(c for c in (trace._clip(s, d, lo, hi)
                                        for _, s, d in _moved(evs, pieces))
                            if c)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for name, ns in _credit(gaps, spans).items():
            credit[name] += ns * 1e-9
    return sorted(credit.items(), key=lambda kv: -kv[1])


def main(argv=None) -> int:
    """``bench/run.py --trace 1`` with the idle breakdown by program span
    printed before the result line. The harness reads its profile through
    ``trace.load`` and then deletes it, so the breakdown is taken at that
    call."""
    from bench import harness
    argv = list(sys.argv[1:] if argv is None else argv)
    load_events = trace.load

    def load_and_break_down(profile_dir: str) -> dict:
        events = load(profile_dir)
        pieces = clock_offset(events, harness.is_kernel)
        print("device clock offset (from device ns, lowest ms, highest "
              "ms): " + json.dumps([(p[0], p[1] * 1e-6, p[2] * 1e-6)
                                    for p in pieces or []]), flush=True)
        print("idle by span: " + json.dumps(idle_by_span(events, pieces)),
              flush=True)
        return load_events(profile_dir)

    trace.load = load_and_break_down
    try:
        return harness.main(argv + ["--trace", "1"])
    finally:
        trace.load = load_events


if __name__ == "__main__":
    sys.exit(main())
