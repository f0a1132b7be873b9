"""The reduction from trace to busy time, kernel time and breakdown, on a
small trace recorded on a TPU v5 lite (40 ms of an icarus.batch window:
its device operations and the benchmark's host spans)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, trace

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def events():
    raw = json.loads(DATA.read_text())
    return {"device": {int(c): [tuple(e) for e in evs]
                       for c, evs in raw["device"].items()},
            "host": [tuple(h) for h in raw["host"]]}


def timeline(evs, lo, hi):
    """Brute force: a 100 ns grid over [lo, hi), True where some
    operation runs."""
    n = int((hi - lo) // 100)
    busy = np.zeros(n, bool)
    for _, s, d in evs:
        a, b = max(0, int((s - lo) // 100)), min(n, int((s + d - lo) // 100))
        busy[a:b] = True
    return busy


def test_busy_and_idle_match_a_brute_force_timeline(events):
    s = trace.reduce(events, harness.is_kernel)
    (_, lo, dur), = [h for h in events["host"] if h[0] == "bench.window"]
    assert s.window_s == pytest.approx(dur * 1e-9)
    for chip, evs in events["device"].items():
        grid = timeline(evs, lo, lo + dur)
        assert s.busy_s[chip] == pytest.approx(grid.mean() * s.window_s,
                                               abs=2e-6)
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.mean_busy_s, rel=1e-6)
    assert 0 < s.mean_busy_s < s.window_s


def test_kernel_time_is_the_kernel_calls_inside_the_window(events):
    s = trace.reduce(events, harness.is_kernel)
    (_, lo, dur), = [h for h in events["host"] if h[0] == "bench.window"]
    for chip, evs in events["device"].items():
        calls = [(max(st, lo), min(st + d, lo + dur)) for n, st, d in evs
                 if harness.is_kernel(n) and st < lo + dur and st + d > lo]
        assert s.kernel_calls[chip] == len(calls) > 0
        assert s.kernel_s[chip] == pytest.approx(
            sum(b - a for a, b in calls) * 1e-9)
        assert s.kernel_s[chip] <= s.busy_s[chip]


def test_breakdown_ranks_operations_and_labels_gaps(events):
    s = trace.reduce(events, harness.is_kernel, top=3)
    assert len(s.device_ops) <= 3
    times = [t for _, t in s.device_ops]
    assert times == sorted(times, reverse=True)
    # the kernel leads, under its short name
    assert s.device_ops[0][0].endswith("custom-call")
    labels = {n for n, _ in s.idle_gaps}
    assert labels <= {h[0] for h in events["host"]} | {"idle"}
    assert "bench.window" not in labels


def test_a_trace_without_the_window_span_is_refused(events):
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce({"device": events["device"], "host": []},
                     harness.is_kernel)
