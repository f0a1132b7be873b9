"""The program's spans, read from its ring and from a JAX profile: the
per-tile and per-request readings, the idle time credited piece by piece
to the innermost span, and, on the CPU, a profile of a few engine steps
that holds the program's spans under the benchmark's own."""
import json
import tempfile
import time
from collections import Counter, defaultdict
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import harness, loadgen, program_spans, trace
from bench.tests.test_bench_harness import tiny_cell

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


# ------------------------------------------------------------ readings ----
def test_readings_of_the_ring():
    spans = [("engine.submit", 0.0, 0.010), ("request.rays", 0.001, 0.009),
             ("engine.step", 0.010, 0.020), ("tile.coalesce", 0.010, 0.011),
             ("tile.wait", 0.012, 0.018), ("tile.scatter", 0.019, 0.0195),
             ("engine.step", 0.020, 0.030), ("tile.coalesce", 0.020, 0.021),
             ("tile.wait", 0.022, 0.029), ("engine.submit", 0.030, 0.036)]
    run = SimpleNamespace(spans=spans)
    # (20 ms of steps - 13 ms of waits) over 2 tiles
    assert program_spans.step_host_ms_per_tile(run) == pytest.approx(3.5)
    assert program_spans.submit_ms_per_request(run) == pytest.approx(8.0)


def test_readings_are_silent_without_the_spans():
    """A program from before the spans records coalesce and scatter
    only: the readings give nothing, and never 0."""
    run = SimpleNamespace(spans=[("tile.coalesce", 0.0, 0.001),
                                 ("tile.scatter", 0.002, 0.003)])
    assert program_spans.step_host_ms_per_tile(run) is None
    assert program_spans.submit_ms_per_request(run) is None


# ---------------------------------------------------- idle attribution ----
def test_a_gap_is_credited_piecewise_to_the_innermost_span():
    events = {
        "device": {0: [("op", 0, 10), ("op", 60, 30), ("op", 95, 5)],
                   1: [("op", 0, 100)]},
        "host": [("bench.window", 0, 100), ("bench.step", 5, 65),
                 ("engine.step", 12, 43), ("tile.commit", 15, 5),
                 ("tile.wait", 40, 14)],
    }
    got = dict(program_spans.idle_by_span(events))
    # chip 0 idles over [10, 60) and [90, 95); chip 1 never
    want = {"bench.step": 2 + 5, "engine.step": 3 + 20 + 1,
            "tile.commit": 5, "tile.wait": 14, "idle": 5}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def _tiles(skews):
    """A tile per entry of ``skews``, 10 us apart, at depth 1: dispatch,
    then the wait; the kernel runs from 0.1 us after the dispatch ends to
    0.4 us before the wait ends, on a device clock that many ns behind
    the host's."""
    host, kernels = [("bench.window", 0, len(skews) * 10_000)], []
    for i, skew in enumerate(skews):
        t = i * 10_000
        host += [("engine.step", t + 500, 9_000),
                 ("plcore.dispatch", t + 1_000, 300),
                 ("tile.wait", t + 1_400, 7_000)]
        kernels.append(("tpu_custom_call", t + 1_400 - skew, 6_600))
    return {"device": {0: kernels}, "host": host}


def test_the_clock_offset_is_bounded_by_dispatch_and_wait():
    events = _tiles([1_000] * 4)
    # a kernel starts after its dispatch began, ends before its wait ended
    first = events["device"][0][0][1]
    assert program_spans.clock_offset(events, harness.is_kernel) == \
        [(first, 1_000 - 400, 1_000 + 400)]
    # a kernel whose host spans the profile lost stays unpaired
    events["host"] = [h for h in events["host"]
                      if not 20_000 <= h[1] < 30_000]
    assert program_spans.clock_offset(events, harness.is_kernel) == \
        [(first, 600, 1_400)]
    # without the program's waits there is nothing to bound it with
    parent = {"device": events["device"],
              "host": [h for h in events["host"] if h[0] != "tile.wait"]}
    assert program_spans.clock_offset(parent, harness.is_kernel) is None


def test_a_jump_of_the_device_clock_starts_a_new_piece():
    events = _tiles([1_000] * 3 + [3_000] * 3)
    pieces = program_spans.clock_offset(events, harness.is_kernel)
    assert [(lo, hi) for _, lo, hi in pieces] == [(600, 1_400),
                                                  (2_600, 3_400)]
    assert pieces[1][0] == events["device"][0][3][1]


def test_the_offset_moves_idle_time_onto_the_spans_it_fell_in():
    events = _tiles([1_000] * 3 + [3_000] * 3)
    skewed = dict(program_spans.idle_by_span(events))
    pieces = program_spans.clock_offset(events, harness.is_kernel)
    fixed = dict(program_spans.idle_by_span(events, pieces))
    # on the device's own clock the kernels seem to start before their
    # dispatch; moved by the offset, each runs inside its wait
    assert skewed.get("plcore.dispatch", 0) == 0
    assert fixed["plcore.dispatch"] == pytest.approx(6 * 300e-9)
    assert fixed["tile.wait"] == pytest.approx(6 * 400e-9)
    assert sum(fixed.values()) == pytest.approx(sum(skewed.values()),
                                                abs=5e-6)


def test_the_recorded_trace_keeps_its_idle_total():
    raw = json.loads(DATA.read_text())
    events = {"device": {int(c): [tuple(e) for e in evs]
                         for c, evs in raw["device"].items()},
              "host": [tuple(h) for h in raw["host"]]}
    s = trace.reduce(events, harness.is_kernel)
    got = program_spans.idle_by_span(events)
    assert sum(v for _, v in got) == pytest.approx(
        s.window_s - s.mean_busy_s, rel=1e-6)
    assert {n for n, _ in got} <= {h[0] for h in events["host"]} | {"idle"}
    assert "bench.window" not in dict(got)


# ----------------------------------------------- a CPU profile, read back --
PROGRAM = ("engine.step", "engine.submit", "request.rays", "tile.coalesce",
           "tile.commit", "plcore.dispatch", "tile.wait", "tile.fetch",
           "tile.scatter")


@pytest.fixture(scope="module")
def profiled():
    """A second of the tiny live cell's traffic, closed loop, under the
    harness's profile options: (profile events, the ring's spans)."""
    from repro.obs.trace import SpanTracer
    from repro.serving import RenderRequest

    cell = tiny_cell("icarus.live")
    tracer = SpanTracer()
    engine, cache = harness.build_engine(cell, 5, jax.devices()[0], tracer)
    harness.warm_up(cell, engine, cache)
    driver = loadgen.Driver(
        engine, lambda s: RenderRequest(f"scene{s.scene}", hw=s.hw,
                                        theta=s.theta, phi=s.phi,
                                        radius=s.radius),
        annotate=jax.profiler.TraceAnnotation)
    n0 = len(tracer.spans())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        driver.closed(loadgen.requests(cell.traffic, 5), 2, 1.0)
        jax.profiler.stop_trace()
        events = program_spans.load(d)
    return events, tracer.spans()[n0:]


def test_the_profile_holds_the_program_spans(profiled):
    events, ring = profiled
    names = Counter(n for n, _, _ in events["host"])
    ring_names = Counter(s.name for s in ring if s.name in PROGRAM)
    assert set(PROGRAM) <= set(names)
    for name in PROGRAM:
        assert names[name] == ring_names[name] > 0


def test_the_program_spans_nest_under_the_benchmarks(profiled):
    events, _ = profiled
    outer = [(s, s + d) for n, s, d in events["host"]
             if n in ("bench.step", "bench.submit")]
    for name, s, d in events["host"]:
        if name.startswith(program_spans.PROGRAM_PREFIXES):
            assert any(a <= s and s + d <= b for a, b in outer), name


def test_the_two_clocks_agree(profiled):
    """The ring's stamps are read inside each annotation: a span's total
    on the profile's clock holds the ring's, and is within 10% of it
    where the spans are long enough (1 ms and more on average) that the
    annotation's own cost is small beside them."""
    events, ring = profiled
    prof, ring_s, count = defaultdict(float), defaultdict(float), Counter()
    for n, _, d in events["host"]:
        prof[n] += d * 1e-9
    for s in ring:
        ring_s[s.name] += s.t1 - s.t0
        count[s.name] += 1
    for name in PROGRAM:
        assert ring_s[name] <= prof[name] * (1 + 1e-3), name
        if ring_s[name] / count[name] >= 1e-3:
            assert prof[name] == pytest.approx(ring_s[name], rel=0.1), name
    assert ring_s["engine.step"] / count["engine.step"] >= 1e-3


# --------------------------------------------- the result of a traced run --
@pytest.mark.parametrize("name", ["icarus.batch", "icarus.live"])
def test_a_traced_run_reports_the_new_metrics(name):
    cell = tiny_cell(name)
    out = harness.run_cell(cell, 2 ** 33 + 5, 1.0, True, jax.devices(),
                           time.perf_counter(), grace_s=20.0)
    assert out["correct"], out["checks"]
    listed = {m["name"] for m in cell.per_layer}
    new = {f"step_host_ms_per_tile.{name.split('.')[1]}",
           "submit_ms_per_request.live"} & listed
    assert new and new <= set(out["metrics"])
    for m in new:
        assert out["metrics"][m]["value"] > 0
    host = out["metrics"].get("host_ms_per_tile.batch",
                              out["metrics"].get("host_ms_per_tile.live"))
    step = out["metrics"][f"step_host_ms_per_tile.{name.split('.')[1]}"]
    assert step["value"] > host["value"]
