"""The benchmark's one traffic generator and its two drivers.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``,
overlaid by the cell's ``params``); this module turns it into requests
and drives an engine with them for a timed window. The keys, each
optional unless marked:

- ``scenes`` (required): scene ids ``scene0`` ... ; ``zipf_s``: scene k
  is requested with weight 1 / (k + 1) ** zipf_s (default: uniform);
  ``resident``: how many of the first scenes are loaded before the
  window (default: all).
- ``hw``: square frame sizes in equal shares, or ``classes``: request
  classes ``{"hw", "share", "priority", "deadline_ms"}`` where each
  round of requests holds every class ``share`` (a whole number) times.
- ``poses``: ``{"kind": "orbit", "count", "phi", "radius"}`` (consecutive
  poses of a camera path) or ``{"kind": "uniform", "theta", "phi",
  "radius"}`` (ranges); ``views``: theta offsets in degrees, one request
  each per pose (a stereo pair: two).
- ``loop: "closed"`` with ``in_flight``: that many requests outstanding
  at all times, a new one entering as one completes: callers that each
  wait for their frame, as an offline renderer of a camera path does.
- ``loop: "open"`` with ``rate_per_s``: requests arrive whether or not
  the engine keeps up, as independent viewers do. ``arrivals``:
  ``"poisson"`` (default; the gaps are the exponential distribution's
  quantiles at (k + 1/2) / n in one shuffled order), ``"periodic"``
  (a frame clock), or ``"onoff"`` (Poisson at the rate for ``on_s``,
  then silent for ``off_s``); ``per_arrival``: requests due together.

Every seed offers the same arrivals, sizes and scenes in the same order
(``order``, default ``ORDER``; a mix with another ``order`` offers
another sequence of the same work); the seed draws the cameras (and the
harness the weights). Near the knee the order of the gaps and sizes
sets the queueing: with the order drawn from the seed, six seeds' p95
latencies spread by 29% of their median where two runs of one seed
differ by 0-5%.

Each request is timed from its due time, not from when the driver got
round to submitting it, so a stall of the single-threaded server shows
up in every request it delays; how late the driver submitted is
reported as ``lateness`` beside it. A request that fails, or is still
unanswered ``grace_s`` after the window closed, counts as missing any
limit (its latency is the time it had waited when the run gave up).

Adapted from ``repro.serving.loadgen`` (``poisson_trace``,
``run_open_loop``, ``run_closed_loop``), which runs a trace to its end,
leaves failed requests out of the tail and does not report its own
lateness.
"""
from __future__ import annotations

import itertools
import math
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

import numpy as np

DELIVERED = ("ok", "degraded")
STALL_S = 0.05          # an engine call longer than this is a stall
#                         (a step of one tile takes ~0.01 s on the chip)


@dataclass(frozen=True)
class Spec:
    """One request as the generator draws it."""
    scene: int
    hw: int
    theta: float
    phi: float
    radius: float
    priority: int = 0
    deadline_ms: Optional[float] = None


@dataclass
class Record:
    """One request's life on the benchmark's clock."""
    spec: Spec
    due: Optional[float]          # open loop: when it was to be sent
    submit: float
    rid: int = -1
    status: Optional[str] = None  # terminal status, None while pending
    service_start: Optional[float] = None
    complete: Optional[float] = None

    @property
    def delivered(self) -> bool:
        return self.status in DELIVERED


@dataclass
class Window:
    """What a driver saw: the records, the window's bounds, how late the
    open-loop driver ran, and the engine calls that stalled it (see
    ``Driver._timed``)."""
    records: List[Record]
    t0: float
    t1: float
    lateness_s: List[float] = field(default_factory=list)
    stalls: List[tuple] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# ------------------------------------------------------------ generation --
ORDER = 0               # the one seed of every run's order of work


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), sum(map(ord, stream))])


def classes(traffic: dict) -> List[dict]:
    """The mix's request classes (``classes``, or one per ``hw`` size in
    equal shares)."""
    return traffic.get("classes") or [{"hw": h, "share": 1}
                                      for h in traffic["hw"]]


def _classes(traffic: dict) -> Iterator[dict]:
    """Rounds that hold every class ``share`` times, each round in a
    shuffled order."""
    rnd = [c for c in classes(traffic) for _ in range(int(c["share"]))]
    rng = _rng(traffic.get("order", ORDER), "hw")
    while True:
        yield from (rnd[i] for i in rng.permutation(len(rnd)))


def _scenes(traffic: dict) -> Iterator[int]:
    n = traffic["scenes"]
    rng = _rng(traffic.get("order", ORDER), "scenes")
    if "zipf_s" not in traffic:
        while True:
            yield int(rng.integers(n))
    p = 1.0 / np.arange(1, n + 1) ** traffic["zipf_s"]
    while True:
        yield from (int(k) for k in rng.choice(n, 1024, p=p / p.sum()))


def _poses(traffic: dict, seed: int) -> Iterator[tuple]:
    p = traffic["poses"]
    views = traffic.get("views", [0.0])
    if p["kind"] == "orbit":
        # consecutive frames of a camera path around the object, starting
        # at a seeded pose
        step = 360.0 / p["count"]
        k0 = int(_rng(seed, "orbit").integers(p["count"]))
        poses = (((k % p["count"]) * step, p["phi"], p["radius"])
                 for k in itertools.count(k0))
    elif p["kind"] == "uniform":
        rng = _rng(seed, "poses")
        poses = ((float(rng.uniform(*p["theta"])),
                  float(rng.uniform(*p["phi"])), p["radius"])
                 for _ in itertools.count())
    else:
        raise ValueError(f"unknown pose kind {p['kind']!r}")
    for theta, phi, radius in poses:
        for v in views:
            yield (theta + v, phi, radius)


def requests(traffic: dict, seed: int) -> Iterator[Spec]:
    """The mix's requests, endlessly: classes and scenes in the one
    order, cameras from the seed."""
    for c, scene, (theta, phi, radius) in zip(
            _classes(traffic), _scenes(traffic), _poses(traffic, seed)):
        yield Spec(scene, int(c["hw"]), theta, phi, radius,
                   int(c.get("priority", 0)), c.get("deadline_ms"))


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open-loop mix (see the module's
    ``arrivals`` key)."""
    rate = traffic["rate_per_s"]
    kind = traffic.get("arrivals", "poisson")
    if kind == "periodic":
        t = np.arange(max(1, math.ceil(rate * seconds))) / rate
    elif kind in ("poisson", "onoff"):
        on, off = ((seconds, 0.0) if kind == "poisson"
                   else (traffic["on_s"], traffic["off_s"]))
        cycles, rest = divmod(seconds, on + off)
        n = max(1, math.ceil(rate * (cycles * on + min(rest, on))))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        t = np.cumsum(_rng(traffic.get("order", ORDER), "gaps")
                      .permutation(gaps))
        t = t + np.floor(t / on) * off       # silent spells pushed in
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    t = np.repeat(t, int(traffic.get("per_arrival", 1)))
    return t[t < seconds]


# --------------------------------------------------------------- drivers --
class Driver:
    """Drives an engine (``submit(request) -> id``, ``step() -> bool``,
    ``completed[id]`` results with ``status``, ``service_start_s`` and
    ``complete_s``) on ``clock``. ``make_request`` turns a ``Spec`` into
    the engine's request; ``annotate(name)`` returns a context manager
    that marks a host span in the profiler's trace."""

    def __init__(self, engine, make_request: Callable, *,
                 clock=time.perf_counter, sleep=time.sleep,
                 annotate=None):
        self.engine = engine
        self.make_request = make_request
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate or (lambda name: nullcontext())
        self.stalls: List[tuple] = []
        self._t_first = clock()

    @contextmanager
    def _timed(self, what: str):
        """Annotates the block and records it as a stall when it takes
        longer than ``STALL_S``: (what, when after the first call,
        seconds, CPU seconds of this thread, of the whole process)."""
        t0, th0, cpu0 = self.clock(), time.thread_time(), time.process_time()
        with self.annotate(f"bench.{what}"):
            yield
        took = self.clock() - t0
        if took > STALL_S:
            self.stalls.append((what, t0 - self._t_first, took,
                                time.thread_time() - th0,
                                time.process_time() - cpu0))

    def _submit(self, spec: Spec, due: Optional[float]) -> Record:
        with self._timed("submit"):
            rec = Record(spec, due, self.clock())
            rec.rid = self.engine.submit(self.make_request(spec))
        return rec

    def _step(self) -> bool:
        with self._timed("step"):
            return self.engine.step()

    def _settle(self, recs: List[Record]) -> None:
        done = self.engine.completed
        for r in recs:
            if r.status is None and r.rid in done:
                res = done[r.rid]
                r.status = res.status
                r.service_start = res.service_start_s
                r.complete = res.complete_s

    def closed(self, specs: Iterator[Spec], in_flight: int,
               seconds: float) -> Window:
        """Keep ``in_flight`` requests outstanding for ``seconds``. The
        window ends with the last step begun before it closes."""
        recs: List[Record] = []
        open_recs: List[Record] = []
        t0 = self.clock()
        with self.annotate("bench.window"):
            while self.clock() - t0 < seconds:
                self._settle(open_recs)
                open_recs = [r for r in open_recs if r.status is None]
                while len(open_recs) < in_flight:
                    rec = self._submit(next(specs), None)
                    recs.append(rec)
                    open_recs.append(rec)
                    self._settle([rec])       # refused at admission
                    open_recs = [r for r in open_recs if r.status is None]
                self._step()
            t1 = self.clock()
        self._settle(recs)
        return Window(recs, t0, t1, stalls=list(self.stalls))

    def open(self, specs: Iterator[Spec], due: np.ndarray, seconds: float,
             grace_s: float = 60.0) -> Window:
        """Submit each request once its due time (seconds after the
        window opens) has passed; after the window, keep serving until
        every due request is answered or ``grace_s`` has gone by."""
        recs: List[Record] = []
        late: List[float] = []
        i = 0
        t0 = self.clock()
        with self.annotate("bench.window"):
            while True:
                now = self.clock()
                if now - t0 >= seconds:
                    break
                while i < len(due) and t0 + due[i] <= now:
                    rec = self._submit(next(specs), t0 + float(due[i]))
                    late.append(rec.submit - rec.due)
                    recs.append(rec)
                    i += 1
                if not self._step():
                    nxt = float(due[i]) if i < len(due) else seconds
                    wait = t0 + min(nxt, seconds) - self.clock()
                    if wait > 0:
                        with self.annotate("bench.sleep"):
                            self.sleep(min(wait, 0.05))
            t1 = self.clock()
        while self.clock() - t1 < grace_s:
            self._settle(recs)
            if all(r.status is not None for r in recs):
                break
            if not self._step():
                self.sleep(0.001)
        self._settle(recs)
        return Window(recs, t0, t1, late, list(self.stalls))


def latencies_s(window: Window, gave_up_at: float) -> List[float]:
    """Each request's latency from its due time (open loop) or its submit
    (closed loop); a request not delivered counts as having waited until
    ``gave_up_at``."""
    out = []
    for r in window.records:
        start = r.submit if r.due is None else r.due
        out.append((r.complete if r.delivered else gave_up_at) - start)
    return out
