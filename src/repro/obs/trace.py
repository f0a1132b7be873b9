"""Bounded ring-buffer span tracer for the serving fabric.

Every request and every tile walks a fixed lifecycle through the
scheduler / executor / completion layers (engine module docstring); the
tracer records that walk as SPANS (named intervals with attributes) and
INSTANT events in one bounded ring. Design constraints, in order:

* **Deterministic.** Span ids are a per-tracer sequence counter, and
  every timestamp comes from the tracer's injectable ``clock`` — the
  same fake clock the engine runs on. Fixed seed + fake clock => two
  runs produce identical span streams (a CI-checkable property, like
  the engine's bit-identity gates).
* **Bounded.** The ring holds ``capacity`` closed spans; overflow drops
  the OLDEST and counts ``dropped`` — a long-running server can leave
  tracing on without unbounded memory, and exporters can say exactly
  how much history they are missing.
* **Cheap when off.** ``NULL_TRACER`` no-ops every call; instrumented
  code tests ``tracer.enabled`` only where it would otherwise do real
  work (building attribute dicts). The tracing-off overhead is gated
  < 3% by the ``serving.observability`` benchmark block.
* **On the profiler's clock too.** ``span`` (a context manager) opens
  and closes a ring span like ``begin``/``end`` and, while the tracer
  is enabled, wraps it in a ``jax.profiler.TraceAnnotation`` of the
  same bare name, so an active JAX profile holds the host's spans beside
  the device's operations. The ring's stamps are read inside the
  annotation, which keeps its cost out of the span durations.

Span taxonomy (docs/observability.md): ``engine.step`` / ``.submit``
around the engine's two entry points, ``request.*`` lifecycle,
``tile.*`` per-dispatch chain (coalesce -> commit -> dispatch ->
device_compute -> wait -> fetch -> drain -> scatter, with retry /
fallback / redispatch / requeue / abandon / drop branches), ``cache.*``
residency, ``host.*`` cluster events, ``plcore.dispatch`` device-side
enqueue, ``jax.compile`` backend compiles (``watch_compiles``).
"""
from __future__ import annotations

import time
import weakref
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, Optional

__all__ = ["Span", "SpanTracer", "NullTracer", "NULL_TRACER",
           "watch_compiles"]


class Span:
    """One named interval (``ph="X"``) or instant (``ph="i"``).
    ``t1 is None`` while the span is open. ``attrs`` is flat
    (str -> scalar); exporters pass it through as Chrome ``args``."""
    __slots__ = ("sid", "name", "cat", "ph", "t0", "t1", "attrs")

    def __init__(self, sid: int, name: str, cat: str, ph: str,
                 t0: float, t1: Optional[float], attrs: dict):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.ph = ph
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs

    def key(self) -> tuple:
        """Deterministic identity for replay comparison: everything,
        attributes sorted."""
        return (self.sid, self.name, self.cat, self.ph, self.t0, self.t1,
                tuple(sorted(self.attrs.items())))

    def __repr__(self):
        dur = ("open" if self.t1 is None
               else f"{(self.t1 - self.t0) * 1e6:.1f}us")
        return f"<Span {self.sid} {self.name} [{self.cat}] {dur} {self.attrs}>"


class NullTracer:
    """The tracing-off fast path: every method is a no-op returning a
    harmless value. Instrumented code never branches on ``None`` —
    it calls through unconditionally."""
    enabled = False

    def begin(self, name, cat="engine", **attrs):
        return None

    def end(self, span, **attrs):
        pass

    def event(self, name, cat="engine", **attrs):
        return None

    def complete(self, name, t0, cat="engine", **attrs):
        return None

    def span(self, name, cat="engine", **attrs):
        return _NULL_SCOPE

    def discard(self, span):
        pass

    def sampled_request(self, rid: int) -> bool:
        return False

    def spans(self):
        return []

    def summary(self) -> dict:
        return {"enabled": False}


NULL_TRACER = NullTracer()
_NULL_SCOPE = nullcontext()


class _Scope:
    """``SpanTracer.span``'s context: a ring span inside a profiler
    annotation of the same bare name (attributes stay in the ring: a
    ``#k=v#`` suffix would split the profile's grouping by name)."""
    __slots__ = ("tracer", "name", "cat", "attrs", "span", "annotation")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 attrs: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def __enter__(self) -> Span:
        from jax import profiler
        self.annotation = profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.span = self.tracer.begin(self.name, self.cat, **self.attrs)
        return self.span

    def __exit__(self, *exc) -> None:
        try:
            if self.span.sid in self.tracer._open:     # not discarded
                self.tracer.end(self.span)
        finally:
            self.annotation.__exit__(*exc)


class SpanTracer:
    """The real tracer. ``capacity`` bounds CLOSED spans (open spans are
    held separately until ended); ``sample_every=N`` samples request
    lifecycle chains (rid % N == 0) while tile/cache/host events stay
    always-on — the span-chain integrity gate covers 100% of dispatched
    tiles regardless of request sampling."""
    enabled = True

    def __init__(self, capacity: int = 65536, clock=time.perf_counter,
                 sample_every: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, got {sample_every}")
        self.capacity = int(capacity)
        self.clock = clock
        self.sample_every = int(sample_every)
        self._ring: deque = deque(maxlen=self.capacity)
        self._open: Dict[int, Span] = {}
        self._sid = 0
        self.dropped = 0
        watch_compiles()
        _LIVE_TRACERS.add(self)

    # ------------------------------------------------------------ emit ----
    def _next_sid(self) -> int:
        sid = self._sid
        self._sid += 1
        return sid

    def _commit(self, span: Span) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(span)

    def begin(self, name: str, cat: str = "engine", **attrs) -> Span:
        """Open a span; close it with ``end``. Open spans don't occupy
        ring capacity and survive overflow."""
        span = Span(self._next_sid(), name, cat, "X", self.clock(), None,
                    attrs)
        self._open[span.sid] = span
        return span

    def end(self, span: Optional[Span], **attrs) -> None:
        """Close an open span (no-op for ``None`` — the sampled-out /
        NullTracer handle), folding in final attributes."""
        if span is None:
            return
        span.t1 = self.clock()
        if attrs:
            span.attrs.update(attrs)
        self._open.pop(span.sid, None)
        self._commit(span)

    def event(self, name: str, cat: str = "engine", **attrs) -> Span:
        """Instant event (zero-duration mark)."""
        now = self.clock()
        span = Span(self._next_sid(), name, cat, "i", now, now, attrs)
        self._commit(span)
        return span

    def complete(self, name: str, t0: float, cat: str = "engine",
                 **attrs) -> Span:
        """Retrofit span: the caller measured ``t0`` itself (no handle
        to thread through); the end is now."""
        span = Span(self._next_sid(), name, cat, "X", t0, self.clock(),
                    attrs)
        self._commit(span)
        return span

    def span(self, name: str, cat: str = "engine", **attrs) -> _Scope:
        """``with tracer.span(name, cat, **attrs) as sp:`` — the ring
        span ``begin``/``end`` would write, mirrored into any active JAX
        profile. ``sp`` is the open ``Span`` (``None`` from
        ``NULL_TRACER``): set final attributes on ``sp.attrs``, or drop
        it with ``discard``."""
        return _Scope(self, name, cat, attrs)

    def discard(self, span: Optional[Span]) -> None:
        """Forget an open span without committing it (work that turned
        out to be nothing, such as a coalesce that found no rays)."""
        if span is not None:
            self._open.pop(span.sid, None)

    # ------------------------------------------------------------ read ----
    def sampled_request(self, rid: int) -> bool:
        return self.sample_every <= 1 or rid % self.sample_every == 0

    def spans(self) -> List[Span]:
        """Closed spans, oldest first (newest ``capacity`` survive)."""
        return list(self._ring)

    def open_spans(self) -> List[Span]:
        return list(self._open.values())

    def summary(self) -> dict:
        spans = events = 0
        for s in self._ring:
            if s.ph == "i":
                events += 1
            else:
                spans += 1
        return {
            "spans": spans,
            "events": events,
            "open_spans": len(self._open),
            "dropped": self.dropped,
            "capacity": self.capacity,
            "sample_every": self.sample_every,
        }


# ---------------------------------------------------------------------------
# Backend compiles, counted inside the program: one process-wide
# jax.monitoring listener (jax keeps listeners for the life of the
# process, so it is registered once) feeds global_registry() and writes a
# back-dated ``jax.compile`` span into every live enabled tracer that
# runs on the host clock the duration is measured on.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LIVE_TRACERS: "weakref.WeakSet[SpanTracer]" = weakref.WeakSet()
_WATCHING = False


def watch_compiles() -> None:
    """Register the compile listener (idempotent): ``jax_compiles_total``
    and ``jax_compile_seconds`` in ``global_registry()``, and a
    ``jax.compile`` span ending now in each live ``SpanTracer`` whose
    clock is ``time.perf_counter`` (a tracer on another clock, such as a
    test's fake one, cannot place a host-clock interval)."""
    global _WATCHING
    if _WATCHING:
        return
    from jax import monitoring

    from repro.obs.metrics import global_registry
    reg = global_registry()
    count = reg.counter("jax_compiles_total",
                        "XLA backend compiles in this process")
    seconds = reg.histogram("jax_compile_seconds",
                            "duration of each XLA backend compile",
                            unit="s")

    def on_duration(event: str, duration: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        count.inc()
        seconds.observe(duration)
        now = time.perf_counter()
        for tr in list(_LIVE_TRACERS):
            if tr.clock is time.perf_counter:
                tr._commit(Span(tr._next_sid(), "jax.compile", "jax", "X",
                                now - duration, now, {}))

    monitoring.register_event_duration_secs_listener(on_duration)
    _WATCHING = True
