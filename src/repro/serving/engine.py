"""Multi-tenant render engine: scheduler / executor / completion layers.

ICARUS §5 scales by putting a ray dispatcher in front of many PLCores;
Cicero (2404.11852) shows that once the per-sample kernel is fused, the
remaining throughput levers are *scheduling* and *memory traffic*. The
engine is that dispatcher, decomposed into three explicit layers so each
lever has one home:

* ``TileScheduler`` — the policy layer. Owns the request queue
  (``submit`` allocates a NaN-filled framebuffer: every pixel must
  arrive via a tile scatter, so gaps or cross-request leaks surface as
  NaN instead of silently reading as black), picks the next scene by
  (priority, FIFO) with sticky-scene grouping, coalesces one fixed-shape
  tile of ``tile_rays`` rays across that scene's pending requests (pad
  only the tail).
* ``TileExecutor`` — the dispatch layer. Keeps up to ``pipeline_depth``
  tiles in flight: ``PackedPlcore.dispatch_tile`` returns an UN-BLOCKED
  device array (jax async dispatch), so the executor dispatches tile k+1
  and drains tile k−(depth−1) while the device computes the tiles in
  between — host coalescing/scatter overlaps device compute instead of
  alternating with it. The served default is ``DEFAULT_PIPELINE_DEPTH``
  (2): the next tile is queued on the device before the host blocks on
  the last one, so the device never waits for the host's per-tile work.
  ``pipeline_depth=1`` flushes every dispatch immediately and reduces
  EXACTLY to the synchronous dispatch→block→scatter loop (the
  bit-identity anchor CI pins). The
  executor pins each tile's scene in the ``SceneCache`` for the life of
  the slot, so eviction can never drop weights under an in-flight
  dispatch.
* ``CompletionSink`` — the output layer. Materializes a drained tile's
  pixels, scatters them to each contributing request's framebuffer and
  completes requests OUT OF ORDER as their last ray lands — semantics
  identical to the synchronous engine.

``RenderEngine`` is the façade wiring the three together behind the same
``submit``/``step``/``drain``/``take`` surface as before. Because every
per-ray op depends only on its own ray, the per-request images are
bit-identical across pipeline depths even when the tile partition
differs — only throughput moves.

Fault tolerance
---------------

One loader exception, one NaN-poisoned tile, or one straggling dispatch
must not crash or corrupt the other in-flight requests: ``step()`` and
``drain()`` never raise for those fault classes. Every submitted request
instead reaches exactly ONE terminal status:

* ``ok``       — every pixel delivered at full quality.
* ``degraded`` — completed coarse-only under the overload-degradation
  policy (Cicero: controlled quality reduction is a legitimate overload
  response) — ~1/3 of the sample budget, flagged, never silent.
* ``partial``  — deadline expired mid-render; delivered with the pixels
  that landed (unrendered pixels stay NaN — visible, not fabricated).
* ``expired``  — deadline expired before the first ray was tiled.
* ``rejected`` — refused terminally: at admission (bounded queue full,
  or SLO admission control predicts the queueing delay alone exceeds
  the request's deadline) or because its scene's loader failed
  ``max_load_failures`` consecutive times.

Recovery ladder for a failed tile (dispatch raised, or the drained
buffer is non-finite): up to ``max_tile_retries`` fresh dispatches with
capped exponential backoff — a retry re-renders the same rays through
the same resident weights, so recovery is BIT-EXACT — then the
two-dispatch oracle program (``PackedPlcore.render_tile_oracle``, the
trusted bit-identical floor). A ``StragglerMonitor``
(``runtime.straggler``) watches per-tile in-flight latency; a tile
whose latency blows past the deadline factor is abandoned and
redispatched rather than stalling the drain point. Scene-loader
failures are contained by the ``SceneCache``'s negative-result backoff
(the scheduler simply schedules other scenes meanwhile). All of it is
deterministically exercisable via ``serving.faults.FaultPlan``
(seeded injection at each trust boundary), which CI runs as a chaos
smoke: goodput gated, fault-free-request pixels bit-identical to a
clean run.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.data import rays as R
from repro.obs.metrics import (MetricsRegistry, engine_stats_view)
from repro.obs.trace import NULL_TRACER, watch_compiles
from repro.serving.faults import FaultPlan, InjectedDispatchError
from repro.serving.scene_cache import SceneCache, SceneLoadError

#: Terminal request statuses (see module docstring).
STATUSES = ("ok", "degraded", "partial", "expired", "rejected")

#: In-flight tile slots of a ``RenderEngine`` built without
#: ``pipeline_depth``: tile k+1 is coalesced, committed and enqueued while
#: tile k runs, so the host's per-tile work hides behind the kernel.
DEFAULT_PIPELINE_DEPTH = 2


@dataclass(frozen=True)
class RenderRequest:
    """One render-an-image request. The camera is a spherical orbit pose
    (the repo's scene convention); ``priority`` is higher-wins, ties
    FIFO. ``deadline_s`` (relative to submit) arms SLO admission control
    and expiry: ``None`` never expires — the pre-fault-tolerance
    behavior."""
    scene_id: str
    hw: int = 64
    theta: float = 45.0
    phi: float = -25.0
    radius: float = 4.0
    priority: int = 0
    deadline_s: Optional[float] = None


@dataclass
class RenderResult:
    request_id: int
    scene_id: str
    image: np.ndarray            # (hw, hw, 3) float32
    n_rays: int
    submit_s: float              # engine-clock timestamps
    service_start_s: float       # first ray handed to a tile
    complete_s: float
    dispatch_baseline: int       # tiles a request-at-a-time server pays
    status: str = "ok"           # terminal status (STATUSES)
    error: Optional[str] = None  # human-readable failure reason
    retries: int = 0             # tile retry attempts touching this request
    fallbacks: int = 0           # oracle-fallback tiles touching it

    @property
    def latency_s(self) -> float:
        return self.complete_s - self.submit_s

    @property
    def queueing_s(self) -> float:
        """Time spent waiting in the queue before the scheduler handed
        the first ray to a tile."""
        return self.service_start_s - self.submit_s

    @property
    def service_s(self) -> float:
        """First-ray-dispatched -> last-pixel-scattered."""
        return self.complete_s - self.service_start_s

    @property
    def delivered(self) -> bool:
        """Whether the image carries fully-rendered pixels (``ok`` /
        ``degraded``) — the goodput numerator."""
        return self.status in ("ok", "degraded")


class _Active:
    """Queue entry: request + flattened rays + framebuffer + cursors.
    Under adaptive sampling the single ``next_ray`` cursor is joined by
    per-budget-class index lists (``bucket_idx``/``bucket_next``): rays
    are handed out bucket-by-bucket so tiles stay (scene, budget)-pure,
    while ``next_ray`` keeps counting TOTAL handed-out rays so
    ``remaining`` and the admission math are bucket-agnostic."""
    __slots__ = ("req", "rid", "seq", "rays_o", "rays_d", "radii", "fb",
                 "next_ray", "n_done", "n_rays", "submit_s",
                 "service_start_s", "deadline_abs", "terminal",
                 "degraded", "retries", "fallbacks",
                 "dispatches_at_submit", "trace_span",
                 "bucket_idx", "bucket_next")

    def __init__(self, req: RenderRequest, rid: int, seq: int, now: float):
        self.req, self.rid, self.seq, self.submit_s = req, rid, seq, now
        c2w = R.pose_spherical(req.theta, req.phi, req.radius)
        ro, rd = R.camera_rays(c2w, req.hw, req.hw, 0.9 * req.hw)
        self.rays_o = np.asarray(ro, np.float32).reshape(-1, 3)
        self.rays_d = np.asarray(rd, np.float32).reshape(-1, 3)
        self.radii = None            # (n, 1) pixel radii: cone scenes only
        self.n_rays = self.rays_o.shape[0]
        # NaN framebuffer: a pixel the scatter never wrote — or a padded
        # tail ray leaking into a neighbor — cannot hide as black
        self.fb = np.full((self.n_rays, 3), np.nan, np.float32)
        self.next_ray = 0            # rays handed to tiles so far
        self.n_done = 0              # rays scattered back so far
        self.service_start_s = None  # set when the first ray is tiled
        self.deadline_abs = (None if req.deadline_s is None
                             else now + req.deadline_s)
        self.terminal = False        # a terminal RenderResult exists
        self.degraded = False        # overload policy: coarse-only tiles
        self.retries = 0
        self.fallbacks = 0
        self.dispatches_at_submit = 0   # priority-aging anchor
        self.trace_span = None          # open request-lifecycle span
        self.bucket_idx = None          # per-budget-class ray index lists
        self.bucket_next = None         # per-class hand-out cursors

    @property
    def remaining(self) -> int:
        return self.n_rays - self.next_ray

    def footprint(self, tracer) -> None:
        """The per-pixel cone radii a cone (Mip-NeRF) scene renders with:
        each pixel's distance to its neighbour's unit direction times
        2 / sqrt(12) (``data.rays.pixel_radii``), as an (n, 1) column."""
        hw = self.req.hw
        with tracer.span("request.footprint", cat="request", hw=hw):
            self.radii = R.pixel_radii(hw, hw, 0.9 * hw).reshape(-1, 1)


def _is_cone(pp) -> bool:
    """Whether a resident scene renders cone rays (Mip-NeRF)."""
    return bool(getattr(getattr(pp, "cfg", None), "cone", False))


@dataclass
class _Tile:
    """One coalesced dispatch unit flowing scheduler -> executor ->
    completion. ``spans`` records which request contributed which rays,
    so the completion layer can scatter out of order. ``host_id`` /
    ``prev_host`` only matter under the multi-host cluster
    (``serving.cluster``): the host the tile is placed on, and the last
    host it was actually dispatched on — a re-dispatch on a different
    host is the cross-host failover the cluster counts."""
    scene_id: str
    pp: object                              # resident PackedPlcore
    spans: List[tuple]                      # (_Active, start | idx, take):
    #                                         ``start`` int = contiguous
    #                                         span; ndarray = per-ray
    #                                         indices (adaptive buckets)
    rays_o: np.ndarray
    rays_d: np.ndarray
    n_real: int                             # non-pad rays
    radii: Optional[np.ndarray] = None      # (n, 1) cone radii (Mip-NeRF)
    degraded: bool = False                  # coarse-only program
    budget: Optional[int] = None            # adaptive fine-sample budget
    dead_bucket: bool = False               # rays all hinted-dead: memo
    #                                         recon path, kernel skipped
    host_id: Optional[int] = None           # cluster placement
    prev_host: Optional[int] = None         # last host that dispatched it
    tid: int = -1                           # deterministic trace id


# ---------------------------------------------------------------------------
class AdaptiveSampling:
    """ASDR coordinator shared by scheduler and executor: per-scene
    ``core.pipeline.AdaptiveRenderer`` instances riding the SceneCache.

    The first touch of a scene runs the density-calibration probe
    (``build_scene_aux``) through ``SceneCache.ensure_aux`` — the
    SampleStats + trunk memo become auxiliary residents of the scene's
    cache entry, byte-accounted and evicted WITH it. A renderer is
    rebuilt whenever the resident ``PackedPlcore`` object changed
    (eviction + reload dropped the old aux alongside the old weights),
    so stale stats can never classify rays for fresh weights."""

    def __init__(self, cache: SceneCache, *, budgets=None,
                 memo_mb: float = 32.0, grid_res: int = 32,
                 probe_hw: int = 8):
        self.cache = cache
        self.budgets = tuple(int(b) for b in budgets) if budgets else None
        self.memo_mb = float(memo_mb)
        self.grid_res = int(grid_res)
        self.probe_hw = int(probe_hw)
        self._renderers: Dict[str, object] = {}

    def renderer(self, scene_id: str, pp):
        """The scene's AdaptiveRenderer; probes + builds on first touch
        (the scene is already resident — the scheduler's ``cache.get``
        ran) and rebuilds after a reload."""
        ar = self._renderers.get(scene_id)
        if ar is not None and ar.pp is pp:
            return ar
        from repro.core import pipeline as P
        n_classes = len(self.budgets) if self.budgets else 3
        aux = self.cache.ensure_aux(
            scene_id,
            lambda p: P.build_scene_aux(
                p, grid_res=self.grid_res, n_classes=n_classes,
                memo_mb=self.memo_mb, probe_hw=self.probe_hw))
        ar = P.AdaptiveRenderer(pp, aux, self.budgets)
        self._renderers[scene_id] = ar
        return ar

    def account(self, tile: "_Tile", info: dict, stats: dict) -> None:
        """Fold one adaptive dispatch's info into the engine stats block
        (schema keys from ``SAMPLING_STATS_SCHEMA``) and the labeled
        metric families."""
        stats["adaptive_tiles"] += 1
        stats["dead_rays"] += info["dead"]
        stats["skipped_fine_samples"] += info["skipped_fine_samples"]
        if info["full_dead"]:
            stats["full_dead_tiles"] += 1
        hits = misses = evs = topup = rays = dead = 0
        resident = 0.0
        for ar in self._renderers.values():
            ms = ar.aux.memo.stats()
            hits += ms["hits"]
            misses += ms["misses"]
            evs += ms["evictions"]
            resident += ms["resident_mb"]
            topup += ar.counters["topup_voxels"]
            rays += ar.counters["rays"]
            dead += ar.counters["dead_rays"]
        stats["memo_hits"] = hits
        stats["memo_misses"] = misses
        stats["memo_evictions"] = evs
        stats["memo_topup_voxels"] = topup
        stats["memo_resident_mb"] = round(resident, 3)
        stats["dead_ray_fraction"] = round(dead / rays, 4) if rays else 0.0
        m = getattr(stats, "m", None)
        if m is not None:
            m.budget_tiles.labels(budget_class=info["budget"]).inc()
            m.budget_rays.labels(budget_class=info["budget"]).inc(
                info["rays"])

    def report(self) -> dict:
        """Per-scene ``sampling`` blocks (budget histograms + memo
        traffic) keyed by scene id."""
        return {sid: ar.report()
                for sid, ar in sorted(self._renderers.items())}


# ---------------------------------------------------------------------------
class TileScheduler:
    """Layer 1 — policy. Queue, admission control, priority/sticky-scene
    scene pick (with optional deterministic priority aging), overload
    degradation marking, deadline expiry and tile coalescing. Produces
    ``_Tile``s; never touches the device. Scene-loader failures are
    absorbed here: a scene whose ``SceneCache.get`` raises is skipped for
    the current tile (other scenes keep rendering) and its queued
    requests are terminated once the cache reports ``max_load_failures``
    consecutive real failures."""

    def __init__(self, cache: SceneCache, *, tile_rays: int,
                 max_sticky_tiles: int, stats: dict, clock,
                 max_queue: Optional[int] = None,
                 aging_tiles: Optional[int] = None,
                 degrade_on_overload: bool = False,
                 degrade_queue_tiles: int = 8,
                 degrade_max_priority: int = 0,
                 max_load_failures: int = 3,
                 tile_service_prior_s: Optional[float] = None,
                 adaptive: "Optional[AdaptiveSampling]" = None,
                 tracer=None):
        self.cache = cache
        # adaptive sampling (PR 10): rays classify into fine-sample
        # budget classes and tiles coalesce (scene, budget)-pure — the
        # same purity rule the degraded/full mode split already enforces
        self.adaptive = adaptive
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.tile_rays = int(tile_rays)
        # stickiness bound: after this many consecutive tiles for one
        # scene, the best-ranked request wins even at equal priority —
        # residency amortizes, but an early request for another scene
        # can't be starved forever by a stream of same-priority arrivals
        self.max_sticky_tiles = int(max_sticky_tiles)
        self.stats = stats
        self._clock = clock
        self.max_queue = max_queue
        self.aging_tiles = aging_tiles
        self.degrade_on_overload = bool(degrade_on_overload)
        self.degrade_queue_tiles = int(degrade_queue_tiles)
        self.degrade_max_priority = int(degrade_max_priority)
        self.max_load_failures = int(max_load_failures)
        self.tile_service_prior_s = tile_service_prior_s
        self.queue: List[_Active] = []
        self._seq = 0
        self._tile_seq = 0           # deterministic per-engine tile ids
        self._current_scene: Optional[str] = None
        self._sticky_run = 0         # consecutive tiles for current scene
        self._deadlines_armed = False
        self.completion: Optional["CompletionSink"] = None   # wired by engine
        self.executor: Optional["TileExecutor"] = None       # wired by engine

    # ------------------------------------------------------- admission ----
    def _estimated_queueing_s(self) -> Optional[float]:
        """Predicted wait until a NEW request's first ray is tiled: the
        backlog ahead of it (queued tiles + in-flight slots) times the
        observed per-tile service EWMA. Before the executor has drained a
        tile the estimator falls back to ``tile_service_prior_s`` — the
        cold-start hole (a cold engine under burst load used to admit
        EVERYTHING, then mass-expire once the real service rate showed
        up); with neither observation nor prior it still returns ``None``
        (admit optimistically, the pre-prior behavior)."""
        ewma = (self.stats.get("tile_service_s_ewma")
                or self.tile_service_prior_s)
        if not ewma:
            return None
        backlog = -(-sum(a.remaining for a in self.queue) // self.tile_rays)
        in_flight = self.executor.in_flight if self.executor else 0
        return (backlog + in_flight) * ewma

    def submit(self, req: RenderRequest) -> int:
        """Enqueue a request; returns its request id. A request refused
        by admission control still gets an id — its terminal
        ``rejected`` result is recorded immediately, so every submit is
        answered exactly once."""
        if req.hw < 1:
            raise ValueError(f"request resolution must be >= 1, got "
                             f"hw={req.hw}")
        rid = self._seq
        self._seq += 1
        with self.tracer.span("engine.submit", request=rid,
                              scene=req.scene_id):
            return self._admit(req, rid)

    def _admit(self, req: RenderRequest, rid: int) -> int:
        """The body of ``submit``: build the queue entry (its camera
        rays), then admit or reject it."""
        tr = self.tracer
        with tr.span("request.rays", cat="request", hw=req.hw):
            a = _Active(req, rid, rid, self._clock())
        if _is_cone(self.cache.peek(req.scene_id)):
            a.footprint(tr)
        a.dispatches_at_submit = self.stats["dispatches"]
        if tr.enabled and tr.sampled_request(rid):
            a.trace_span = tr.begin("request", cat="request", request=rid,
                                    scene=req.scene_id, hw=req.hw,
                                    priority=req.priority)
            tr.event("request.submit", cat="request", request=rid,
                     scene=req.scene_id)
        if req.deadline_s is not None:
            self._deadlines_armed = True
        reason = None
        if (self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            reason = (f"queue full ({len(self.queue)} >= "
                      f"max_queue={self.max_queue})")
        elif req.deadline_s is not None:
            est = self._estimated_queueing_s()
            if est is not None and est > req.deadline_s:
                reason = (f"admission control: predicted queueing delay "
                          f"{est:.4f}s exceeds deadline {req.deadline_s}s")
        if reason is not None:
            if a.trace_span is not None:
                tr.event("request.reject", cat="request", request=rid,
                         reason=reason)
            self.completion.terminate(a, "rejected", error=reason)
            return rid
        if a.trace_span is not None:
            tr.event("request.admit", cat="request", request=rid,
                     queue_depth=len(self.queue))
        self.queue.append(a)
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.queue_depth.set(len(self.queue))
            m.queue_depth_hist.observe(len(self.queue))
        self.stats["dispatch_baseline"] += -(-a.n_rays // self.tile_rays)
        return rid

    def remove(self, a: _Active) -> None:
        self.queue.remove(a)

    def expire(self, now: float) -> None:
        """Terminate overdue requests: ``partial`` if any pixels landed,
        ``expired`` otherwise. In-flight tiles referencing a terminated
        request scatter harmlessly into the void (``late_rays``)."""
        if not self._deadlines_armed:
            return
        for a in [a for a in self.queue
                  if a.deadline_abs is not None and now >= a.deadline_abs]:
            self.completion.terminate(
                a, "partial" if a.n_done > 0 else "expired",
                error=f"deadline {a.req.deadline_s}s exceeded")

    # ----------------------------------------------------------- policy ----
    def _eff_priority(self, a: _Active) -> int:
        """Priority with deterministic aging: every ``aging_tiles``
        engine dispatches a request has waited, its effective priority
        rises by one — a low-priority request can be bypassed only
        boundedly often, so overload can't starve it forever. Counted in
        dispatches (not seconds) so closed-loop scheduling decisions
        stay clockless-deterministic."""
        if not self.aging_tiles:
            return a.req.priority
        waited = self.stats["dispatches"] - a.dispatches_at_submit
        return a.req.priority + waited // self.aging_tiles

    def _rank(self, a: _Active):
        return (-self._eff_priority(a), a.seq)

    def _schedulable(self) -> List[_Active]:
        """Requests that still have rays to hand out. Entries whose rays
        are all in flight (dispatched, not yet scattered) stay queued but
        must not influence scene choice — that keeps scheduling decisions
        independent of WHEN the executor drains, so any pipeline depth
        walks the same policy path."""
        return [a for a in self.queue if a.remaining > 0]

    def _pick_scene(self, cands: List[_Active]) -> str:
        """Scene of the best-ranked schedulable request — but sticky to
        the current scene while it still has queued rays at the same top
        priority, so consecutive tiles group by scene (weight residency
        amortizes); a strictly higher-priority request preempts, and
        ``max_sticky_tiles`` bounds how long an equal-priority request
        for another scene can be bypassed."""
        best = min(cands, key=self._rank)
        if (self._current_scene is not None
                and self._sticky_run < self.max_sticky_tiles):
            mine = [self._eff_priority(a) for a in cands
                    if a.req.scene_id == self._current_scene]
            if mine and self._eff_priority(best) <= max(mine):
                return self._current_scene
        return best.req.scene_id

    def _mark_degraded(self, cands: List[_Active]) -> None:
        """Overload degradation: when the queued backlog exceeds
        ``degrade_queue_tiles`` tiles, requests at or below
        ``degrade_max_priority`` that have NOT started rendering are
        switched to the coarse-only program for their whole image (a
        request never mixes qualities). Flagged in stats and in the
        terminal status (``degraded``) — controlled degradation is a
        policy, not a silent corner cut."""
        if not self.degrade_on_overload:
            return
        backlog = -(-sum(a.remaining for a in cands) // self.tile_rays)
        if backlog <= self.degrade_queue_tiles:
            return
        for a in cands:
            if (not a.degraded and a.service_start_s is None
                    and self._eff_priority(a) <= self.degrade_max_priority):
                a.degraded = True
                self.stats["degraded_requests"] += 1

    def _cone_refusals(self) -> List[str]:
        """The armed modes that do not render cone (Mip-NeRF) scenes: a
        cone scene's first tile raises a ValueError naming the first."""
        modes = []
        if self.adaptive is not None:
            modes.append("adaptive sampling")
        if self.degrade_on_overload:
            modes.append("coarse_only degradation (degrade_on_overload)")
        return modes

    def _note_load_failure(self, scene: str, err: SceneLoadError) -> None:
        """Account one failed ``cache.get`` and, once the cache reports
        ``max_load_failures`` consecutive REAL loader failures for the
        scene, declare it dead: terminate every queued request for it
        (``partial`` if pixels already landed, else ``rejected``) so the
        serving loop always makes progress past a dead scene."""
        key = "scene_load_fail_fasts" if err.fail_fast else "scene_load_errors"
        self.stats[key] += 1
        if (not err.fail_fast
                and self.cache.consecutive_failures(scene)
                >= self.max_load_failures):
            for a in [a for a in self.queue if a.req.scene_id == scene]:
                self.completion.terminate(
                    a, "partial" if a.n_done > 0 else "rejected",
                    error=f"scene load failed: {err}")

    def _resolve_scene(self):
        """Pick the best loadable scene and its resident weights:
        ``(scene_id, pp, cands, host_id)`` or ``None`` when no request
        has rays left to hand out (or every candidate scene's loader is
        failing — their requests stay queued through the cache's backoff
        window and are terminated when the scene is declared dead).
        ``host_id`` is always ``None`` here; the multi-host
        ``ClusterScheduler`` overrides this to fold host placement into
        the same decision."""
        tried = set()
        while True:
            cands = [a for a in self._schedulable()
                     if a.req.scene_id not in tried]
            if not cands:
                return None
            self._mark_degraded(cands)
            scene = self._pick_scene(cands)
            try:
                pp = self.cache.get(scene)
            except SceneLoadError as e:
                tried.add(scene)
                self._note_load_failure(scene, e)
                continue
            return scene, pp, cands, None

    def next_tile(self) -> Optional[_Tile]:
        """Coalesce ONE tile from the best loadable scene's pending
        requests in queue order (scene + residency resolution in
        ``_resolve_scene``); ``None`` when nothing is schedulable. A
        produced tile is traced as ``tile.coalesce``: a step that finds
        every ray handed out (at depth >= 2 it goes on to drain) opens
        no span, so the profile's annotations match the ring's spans."""
        if not any(a.remaining > 0 for a in self.queue):
            return None
        tr = self.tracer
        with tr.span("tile.coalesce", cat="tile") as sp:
            t0 = self._clock() if sp is None else None
            tile = self._coalesce()
            if tile is None:
                tr.discard(sp)
                return None
            if sp is not None:
                sp.attrs.update(
                    tile=tile.tid, scene=tile.scene_id, rays=tile.n_real,
                    pad=len(tile.rays_o) - tile.n_real,
                    requests=len(tile.spans), host=tile.host_id,
                    degraded=tile.degraded, budget_class=tile.budget)
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.coalesce_seconds.observe(self._clock() - t0 if sp is None
                                       else sp.t1 - sp.t0)
        return tile

    def _coalesce(self) -> Optional[_Tile]:
        resolved = self._resolve_scene()
        if resolved is None:
            return None
        scene, pp, cands, host_id = resolved
        cone = _is_cone(pp)
        refused = self._cone_refusals() if cone else []
        if refused:
            raise ValueError(f"{refused[0]} does not render cone "
                             f"(Mip-NeRF) scenes: scene {scene!r}")
        if scene != self._current_scene:
            self.stats["scene_switches"] += 1
            self._current_scene = scene
            self._sticky_run = 0
        self._sticky_run += 1

        now = self._clock()
        scene_cands = sorted((a for a in cands if a.req.scene_id == scene),
                             key=self._rank)
        # a tile is mode-pure: degraded (coarse-only) and full-quality
        # rays can't share a dispatch program, so coalesce only requests
        # matching the best-ranked candidate's mode
        degraded = scene_cands[0].degraded
        # ... and under adaptive sampling BUDGET-pure: every ray in the
        # tile renders at one budget class's n_fine, so the fixed-shape
        # per-budget program is reused and no ray is over/under-sampled
        # by its tile-mates. Classification is lazy (first coalesce touch
        # of each request — the scene's calibration stats are resident by
        # then); the bucket served is the best-ranked candidate's first
        # non-exhausted class.
        bucket = budget = None
        if self.adaptive is not None and not degraded:
            ar = self.adaptive.renderer(scene, pp)
            for a in scene_cands:
                if a.bucket_idx is None:
                    cls = ar.classify_rays(a.rays_o, a.rays_d)
                    hint = ar.dead_hint(a.rays_o, a.rays_d)
                    # hinted-dead rays (provably empty from the stats —
                    # always class 0, since their score is below the
                    # first quantile edge) get a dedicated extra bucket:
                    # coalesced across requests they form tiles that
                    # resolve fully dead at the executor and skip the
                    # kernel dispatch entirely
                    a.bucket_idx = [np.nonzero((cls == c) & ~hint)[0]
                                    for c in range(len(ar.budgets))]
                    a.bucket_idx.append(np.nonzero(hint)[0])
                    a.bucket_next = [0] * len(a.bucket_idx)
            a0 = scene_cands[0]
            bucket = next(c for c in range(len(a0.bucket_idx))
                          if len(a0.bucket_idx[c]) > a0.bucket_next[c])
            # the dead bucket renders at the lowest budget — its rays are
            # all class 0, and any that resolve alive (memo top-up cap)
            # render in-kernel at exactly their class's n_fine
            budget = int(ar.budgets[min(bucket, len(ar.budgets) - 1)]
                         if bucket < len(ar.budgets) else ar.budgets[0])
        spans, chunks_o, chunks_d, chunks_r, n = [], [], [], [], 0
        for a in scene_cands:
            if a.degraded != degraded:
                continue
            if cone and a.radii is None:
                # the scene was not resident when the request arrived
                a.footprint(self.tracer)
            if bucket is not None:
                avail = a.bucket_idx[bucket]
                cur = a.bucket_next[bucket]
                take = min(len(avail) - cur, self.tile_rays - n)
                if take <= 0:
                    continue
                idx = avail[cur:cur + take]
                if a.service_start_s is None:
                    a.service_start_s = now
                spans.append((a, idx, take))
                chunks_o.append(a.rays_o[idx])
                chunks_d.append(a.rays_d[idx])
                a.bucket_next[bucket] = cur + take
            else:
                take = min(a.remaining, self.tile_rays - n)
                if take <= 0:
                    continue
                if a.service_start_s is None:
                    a.service_start_s = now
                spans.append((a, a.next_ray, take))
                chunks_o.append(a.rays_o[a.next_ray:a.next_ray + take])
                chunks_d.append(a.rays_d[a.next_ray:a.next_ray + take])
                if cone:
                    chunks_r.append(a.radii[a.next_ray:a.next_ray + take])
            a.next_ray += take
            n += take
            if n == self.tile_rays:
                break
        # adaptive bucket tiles SHRINK to the next power of two when the
        # bucket drained below tile_rays: a 40-ray minority class must
        # not pad out to a full-size kernel dispatch. Shapes stay
        # canonical (pow2 in [32, tile_rays]) so the per-budget program
        # cache stays bounded; the static path keeps fixed-size tiles.
        target = self.tile_rays
        if bucket is not None and n < target:
            target = min(target,
                         max(32, 1 << int(np.ceil(np.log2(max(n, 2))))))
        pad = target - n
        if pad:                       # tail tile: repeat the last real ray
            chunks_o.append(np.repeat(chunks_o[-1][-1:], pad, axis=0))
            chunks_d.append(np.repeat(chunks_d[-1][-1:], pad, axis=0))
            if cone:
                chunks_r.append(np.repeat(chunks_r[-1][-1:], pad, axis=0))
            self.stats["padded_rays"] += pad
        tid = self._tile_seq
        self._tile_seq += 1
        tile = _Tile(scene, pp, spans, np.concatenate(chunks_o),
                     np.concatenate(chunks_d), n, degraded=degraded,
                     budget=budget,
                     dead_bucket=(bucket is not None
                                  and bucket >= len(ar.budgets)),
                     host_id=host_id, tid=tid,
                     radii=np.concatenate(chunks_r) if cone else None)
        return tile


# ---------------------------------------------------------------------------
class TileExecutor:
    """Layer 2 — dispatch. A ring of up to ``depth`` in-flight tile
    slots over jax async dispatch: ``dispatch`` enqueues the device
    program and returns without blocking; the oldest slot is drained
    (host-synced and handed to completion) only when the ring is full or
    at an explicit flush. ``depth=1`` drains every dispatch immediately —
    exactly the synchronous loop.

    Failure handling lives at the executor's two trust boundaries. A
    dispatch that RAISES, or a drained buffer with non-finite real rays
    (the NaN scatter sentinel means corruption cannot hide), enters the
    synchronous retry ladder: up to ``max_tile_retries`` fresh dispatches
    with capped exponential backoff, then the bit-exact oracle program —
    so a recovered tile's pixels are identical to a healthy one's and
    ``dispatch``/``drain_one`` never raise for these fault classes. The
    optional ``StragglerMonitor`` watches per-tile in-flight latency and
    abandons+redispatches tiles that blow past its deadline factor. A
    ``FaultPlan`` (chaos testing) injects failures at exactly these
    boundaries; the ladder and oracle are never wrapped."""

    def __init__(self, completion: "CompletionSink", cache: SceneCache,
                 stats: dict, depth: int = 1, *,
                 faults: Optional[FaultPlan] = None,
                 straggler=None, max_tile_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 max_retry_backoff_s: float = 0.05,
                 check_finite: bool = True, clock=time.perf_counter,
                 sleep=time.sleep, redispatch_hook=None, tracer=None,
                 adaptive: "Optional[AdaptiveSampling]" = None):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.completion = completion
        self.cache = cache
        self.stats = stats
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.depth = int(depth)
        self.faults = faults
        self.straggler = straggler
        # adaptive sampling (PR 10): budget-stamped tiles route through
        # the scene's AdaptiveRenderer (budgeted n_fine + memo-dead rays)
        # instead of the static full-budget dispatch
        self.adaptive = adaptive
        # cluster failover: tried BEFORE the local retry ladder — a tile
        # that failed here is first offered to a DIFFERENT host; only
        # when the hook declines (returns None) does the local
        # retry -> oracle ladder run as the last rung
        self.redispatch_hook = redispatch_hook
        self.max_tile_retries = int(max_tile_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.max_retry_backoff_s = float(max_retry_backoff_s)
        self.check_finite = bool(check_finite)
        self._clock = clock
        self._sleep = sleep             # injectable alongside the clock
        self._slots: deque = deque()    # (tile, rgb, t0, extra_s, span)
        # when the last drained tile was materialized: at depth >= 2 the
        # next tile was enqueued behind it and only starts then, so its
        # service is measured from there, not from dispatch
        self._last_drain: Optional[float] = None

    @property
    def in_flight(self) -> int:
        return len(self._slots)

    # ------------------------------------------------------- internals ----
    def _attempt(self, tile: _Tile, allow_straggle: bool = True):
        """ONE dispatch attempt through the fault plan. Returns
        ``(device_rgb, injected_extra_latency_s)``; raises on an
        (injected or real) dispatch failure."""
        fault = (self.faults.draw_dispatch(allow_straggle=allow_straggle)
                 if self.faults is not None else None)
        if fault is not None and fault["kind"] == "dispatch_error":
            raise InjectedDispatchError(
                f"injected dispatch failure (tile scene={tile.scene_id})")
        tr = self.tracer
        extra = (fault["extra_s"]
                 if fault is not None and fault["kind"] == "straggle"
                 else 0.0)
        if tile.budget is not None and self.adaptive is not None:
            # adaptive path: budget-stamped tile renders at its class's
            # n_fine with memo-dead rays masked out of the fused kernel
            ar = self.adaptive.renderer(tile.scene_id, tile.pp)
            rgb, info = ar.render_tile(tile.rays_o, tile.rays_d,
                                       budget=tile.budget,
                                       resolve_dead=tile.dead_bucket)
            self.adaptive.account(tile, info, self.stats)
            if tr.enabled:
                tr.event("tile.adaptive", cat="tile", tile=tile.tid,
                         host=tile.host_id, budget_class=tile.budget,
                         dead=info["dead"], full_dead=info["full_dead"])
            return rgb, extra
        footprint = {}
        with tr.span("tile.commit", cat="tile", tile=tile.tid):
            o = tile.pp.commit(tile.rays_o)
            d = tile.pp.commit(tile.rays_d)
            if tile.radii is not None:
                footprint["radii"] = tile.pp.commit(tile.radii)
        rgb, _ = tile.pp.dispatch_tile(
            o, d, coarse_only=tile.degraded, **footprint,
            tracer=tr if tr.enabled else None,
            trace_attrs={"tile": tile.tid, "host": tile.host_id,
                         "scene": tile.scene_id} if tr.enabled else None)
        return rgb, extra

    def _is_finite(self, arr: np.ndarray, tile: _Tile) -> bool:
        """Real (non-pad) rays must be finite. Checked whenever
        ``check_finite`` is on (the default) or faults are injected;
        with both off the check — and its cost — disappears."""
        if not self.check_finite and self.faults is None:
            return True
        return bool(np.isfinite(arr[:tile.n_real]).all())

    def _bump_retries(self, tile: _Tile) -> None:
        for a, _, _ in tile.spans:
            if not a.terminal:
                a.retries += 1

    def _resolve_sync(self, tile: _Tile):
        """The synchronous retry ladder for a tile whose primary
        dispatch failed or drained corrupt: up to ``max_tile_retries``
        fresh dispatches (each a new fault-plan event, so transient
        faults clear; capped exponential backoff between attempts), then
        the bit-exact oracle program — which the fault plan never
        touches. Returns the finite rgb ndarray; retry attempts are
        accounted per tile and per touched request, the oracle rung as
        ``oracle_fallbacks``."""
        st = self.stats
        tr = self.tracer
        if self.redispatch_hook is not None:
            # cross-host failover outranks the local ladder: a tile that
            # failed on THIS host is redispatched to a different healthy
            # one (bit-exact — same scene weights, per-ray independence);
            # the local retry -> oracle ladder is the last rung, taken
            # only when no other host can serve the tile
            resolved = self.redispatch_hook(tile)
            if resolved is not None:
                return resolved
        for attempt in range(self.max_tile_retries):
            st["tile_retries"] += 1
            self._bump_retries(tile)
            if tr.enabled:
                tr.event("tile.retry", cat="tile", tile=tile.tid,
                         host=tile.host_id, attempt=attempt + 1)
            if self.retry_backoff_s > 0.0:
                self._sleep(min(self.retry_backoff_s * (2 ** attempt),
                                self.max_retry_backoff_s))
            try:
                rgb, _ = self._attempt(tile, allow_straggle=False)
            except Exception:
                st["dispatch_errors"] += 1
                continue
            arr = np.asarray(rgb)
            if self.faults is not None:
                bad = self.faults.corrupt_tile(arr)
                if bad is not None:
                    arr = bad
            if self._is_finite(arr, tile):
                return arr
            st["corrupt_tiles"] += 1
        st["oracle_fallbacks"] += 1
        if tr.enabled:
            tr.event("tile.fallback", cat="tile", tile=tile.tid,
                     host=tile.host_id)
        for a, _, _ in tile.spans:
            if not a.terminal:
                a.fallbacks += 1
        o = tile.pp.commit(tile.rays_o)
        d = tile.pp.commit(tile.rays_d)
        footprint = ({} if tile.radii is None
                     else {"radii": tile.pp.commit(tile.radii)})
        return np.asarray(
            tile.pp.render_tile(o, d, coarse_only=True) if tile.degraded
            else tile.pp.render_tile_oracle(o, d, **footprint))

    def _account(self, tile: _Tile) -> None:
        st = self.stats
        st["dispatches"] += 1
        st["rays_rendered"] += tile.n_real
        if tile.degraded:
            st["degraded_tiles"] += 1

    def _update_service_ewma(self, dt: float) -> None:
        prev = self.stats.get("tile_service_s_ewma")
        self.stats["tile_service_s_ewma"] = (
            dt if not prev else 0.7 * prev + 0.3 * dt)
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.service_seconds.observe(dt)

    # ----------------------------------------------------------- public ----
    def dispatch(self, tile: _Tile) -> None:
        """Issue one tile (non-blocking), pin its scene for the life of
        the slot, account it, then drain down to ``depth - 1`` so at most
        ``depth`` programs are ever enqueued. A dispatch-time failure is
        resolved SYNCHRONOUSLY through the retry ladder (it never occupies
        a slot) — this method does not raise for handled fault classes."""
        self.cache.pin(tile.scene_id)
        tr = self.tracer
        if tr.enabled:
            tr.event("tile.dispatch", cat="tile", tile=tile.tid,
                     scene=tile.scene_id, host=tile.host_id,
                     slot=len(self._slots), degraded=tile.degraded)
        try:
            rgb, extra = self._attempt(tile)
        except Exception as e:
            self.stats["dispatch_errors"] += 1
            if tr.enabled:
                tr.event("tile.dispatch_error", cat="tile", tile=tile.tid,
                         host=tile.host_id, error=str(e)[:120])
            arr = self._resolve_sync(tile)
            self._account(tile)
            self.completion.scatter(tile, arr)
            self.cache.unpin(tile.scene_id)
            return
        sp = (tr.begin("tile.device_compute", cat="tile", tile=tile.tid,
                       host=tile.host_id, slot=len(self._slots))
              if tr.enabled else None)
        overlapped = bool(self._slots)
        self._slots.append((tile, rgb, self._clock(), extra, sp))
        self._account(tile)
        self.stats["max_in_flight"] = max(self.stats["max_in_flight"],
                                          len(self._slots))
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.in_flight_tiles.set(len(self._slots))
            if overlapped:
                m.overlapped_dispatches.inc()
        while len(self._slots) >= self.depth:
            self.drain_one()

    def drain_one(self) -> bool:
        """Materialize the OLDEST in-flight tile (the only host sync in
        the loop), recover it if it drained corrupt or straggled, scatter
        it, release its scene pin. Never raises for handled faults."""
        if not self._slots:
            return False
        self._finish_slot(*self._slots.popleft())
        return True

    def _finish_slot(self, tile, rgb, t0, extra, sp) -> None:
        """``drain_one``'s body: materialize, recover if
        corrupt/straggled, scatter, unpin. Traced, the materialization is
        split into the wait for the device and the copy to the host. The
        tile's service runs from the later of its dispatch and the
        previous drain: a tile enqueued behind another waits for it on
        the device, and that wait is the older tile's service, not this
        one's."""
        tr = self.tracer
        start = t0 if self._last_drain is None else max(t0, self._last_drain)
        if tr.enabled:
            with tr.span("tile.wait", cat="tile", tile=tile.tid):
                jax.block_until_ready(rgb)
            with tr.span("tile.fetch", cat="tile", tile=tile.tid):
                arr = np.asarray(rgb)
        else:
            arr = np.asarray(rgb)
        tr.end(sp)
        if tr.enabled:
            tr.event("tile.drain", cat="tile", tile=tile.tid,
                     host=tile.host_id)
        if self.faults is not None:
            bad = self.faults.corrupt_tile(arr)
            if bad is not None:
                arr = bad
        redispatched = False
        if self.straggler is not None:
            # effective in-flight latency includes any injected straggle;
            # past the monitor's deadline the slow result is abandoned
            # and the tile redispatched fresh (under the cluster, on
            # another host first; here it models cutting the loss instead
            # of stalling the drain point)
            verdict = self.straggler.record_step(
                self._clock() - start + extra)
            if verdict["deadline_exceeded"]:
                self.stats["straggler_redispatches"] += 1
                if tr.enabled:
                    tr.event("tile.straggler_redispatch", cat="tile",
                             tile=tile.tid, host=tile.host_id)
                arr = self._resolve_sync(tile)
                redispatched = True
            elif extra > 0.0:
                self._sleep(extra)    # the monitor missed it: pay the stall
                self.stats["straggle_wait_s"] += extra
        elif extra > 0.0:
            self._sleep(extra)
            self.stats["straggle_wait_s"] += extra
        if not redispatched and not self._is_finite(arr, tile):
            self.stats["corrupt_tiles"] += 1
            if tr.enabled:
                tr.event("tile.corrupt", cat="tile", tile=tile.tid,
                         host=tile.host_id)
            arr = self._resolve_sync(tile)
        now = self._clock()
        self._last_drain = now
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.in_flight_tiles.set(len(self._slots))
        self._update_service_ewma(now - start)
        self.completion.scatter(tile, arr)
        self.cache.unpin(tile.scene_id)

    def drain_all(self) -> None:
        while self.drain_one():
            pass

    def abandon_all(self) -> List[_Tile]:
        """Drop every in-flight slot WITHOUT materializing its result
        (the device arrays of a dead host are unreachable) and release
        the scene pins; returns the abandoned tiles so the cluster can
        re-queue them for dispatch on a different host. Their rays were
        already handed out by the scheduler, so re-queueing the tiles —
        not rewinding the requests — is what keeps every submit answered
        exactly once."""
        tiles = []
        tr = self.tracer
        while self._slots:
            tile, _rgb, _t0, _extra, sp = self._slots.popleft()
            tr.end(sp, abandoned=True)
            if tr.enabled:
                tr.event("tile.abandon", cat="tile", tile=tile.tid,
                         host=tile.host_id)
            self.cache.unpin(tile.scene_id)
            tiles.append(tile)
        return tiles


# ---------------------------------------------------------------------------
class CompletionSink:
    """Layer 3 — output. Scatters drained tiles to per-request
    framebuffers and completes requests out of order as their last ray
    lands — and owns TERMINATION: every request ends here exactly once,
    whether it rendered (``ok``/``degraded``), timed out (``partial``/
    ``expired``) or was refused (``rejected``)."""

    def __init__(self, scheduler: TileScheduler, stats: dict, clock,
                 check_finite: bool = True, tracer=None):
        self.scheduler = scheduler
        self.stats = stats
        self._clock = clock
        self.check_finite = bool(check_finite)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.completed: Dict[int, RenderResult] = {}
        self.completion_order: List[int] = []

    def scatter(self, tile: _Tile, rgb: np.ndarray) -> None:
        with self.tracer.span("tile.scatter", cat="tile", tile=tile.tid,
                              scene=tile.scene_id,
                              host=tile.host_id) as sp:
            t0 = self._clock() if sp is None else None
            late = self._write(tile, rgb)
            if sp is not None:
                sp.attrs["late"] = late
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.scatter_seconds.observe(self._clock() - t0 if sp is None
                                      else sp.t1 - sp.t0)

    def _write(self, tile: _Tile, rgb: np.ndarray) -> int:
        """``scatter``'s body: the tile's pixels into each contributing
        request's framebuffer; returns the rays that landed too late."""
        off = 0
        late = 0
        for a, start, take in tile.spans:
            if a.terminal:
                # request already reached a terminal status (expired /
                # rejected mid-flight): its late pixels drop harmlessly
                self.stats["late_rays"] += take
                late += take
                off += take
                continue
            if isinstance(start, np.ndarray):
                # budget-bucketed tile: this span is a gather of the
                # request's rays for ONE class, scattered by index
                a.fb[start] = rgb[off:off + take]
            else:
                a.fb[start:start + take] = rgb[off:off + take]
            a.n_done += take
            off += take
            if a.n_done == a.n_rays:
                self._complete(a)
        return late

    def _finish(self, a: _Active, status: str,
                error: Optional[str] = None) -> None:
        a.terminal = True
        if a in self.scheduler.queue:
            self.scheduler.remove(a)
        hw = a.req.hw
        res = RenderResult(
            request_id=a.rid, scene_id=a.req.scene_id,
            image=a.fb.reshape(hw, hw, 3), n_rays=a.n_rays,
            submit_s=a.submit_s,
            service_start_s=(a.submit_s if a.service_start_s is None
                             else a.service_start_s),
            complete_s=self._clock(),
            dispatch_baseline=-(-a.n_rays // self.scheduler.tile_rays),
            status=status, error=error, retries=a.retries,
            fallbacks=a.fallbacks)
        self.completed[a.rid] = res
        self.completion_order.append(a.rid)
        self.stats["requests_completed"] += 1
        counts = self.stats["status_counts"]
        counts[status] = counts.get(status, 0) + 1
        sp = a.trace_span
        if sp is not None:
            a.trace_span = None
            tr = self.tracer
            tr.event("request.complete", cat="request", request=a.rid,
                     status=status)
            tr.end(sp, status=status)
        m = getattr(self.stats, "m", None)
        if m is not None:
            m.queue_depth.set(len(self.scheduler.queue))
            if res.delivered:
                m.request_latency_seconds.observe(res.latency_s)

    def _complete(self, a: _Active) -> None:
        if self.check_finite and not np.isfinite(a.fb).all():
            # fully-scattered framebuffer with a non-finite pixel: the
            # recovery ladder guarantees finite tiles, so this is an
            # ENGINE INVARIANT violation (scatter gap / leaked sentinel),
            # not a handled fault class — surface it loudly rather than
            # ship a poisoned image (disable via check_finite=False)
            bad = int((~np.isfinite(a.fb)).any(axis=-1).sum())
            raise RuntimeError(
                f"delivered framebuffer for request {a.rid} "
                f"(scene {a.req.scene_id!r}) has {bad} non-finite pixels "
                f"— NaN scatter sentinel not fully overwritten")
        self._finish(a, "degraded" if a.degraded else "ok")

    def terminate(self, a: _Active, status: str,
                  error: Optional[str] = None) -> None:
        """Force a request to a terminal status (expiry, rejection, dead
        scene). Idempotent: the first terminal status wins."""
        if a.terminal:
            return
        self._finish(a, status, error)


# ---------------------------------------------------------------------------
class RenderEngine:
    """Continuous-batching serving loop over a ``SceneCache`` — the
    scheduler/executor/completion stack behind one façade.

    ``tile_rays`` is the fixed dispatch shape — every tile that reaches
    the device has exactly this many rays (the compiled tile program is
    reused forever), and only a tail tile carries padding.
    ``pipeline_depth`` bounds the executor's in-flight slots (default
    ``DEFAULT_PIPELINE_DEPTH`` = 2, which overlaps the host's commit,
    dispatch, copy back and scatter with device compute; 1 =
    synchronous, the bit-identical baseline).

    Fault-tolerance knobs (all default to the pre-fault behavior):
    ``max_queue`` bounds the request queue (admission rejects beyond);
    requests with a ``deadline_s`` get SLO admission control + expiry;
    ``aging_tiles`` arms deterministic priority aging;
    ``degrade_on_overload`` arms coarse-only rendering for low-priority
    requests under backlog; ``max_tile_retries``/``retry_backoff_s``
    shape the per-tile retry ladder; ``faults`` injects a seeded
    ``FaultPlan``; ``straggler_mitigation`` wires the
    ``runtime.straggler`` monitor into the executor (default: on exactly
    when faults are injected, so clean deterministic runs stay
    timing-insensitive); ``check_finite`` asserts delivered framebuffers
    are finite (on by default — a leaked NaN pixel must not ship
    silently); ``tile_service_prior_s`` seeds the admission-control
    service estimate before any tile has drained, closing the cold-start
    hole where a burst at an empty engine was admitted wholesale and
    then mass-expired once the real service rate showed up."""

    def __init__(self, cache: SceneCache, *, tile_rays: int = 512,
                 max_sticky_tiles: int = 64, clock=time.perf_counter,
                 pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
                 max_queue: Optional[int] = None,
                 aging_tiles: Optional[int] = None,
                 degrade_on_overload: bool = False,
                 degrade_queue_tiles: int = 8,
                 degrade_max_priority: int = 0,
                 max_load_failures: int = 3,
                 max_tile_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 faults: Optional[FaultPlan] = None,
                 straggler_mitigation: Optional[bool] = None,
                 straggler_cfg=None,
                 check_finite: bool = True,
                 tile_service_prior_s: Optional[float] = None,
                 adaptive_sampling: bool = False,
                 budget_classes=None,
                 memo_mb: float = 32.0,
                 adaptive_grid_res: int = 32,
                 adaptive_probe_hw: int = 8,
                 tracer=None, registry=None):
        if adaptive_sampling and degrade_on_overload:
            raise ValueError("adaptive_sampling and degrade_on_overload "
                             "both rewrite the per-ray sample budget — "
                             "arm one")
        self.cache = cache
        self.faults = faults
        self._clock = clock
        # observability: a per-engine registry backs the stats dict (the
        # keys, order and value types come from ENGINE_STATS_SCHEMA —
        # the old literal dict, now registry-derived so a counter can't
        # be read before initialization), and the tracer records the
        # request/tile lifecycle; NULL_TRACER no-ops when tracing is off
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats = engine_stats_view(self.registry)
        watch_compiles()
        self.adaptive: Optional[AdaptiveSampling] = None
        if adaptive_sampling:
            # sampling extension block, bound ONLY when adaptive
            # sampling is on so the default stats stay byte-identical
            from repro.obs.metrics import (SAMPLING_STATS_SCHEMA,
                                           extend_stats_view)
            extend_stats_view(self.stats, SAMPLING_STATS_SCHEMA)
            self.adaptive = AdaptiveSampling(
                cache, budgets=budget_classes, memo_mb=memo_mb,
                grid_res=adaptive_grid_res, probe_hw=adaptive_probe_hw)
        cache.tracer = self.tracer
        self.scheduler = TileScheduler(
            cache, tile_rays=tile_rays, max_sticky_tiles=max_sticky_tiles,
            stats=self.stats, clock=clock,
            max_queue=max_queue, aging_tiles=aging_tiles,
            degrade_on_overload=degrade_on_overload,
            degrade_queue_tiles=degrade_queue_tiles,
            degrade_max_priority=degrade_max_priority,
            max_load_failures=max_load_failures,
            tile_service_prior_s=tile_service_prior_s,
            adaptive=self.adaptive, tracer=self.tracer)
        self.completion = CompletionSink(self.scheduler, self.stats, clock,
                                         check_finite=check_finite,
                                         tracer=self.tracer)
        if straggler_mitigation is None:
            straggler_mitigation = faults is not None
        monitor = None
        if straggler_mitigation:
            from repro.runtime.straggler import (StragglerConfig,
                                                 StragglerMonitor)
            monitor = StragglerMonitor(
                straggler_cfg if straggler_cfg is not None
                else StragglerConfig(warmup_steps=2, deadline_factor=4.0,
                                     ewma_alpha=0.2))
        self.executor = TileExecutor(
            self.completion, cache, self.stats, depth=pipeline_depth,
            faults=faults, straggler=monitor,
            max_tile_retries=max_tile_retries,
            retry_backoff_s=retry_backoff_s,
            check_finite=check_finite, clock=clock, tracer=self.tracer,
            adaptive=self.adaptive)
        # admission control needs the in-flight count; termination needs
        # the sink — wire the cross-layer references the façade owns
        self.scheduler.completion = self.completion
        self.scheduler.executor = self.executor

    # ------------------------------------------------------------ queue ----
    @property
    def tile_rays(self) -> int:
        return self.scheduler.tile_rays

    @property
    def pipeline_depth(self) -> int:
        return self.executor.depth

    @property
    def pending(self) -> int:
        """Requests not yet completed (queued, partially tiled, or fully
        in flight awaiting their scatter)."""
        return len(self.scheduler.queue)

    @property
    def pending_rays(self) -> int:
        return sum(a.remaining for a in self.scheduler.queue)

    @property
    def in_flight_tiles(self) -> int:
        return self.executor.in_flight

    @property
    def completed(self) -> Dict[int, RenderResult]:
        return self.completion.completed

    @property
    def completion_order(self) -> List[int]:
        return self.completion.completion_order

    def submit(self, req: RenderRequest) -> int:
        """Enqueue a request; returns its request id. Admission control
        may terminate it immediately (status ``rejected``) — the result
        is then already in ``completed``."""
        return self.scheduler.submit(req)

    # ------------------------------------------------------------- loop ----
    def step(self) -> bool:
        """One engine iteration: expire overdue requests, then coalesce
        + dispatch the next tile if any request still has rays to hand
        out, else drain one in-flight slot. Returns False only when
        fully idle (no schedulable rays AND nothing in flight). At
        ``pipeline_depth=1`` each step is exactly the synchronous
        coalesce -> dispatch -> block -> scatter of the pre-pipelined
        engine. Never raises for handled fault classes (dispatch
        failures, corrupt tiles, loader errors, stragglers)."""
        with self.tracer.span("engine.step"):
            self.scheduler.expire(self._clock())
            tile = self.scheduler.next_tile()
            if tile is not None:
                self.executor.dispatch(tile)
                return True
            if self.executor.in_flight:
                self.executor.drain_one()
                return True
            return False

    def take(self, request_id: int) -> RenderResult:
        """Pop a completed result, releasing its framebuffer. Long-running
        servers must consume results through this (``completed`` retains
        every image otherwise — fine for bounded traces/tests only)."""
        return self.completion.completed.pop(request_id)

    def drain(self, max_steps: Optional[int] = None) -> int:
        """Run until idle — queue empty AND every in-flight slot flushed
        (or ``max_steps``); returns steps taken. Termination holds under
        faults: every step either dispatches, drains, or advances a
        failing scene toward dead-scene termination."""
        steps = 0
        while ((self.scheduler.queue or self.executor.in_flight)
               and (max_steps is None or steps < max_steps)):
            self.step()
            steps += 1
        return steps

    # ------------------------------------------------------- reporting ----
    def robustness(self) -> dict:
        """The fault-accounting summary the loadgen/bench/CI chaos paths
        persist: per-status terminal counts, goodput (delivered ok or
        degraded / all terminal), the retry/fallback ladder counters,
        and — when a ``FaultPlan`` is armed — what it injected."""
        st = self.stats
        counts = dict(st["status_counts"])
        n = sum(counts.values())
        good = counts.get("ok", 0) + counts.get("degraded", 0)
        out = {
            "status_counts": counts,
            "goodput": round(good / n, 4) if n else None,
            "tile_retries": st["tile_retries"],
            "oracle_fallbacks": st["oracle_fallbacks"],
            "corrupt_tiles": st["corrupt_tiles"],
            "dispatch_errors": st["dispatch_errors"],
            "scene_load_errors": st["scene_load_errors"],
            "scene_load_fail_fasts": st["scene_load_fail_fasts"],
            "straggler_redispatches": st["straggler_redispatches"],
            "degraded_requests": st["degraded_requests"],
            "late_rays": st["late_rays"],
        }
        if self.faults is not None:
            out["faults_injected"] = self.faults.summary()
        return out

    def sampling_report(self) -> Optional[dict]:
        """Adaptive-sampling summary (``None`` unless the engine runs
        with ``adaptive_sampling``): the engine-wide totals from the
        sampling stats block plus per-scene budget histograms and memo
        traffic — what the bench's ``serving.adaptive`` block and
        serve.py's ``--check`` sampling gates persist."""
        if self.adaptive is None:
            return None
        st = self.stats
        return {
            "adaptive_tiles": st["adaptive_tiles"],
            "full_dead_tiles": st["full_dead_tiles"],
            "dead_rays": st["dead_rays"],
            "dead_ray_fraction": st["dead_ray_fraction"],
            "skipped_fine_samples": st["skipped_fine_samples"],
            "memo_hits": st["memo_hits"],
            "memo_misses": st["memo_misses"],
            "memo_evictions": st["memo_evictions"],
            "memo_topup_voxels": st["memo_topup_voxels"],
            "memo_resident_mb": st["memo_resident_mb"],
            "scenes": self.adaptive.report(),
        }
