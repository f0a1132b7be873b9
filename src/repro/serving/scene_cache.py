"""Multi-scene weight cache — FlexNeRFer-style (2505.06504) model
residency for the serving engine.

One process serves many scenes, but packing a scene's weights into the
kernel layout (``stack_plcore_weights`` + RMCM quantization) is load-time
work the render path must never repeat (``kernels.ops.pack_count`` is the
proof obligation). ``SceneCache`` keeps a capacity-bounded LRU of
``PackedPlcore`` instances: first touch of a scene pays the pack, every
queued tile for a resident scene reuses it, and the engine's
scene-grouped batching keeps touches clustered so residency is long.

Capacity is in MB of actual array bytes (params + quant + packed kernel
layout), not entry count — the quantity that competes for device memory.
Auxiliary per-scene residents (the adaptive-sampling ``SceneAux``:
calibration stats + trunk memo, attached via ``ensure_aux``) count
against the SAME budget at their LIVE size — the memo grows during
serving, so eviction decisions re-read ``aux.nbytes`` instead of a
stale at-insert figure. An evicted scene drops its aux with it.
A resident with tiles in flight on the async executor is PINNED
(``pin``/``unpin`` refcounts): eviction skips pinned entries, so a scene
whose dispatched tiles have not yet drained can never lose its weights
to a colder scene's load mid-flight. Unpinned entries evict LRU-first as
before.
The accounting is PER DEVICE: an array costs the bytes the device that
holds the most of it holds. Eviction never removes the just-inserted
entry, so a cache smaller than one scene still serves (it just thrashes,
and the counters show it).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.core.pipeline import PackedPlcore
from repro.obs.trace import NULL_TRACER


class SceneLoadError(RuntimeError):
    """``SceneCache.get`` failed to produce a resident scene: either the
    loader raised (``fail_fast=False`` — the original exception is
    chained) or the scene is in negative-result backoff after a recent
    failure (``fail_fast=True`` — the loader was NOT invoked)."""

    def __init__(self, msg: str, *, fail_fast: bool = False):
        super().__init__(msg)
        self.fail_fast = fail_fast


def device_nbytes(a) -> int:
    """Per-device resident bytes of one array: the largest total any
    single device holds."""
    try:
        per_dev: dict = {}
        for s in a.addressable_shards:
            per_dev[s.device] = (per_dev.get(s.device, 0)
                                 + s.data.size * a.dtype.itemsize)
        if per_dev:
            return int(max(per_dev.values()))
    except (AttributeError, TypeError):
        pass
    return int(a.size * a.dtype.itemsize)


def plcore_nbytes(pp: PackedPlcore) -> int:
    """Per-device resident bytes of one loaded scene: every array hanging
    off the PackedPlcore (raw params + RMCM quant tree + packed kernel
    layout), each counted at its per-device size."""
    leaves = jax.tree_util.tree_leaves((pp.params, pp.quant, pp.packed))
    return int(sum(device_nbytes(a) for a in leaves))


class SceneCache:
    """LRU cache of loaded scenes: ``scene_id -> PackedPlcore``.

    ``loader(scene_id)`` builds a PackedPlcore on miss (the once-per-
    residency pack); ``capacity_mb`` bounds total PER-DEVICE resident
    bytes. Hits, misses and evictions are counted for the serving stats.

    A loader that RAISES must leave the cache exactly as it was: no
    partially-constructed entry resident, no stale pin refcount, and the
    failure is counted (``load_failures``). The failed scene then enters
    attempt-based negative-result backoff: the next ``fail_backoff``
    ``get`` calls for it raise ``SceneLoadError(fail_fast=True)``
    WITHOUT invoking the loader (so a dead scene can't stall the serving
    loop on repeated load costs), doubling per consecutive failure up to
    ``max_fail_backoff``; the first post-backoff ``get`` retries the
    loader for real, and a success clears the failure state."""

    #: Observability hooks, wired (as instance attrs) by the owning
    #: engine: ``tracer`` records cache.* residency events, ``trace_host``
    #: tags them with the owning cluster host. Class-level defaults keep
    #: a bare SceneCache (tests, tools) tracing-free with zero setup.
    tracer = NULL_TRACER
    trace_host = None

    def __init__(self, loader: Callable[[str], PackedPlcore],
                 capacity_mb: float = 256.0, *, fail_backoff: int = 4,
                 max_fail_backoff: int = 64):
        self._loader = loader
        self.capacity_bytes = int(capacity_mb * (1 << 20))
        self._entries: "OrderedDict[str, Tuple[PackedPlcore, int]]" = \
            OrderedDict()
        # scene -> auxiliary resident (sampling.SceneAux) riding the
        # entry; its nbytes is LIVE (trunk memo grows during serving)
        self._aux: Dict[str, object] = {}
        self._pins: Dict[str, int] = {}
        self.fail_backoff = int(fail_backoff)
        self.max_fail_backoff = int(max_fail_backoff)
        # scene -> [consecutive real failures, fail-fast credits left]
        self._failed: Dict[str, list] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.load_failures = 0      # loader raised
        self.fail_fasts = 0         # negative-result backoff short-circuits

    def __contains__(self, scene_id: str) -> bool:
        return scene_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def resident_scenes(self) -> list:
        """LRU -> MRU order."""
        return list(self._entries)

    @property
    def aux_bytes(self) -> int:
        """LIVE auxiliary resident bytes (stats + memo, re-read per call
        because the memo grows/evicts during serving)."""
        return sum(a.nbytes for a in self._aux.values())

    @property
    def resident_bytes(self) -> int:
        return (sum(nb for _, nb in self._entries.values())
                + self.aux_bytes)

    def aux(self, scene_id: str):
        """The scene's auxiliary resident, or None if never built (or
        dropped with an eviction)."""
        return self._aux.get(scene_id)

    def ensure_aux(self, scene_id: str, builder) -> object:
        """Attach (or fetch) the per-scene auxiliary resident.
        ``builder(pp)`` runs once per residency — e.g. the adaptive
        probe (``pipeline.build_scene_aux``) — and its product rides the
        cache entry: counted against ``capacity_mb`` at LIVE size,
        dropped when the scene evicts, protected by the scene's pins
        while tiles are in flight. The scene must be resident (``get``
        it first): aux without weights has nothing to serve."""
        aux = self._aux.get(scene_id)
        if aux is not None:
            return aux
        ent = self._entries.get(scene_id)
        if ent is None:
            raise KeyError(f"scene {scene_id!r} is not resident — "
                           "load it before attaching aux")
        tr = self.tracer
        sp = tr.begin("cache.aux_build", cat="cache", scene=scene_id,
                      host=self.trace_host) if tr.enabled else None
        aux = builder(ent[0])
        self._aux[scene_id] = aux
        if sp is not None:
            tr.end(sp, ok=True, bytes=int(aux.nbytes))
        self._evict_over_capacity(keep=scene_id)
        return aux

    def pin(self, scene_id: str) -> None:
        """Refcount one in-flight use of a resident scene: a pinned entry
        is skipped by eviction until its last ``unpin`` (the executor pins
        at tile dispatch and unpins when the tile's scatter drains, so a
        resident can never be evicted under an in-flight dispatch)."""
        self._pins[scene_id] = self._pins.get(scene_id, 0) + 1
        if self.tracer.enabled:
            self.tracer.event("cache.pin", cat="cache", scene=scene_id,
                              host=self.trace_host,
                              refs=self._pins[scene_id])

    def unpin(self, scene_id: str) -> None:
        n = self._pins.get(scene_id, 0) - 1
        if n <= 0:
            self._pins.pop(scene_id, None)
        else:
            self._pins[scene_id] = n
        if self.tracer.enabled:
            self.tracer.event("cache.unpin", cat="cache", scene=scene_id,
                              host=self.trace_host, refs=max(0, n))

    def pinned(self, scene_id: str) -> bool:
        return scene_id in self._pins

    def discard(self, scene_id: str) -> bool:
        """Drop one resident entry outside the LRU policy (the cluster's
        graceful host DRAIN frees a departing host's residency after its
        in-flight tiles finish). Pinned entries are refused — a drain
        must never drop weights under a still-in-flight tile. Returns
        whether an entry was dropped."""
        if scene_id not in self._entries or scene_id in self._pins:
            return False
        del self._entries[scene_id]
        self._aux.pop(scene_id, None)
        self.evictions += 1
        self.tracer.event("cache.evict", cat="cache", scene=scene_id,
                          host=self.trace_host, reason="discard")
        return True

    def _evict_over_capacity(self, keep: str) -> None:
        """Evict LRU-first until the LIVE resident total (weights + aux)
        fits capacity. ``keep`` (the just-touched scene) and pinned
        entries are never victims; an evicted scene's aux goes with it."""
        for victim in list(self._entries):   # LRU -> MRU order
            if (len(self._entries) <= 1
                    or self.resident_bytes <= self.capacity_bytes):
                break
            if victim == keep or victim in self._pins:
                continue
            del self._entries[victim]
            self._aux.pop(victim, None)
            self.evictions += 1
            if self.tracer.enabled:
                self.tracer.event("cache.evict", cat="cache", scene=victim,
                                  host=self.trace_host, reason="capacity")

    def failing_scenes(self) -> list:
        """Scenes currently in load-failure state (>= 1 consecutive real
        loader failure, backoff window possibly still open). The cluster
        scheduler reads this per HOST to decide quarantine."""
        return list(self._failed)

    def peek(self, scene_id: str) -> Optional[PackedPlcore]:
        """The scene's resident weights, or None when it is not resident,
        without counting a hit or moving it in the LRU order."""
        ent = self._entries.get(scene_id)
        return None if ent is None else ent[0]

    def get(self, scene_id: str) -> PackedPlcore:
        """Fetch a scene, loading (and possibly evicting) on miss. The
        returned instance is resident until LRU eviction pushes it out;
        pinned entries (in-flight tiles) and the just-inserted entry are
        never eviction victims — a cache whose unpinned residents don't
        cover the overflow stays over capacity until pins drain (the
        counters show it)."""
        tr = self.tracer
        ent = self._entries.get(scene_id)
        if ent is not None:
            self.hits += 1
            self._entries.move_to_end(scene_id)
            if tr.enabled:
                tr.event("cache.hit", cat="cache", scene=scene_id,
                         host=self.trace_host)
            return ent[0]
        fail = self._failed.get(scene_id)
        if fail is not None and fail[1] > 0:
            fail[1] -= 1
            self.fail_fasts += 1
            if tr.enabled:
                tr.event("cache.load_backoff", cat="cache", scene=scene_id,
                         host=self.trace_host, failures=fail[0],
                         credits_left=fail[1])
            raise SceneLoadError(
                f"scene {scene_id!r} is in load-failure backoff "
                f"({fail[0]} consecutive failures; retry in {fail[1] + 1} "
                f"more attempts)", fail_fast=True)
        self.misses += 1
        with tr.span("cache.load", cat="cache", scene=scene_id,
                     host=self.trace_host) as sp:
            try:
                pp = self._loader(scene_id)
                nbytes = plcore_nbytes(pp)
            except Exception as e:
                # failure cleanup: nothing was inserted (the entry only
                # lands below, after the loader AND the size accounting
                # succeed), so cache state/pins are untouched — count it
                # and arm the fail-fast window
                self.load_failures += 1
                n_fail = (fail[0] if fail else 0) + 1
                self._failed[scene_id] = [
                    n_fail, min(self.fail_backoff * (2 ** (n_fail - 1)),
                                self.max_fail_backoff)]
                if sp is not None:
                    sp.attrs.update(ok=False, error=str(e)[:120])
                raise SceneLoadError(
                    f"loader failed for scene {scene_id!r}: {e}") from e
            if sp is not None:
                sp.attrs.update(ok=True, bytes=nbytes)
        self._failed.pop(scene_id, None)
        self._entries[scene_id] = (pp, nbytes)
        self._evict_over_capacity(keep=scene_id)
        return pp

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": round(self.hits / total, 4) if total else 0.0,
            "resident_scenes": len(self._entries),
            "pinned_scenes": len(self._pins),
            "aux_scenes": len(self._aux),
            "aux_mb": round(self.aux_bytes / (1 << 20), 3),
            "resident_mb": round(self.resident_bytes / (1 << 20), 3),
            "capacity_mb": round(self.capacity_bytes / (1 << 20), 3),
            "load_failures": self.load_failures,
            "fail_fasts": self.fail_fasts,
            "failing_scenes": len(self._failed),
        }

    def consecutive_failures(self, scene_id: str) -> int:
        """Consecutive real loader failures for a scene (0 when healthy).
        The scheduler uses this to decide when a scene is dead enough to
        terminate its queued requests."""
        fail = self._failed.get(scene_id)
        return fail[0] if fail else 0
