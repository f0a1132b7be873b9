"""Multi-tenant PLCore serving — the layer between the fused kernel and
"heavy traffic from millions of users" (ROADMAP north star).

The paper scales rendering by tiling PLCores behind a ray dispatcher
(ICARUS §5, Fig. 1); this package is the host-side restatement of that
dispatcher for many *concurrent requests over many scenes*, split into
three explicit layers (see ``engine``'s module docstring for the
dataflow):

* ``engine``       — ``TileScheduler`` (queue, priority/sticky policy,
                     cross-request ray coalescing) -> ``TileExecutor``
                     (double-buffered async-dispatch slots over jax
                     async dispatch) ->
                     ``CompletionSink`` (out-of-order framebuffer
                     scatter), behind the ``RenderEngine`` façade.
* ``scene_cache``  — LRU of resident ``PackedPlcore`` weight sets so one
                     process serves many scenes (FlexNeRFer-style
                     multi-model residency), with in-flight pin
                     refcounts so eviction can't drop weights under a
                     dispatched tile.
* ``loadgen``      — synthetic open/closed-loop client (Poisson
                     arrivals, mixed resolutions) reporting throughput
                     and tail latency, split into queueing delay vs
                     service time.
* ``faults``       — deterministic seeded fault injection (dispatch
                     errors, corrupted tiles, loader failures,
                     stragglers, host kills/slow-downs) exercising the
                     engine's recovery ladder: retry -> oracle fallback,
                     loader backoff, straggler redispatch, SLO admission
                     + expiry.
* ``cluster``      — the multi-host fabric: a ``HostPool`` of isolated
                     per-host cache+executor workers (each a replica on
                     its own device) behind one global
                     ``ClusterScheduler``;
                     heartbeat health states, cross-host tile failover,
                     per-host scene quarantine with recovery probes,
                     aggregate SLO admission, graceful drain/rejoin.
"""
from repro.serving.cluster import (HOST_STATES, ClusterEngine,
                                   ClusterScheduler, Host, HostEvent,
                                   HostPool, split_devices)
from repro.serving.engine import (STATUSES, CompletionSink, RenderEngine,
                                  RenderRequest, RenderResult,
                                  TileExecutor, TileScheduler)
from repro.serving.faults import (FaultConfig, FaultPlan,
                                  InjectedDispatchError,
                                  InjectedLoaderError)
from repro.serving.scene_cache import SceneCache, SceneLoadError

__all__ = ["RenderEngine", "RenderRequest", "RenderResult", "SceneCache",
           "SceneLoadError", "TileScheduler", "TileExecutor",
           "CompletionSink", "FaultConfig", "FaultPlan",
           "InjectedDispatchError", "InjectedLoaderError", "STATUSES",
           "ClusterEngine", "ClusterScheduler", "Host", "HostEvent",
           "HostPool", "HOST_STATES", "split_devices"]
