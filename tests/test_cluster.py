"""Multi-host serving fabric tests: the cluster layer under host failure.

The load-bearing claims: (1) ``split_devices`` partitions the process's
devices into contiguous per-host groups (sharing the full list when the
box is smaller than the pool); (2) placement folds health, residency,
affinity and load into one deterministic score; (3) killing a host with
tiles in flight re-queues them and a DIFFERENT host re-renders them
bit-identically — every submit still answered exactly once; (4) the
cross-host failover hook recovers per-tile failures on another host
before the local retry -> oracle ladder; (5) scene quarantine is
per-host — a scene failing on host A keeps serving from host B, probes
recover A, and only all-hosts-quarantined declares the scene dead;
(6) admission control aggregates over the pool: a cold pool with a
service prior predicts delay (the cold-start hole), a host-less pool
predicts infinite delay; (7) drain migrates cached-scene affinity and
rejoin restores placement; (8) a hung host is killed by the heartbeat
layer and its work recovered; (9) a slow host is flagged suspect, not
killed; (10) under a randomized interleaving of submit/step/take with
chaos faults AND scheduled kill/drain/rejoin events, the cluster always
terminates and every submit reaches exactly one terminal status.
"""
import jax
import numpy as np
import pytest

from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.core.plcore import plcore_decls
from repro.models.params import init_params
from repro.serving import (STATUSES, ClusterEngine, FaultConfig, FaultPlan,
                           HostEvent, RenderEngine, RenderRequest, SceneCache,
                           split_devices)

TILE = 64


@pytest.fixture(scope="module")
def setup():
    cfg = tiny()
    param_sets = {
        f"scene{i}": init_params(plcore_decls(cfg), jax.random.PRNGKey(i),
                                 "float32")
        for i in range(3)}
    return cfg, param_sets


def _loader(cfg, param_sets):
    return lambda sid: PackedPlcore(cfg, param_sets[sid])


def _cluster(cfg, param_sets, n_hosts=2, **kw):
    caches = [SceneCache(_loader(cfg, param_sets), capacity_mb=256.0)
              for _ in range(n_hosts)]
    return ClusterEngine(caches, **kw)


def _run(engine, requests):
    rids = [engine.submit(r) for r in requests]
    engine.drain()
    return {rid: engine.take(rid) for rid in rids}


def _requests(n=4, hw=16):
    return [RenderRequest(scene_id=f"scene{i % 2}", hw=hw, theta=30.0 * i)
            for i in range(n)]


# ----------------------------------------------------------- device split --
def test_split_devices_contiguous_groups():
    groups = split_devices(2, devices=list(range(8)))
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # fewer devices than hosts: every host shares the full list
    assert split_devices(3, devices=[0, 1]) == [[0, 1], [0, 1], [0, 1]]
    with pytest.raises(ValueError):
        split_devices(0)


# -------------------------------------------------------------- placement --
def test_placement_scoring(setup):
    cfg, param_sets = setup
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE)
    sched, pool = eng.scheduler, eng.pool
    h0, h1 = pool.get(0), pool.get(1)
    # residency (+4) dominates the hash tie-break
    h0.cache.get("scene0")
    assert sched._place("scene0").id == 0
    # health dominates residency: suspect 4 + resident 4 < healthy 10
    h0.state = "suspect"
    assert sched._place("scene0").id == 1
    h0.state = "healthy"
    # exclusion and quarantine both remove a host from consideration
    assert sched._place("scene0", exclude={0}).id == 1
    sched._quarantine[(0, "scene0")] = 5
    assert sched._place("scene0", exclude={1}) is None
    del sched._quarantine[(0, "scene0")]
    # dead / draining hosts are never placeable
    h0.state, h1.state = "dead", "draining"
    assert sched._place("scene0") is None


# ------------------------------------------------------------ host kill ----
def test_kill_with_in_flight_requeues_and_recovers_bit_exact(setup):
    cfg, param_sets = setup
    reqs = _requests(n=4)
    clean = {rid: res for rid, res in _run(
        RenderEngine(SceneCache(_loader(cfg, param_sets)), tile_rays=TILE),
        reqs).items()}
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE,
                   pipeline_depth=2)
    rids = [eng.submit(r) for r in reqs]
    # step until some host holds in-flight slots, then kill THAT host —
    # its tiles' pixels have no other path home than the re-queue lane
    victim = None
    for _ in range(200):
        eng.step()
        busy = [h for h in eng.pool if h.executor.in_flight > 0]
        if busy:
            victim = busy[0]
            break
    assert victim is not None
    eng._kill_host(victim)
    eng.drain()
    st = eng.stats
    assert st["host_kills"] == 1
    assert st["requeued_tiles"] >= 1
    assert st["failovers"] >= 1                 # requeued tile re-dispatched
    assert st["cross_host_redispatches"] >= 1   # ... on a DIFFERENT host
    assert victim.summary()["state"] == "dead"
    # exactly once, bit-identically — re-rendering the same rays through
    # the same packed weights on another host changes nothing
    assert eng.pending == 0 and eng.in_flight_tiles == 0
    for rid in rids:
        res = eng.take(rid)
        assert res.status == "ok"
        np.testing.assert_array_equal(res.image, clean[rid].image)


def test_kill_event_fires_at_dispatch_count(setup):
    cfg, param_sets = setup
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE,
                   pipeline_depth=2)
    # a kill aimed at every host guarantees the event machinery fires on
    # whichever host the scheduler actually used
    eng.schedule_host_events([HostEvent("kill", 0, at_dispatch=3),
                              HostEvent("kill", 1, at_dispatch=3)])
    results = _run(eng, _requests(n=4))
    assert eng.stats["host_kills"] >= 1
    # with ALL hosts dead, remaining submits terminate — never hang
    assert eng.pending == 0 and eng.in_flight_tiles == 0
    assert all(r.status in STATUSES for r in results.values())


def test_failover_hook_recovers_on_other_host(setup):
    cfg, param_sets = setup
    reqs = _requests(n=2)
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), reqs)
    plan = FaultPlan(FaultConfig(seed=1, dispatch_error_rate=0.4))
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE, faults=plan)
    results = _run(eng, reqs)
    assert eng.stats["dispatch_errors"] > 0
    # at least one failed tile was served by the OTHER host instead of
    # falling through to the local retry ladder
    assert eng.stats["cross_host_redispatches"] >= 1
    for rid, res in results.items():
        assert res.status == "ok"
        np.testing.assert_array_equal(res.image, clean[rid].image)


# ------------------------------------------------------------ quarantine ---
def _flaky_loader(cfg, param_sets, failing):
    """Loader that raises while ``failing["on"]`` is set."""
    def load(sid):
        if failing["on"]:
            raise RuntimeError("host-local checkpoint store down")
        return PackedPlcore(cfg, param_sets[sid])
    return load


def test_quarantine_is_per_host_and_probes_recover(setup):
    cfg, param_sets = setup
    failing = {"on": True}
    eng = ClusterEngine(
        [SceneCache(_flaky_loader(cfg, param_sets, failing),
                    capacity_mb=256.0, fail_backoff=0),
         SceneCache(_loader(cfg, param_sets), capacity_mb=256.0)],
        tile_rays=TILE, max_load_failures=1, quarantine_probe_tiles=1)
    # affinity steers placement at host 0 FIRST (the hash tie-break
    # would pick host 1 and never exercise the flaky loader): scene0
    # fails there -> quarantined on host 0, served from host 1 anyway
    eng.scheduler._affinity["scene0"] = 0
    res = _run(eng, [RenderRequest(scene_id="scene0", hw=16)])
    assert all(r.status == "ok" for r in res.values())
    assert eng.stats["quarantines"] >= 1
    assert (0, "scene0") in eng.scheduler._quarantine
    # host 0 still failing: the countdown expires, the probe placement
    # fails again and RE-ARMS the window (host 1 draining forces the
    # scheduler to actually look at host 0)
    eng.pool.get(1).state = "draining"
    _run(eng, [RenderRequest(scene_id="scene0", hw=8)])
    assert eng.stats["quarantine_probes"] >= 1
    assert (0, "scene0") in eng.scheduler._quarantine
    # store comes back: the next probe succeeds and lifts the quarantine
    failing["on"] = False
    res = _run(eng, [RenderRequest(scene_id="scene0", hw=8)])
    assert all(r.status == "ok" for r in res.values())
    assert eng.stats["quarantine_recoveries"] >= 1
    assert (0, "scene0") not in eng.scheduler._quarantine


def test_scene_dead_only_when_every_host_quarantined(setup):
    cfg, param_sets = setup
    failing = {"on": True}
    loaders = [_flaky_loader(cfg, param_sets, failing) for _ in range(2)]
    eng = ClusterEngine(
        [SceneCache(ld, capacity_mb=256.0, fail_backoff=0)
         for ld in loaders],
        tile_rays=TILE, max_load_failures=1)
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8))
    eng.drain()
    res = eng.take(rid)
    assert res.status == "rejected"
    assert "every serving host" in res.error
    # the pool itself is fine: a loadable scene still serves
    failing["on"] = False
    res2 = _run(eng, [RenderRequest(scene_id="scene1", hw=8)])
    assert all(r.status == "ok" for r in res2.values())


# -------------------------------------------------------------- admission --
def test_aggregate_admission_uses_prior_and_pool_health(setup):
    cfg, param_sets = setup
    # cold pool + service prior: predicted delay from the prior rejects
    # an unmeetable deadline BEFORE any EWMA exists (the cold-start hole)
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE,
                   tile_service_prior_s=10.0)
    eng.submit(RenderRequest(scene_id="scene0", hw=16))       # backlog
    rid = eng.submit(RenderRequest(scene_id="scene0", hw=8, deadline_s=0.5))
    res = eng.take(rid)
    assert res.status == "rejected" and "admission control" in res.error
    eng.drain()
    # no placeable host => infinite predicted delay
    for h in eng.pool:
        h.state = "dead"
    assert eng.scheduler._estimated_queueing_s() == float("inf")
    # cold pool without a prior: no estimate, admit optimistically
    eng2 = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE)
    assert eng2.scheduler._estimated_queueing_s() is None


# ---------------------------------------------------------- drain/rejoin ---
def test_drain_migrates_affinity_and_rejoin_restores(setup):
    cfg, param_sets = setup
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE)
    _run(eng, [RenderRequest(scene_id="scene0", hw=8)])
    served = [h for h in eng.pool if "scene0" in h.cache]
    assert len(served) == 1
    src = served[0]
    other = eng.pool.get(1 - src.id)
    eng.schedule_host_events([HostEvent("drain", src.id)])
    eng.step()
    assert src.state == "draining" and not src.placeable
    assert eng.stats["host_drains"] == 1
    # residency handed off: affinity now points at the live host and the
    # drained host's unpinned weights are gone
    assert eng.stats["affinity_migrations"] >= 1
    assert eng.scheduler._affinity["scene0"] == other.id
    assert "scene0" not in src.cache
    res = _run(eng, [RenderRequest(scene_id="scene0", hw=8)])
    assert all(r.status == "ok" for r in res.values())
    assert "scene0" in other.cache
    eng.schedule_host_events([HostEvent("rejoin", src.id)])
    eng.step()
    assert src.state == "healthy" and src.placeable
    assert eng.stats["host_rejoins"] == 1


# ------------------------------------------------------ heartbeat / hang ---
def test_hung_host_is_killed_and_work_recovered(setup):
    cfg, param_sets = setup
    reqs = [RenderRequest(scene_id="scene0", hw=16)]
    clean = _run(RenderEngine(SceneCache(_loader(cfg, param_sets)),
                              tile_rays=TILE), reqs)
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE,
                   pipeline_depth=2, hang_kill_steps=5)
    rid = eng.submit(reqs[0])
    hung = None
    for _ in range(200):
        eng.step()
        busy = [h for h in eng.pool if h.executor.in_flight > 0]
        if busy:
            hung = busy[0]
            break
    assert hung is not None
    eng.schedule_host_events([HostEvent("hang", hung.id)])
    eng.drain()            # the clockless hang_kill_steps fallback fires
    assert eng.stats["heartbeat_timeouts"] >= 1
    assert hung.state == "dead"
    assert eng.stats["requeued_tiles"] >= 1
    res = eng.take(rid)
    assert res.status == "ok"
    np.testing.assert_array_equal(res.image, clean[rid].image)


def test_slow_host_flagged_suspect_not_killed(setup):
    cfg, param_sets = setup
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE,
                   straggler_mitigation=True)
    for _ in range(10):
        eng.monitor.record_host_step(0, 0.01)
        eng.monitor.record_host_step(1, 1.0)
    eng._health_check(eng._clock())
    h0, h1 = eng.pool.get(0), eng.pool.get(1)
    assert h1.state == "suspect" and h0.state == "healthy"
    assert eng.stats["slow_host_flags"] == 1
    assert h1.placeable                       # deprioritized, still served
    assert eng.scheduler._place("scene0").id == 0
    # recovery: the EWMA converges back and the flag clears
    for _ in range(500):
        eng.monitor.record_host_step(1, 0.01)
    eng._health_check(eng._clock())
    assert h1.state == "healthy"


# ------------------------------------------------------------ robustness ---
def test_cluster_stats_and_robustness_schema(setup):
    cfg, param_sets = setup
    eng = _cluster(cfg, param_sets, n_hosts=2, tile_rays=TILE)
    _run(eng, _requests(n=2))
    cs = eng.cluster_stats()
    assert cs["n_hosts"] == 2 and set(cs["hosts"]) == {0, 1}
    for h in cs["hosts"].values():
        assert h["state"] in ("healthy", "suspect", "draining", "dead")
    assert eng.robustness()["cluster"]["host_kills"] == 0


def test_fuzz_cluster_interleaving_always_terminates(setup):
    cfg, param_sets = setup
    rng = np.random.RandomState(11)
    plan = FaultPlan(FaultConfig.cluster_chaos(seed=4))
    eng = ClusterEngine(
        [SceneCache(plan.wrap_loader(_loader(cfg, param_sets)),
                    capacity_mb=256.0) for _ in range(3)],
        tile_rays=32, faults=plan, max_queue=16, aging_tiles=4,
        pipeline_depth=2, max_load_failures=2, quarantine_probe_tiles=2)
    eng.schedule_host_events([
        HostEvent("kill", 2, at_dispatch=10),
        HostEvent("drain", 1, at_dispatch=20),
        HostEvent("rejoin", 1, at_dispatch=30),
        HostEvent("slow", 0, at_dispatch=5, extra_s=0.001)])
    submitted, taken = set(), {}
    for _ in range(6):
        for _ in range(int(rng.randint(0, 4))):
            dl = (None, 0.05, 5.0)[int(rng.randint(3))]
            submitted.add(eng.submit(RenderRequest(
                scene_id=f"scene{int(rng.randint(3))}", hw=8,
                theta=float(rng.uniform(0.0, 360.0)),
                priority=int(rng.randint(2)), deadline_s=dl)))
        for _ in range(int(rng.randint(0, 6))):
            eng.step()
        for rid in list(eng.completed):
            if rng.random_sample() < 0.5:
                taken[rid] = eng.take(rid)
    steps = eng.drain(max_steps=20000)
    assert steps < 20000                       # terminated, not capped
    assert eng.pending == 0 and eng.in_flight_tiles == 0
    assert not eng.scheduler._requeue
    results = dict(taken)
    results.update(eng.completed)
    # every submitted request reached EXACTLY ONE terminal status, even
    # across the kill / drain / rejoin schedule and seeded host faults
    assert set(results) == submitted
    assert eng.stats["requests_completed"] == len(submitted)
    for res in results.values():
        assert res.status in STATUSES
        if res.delivered:
            assert np.isfinite(res.image).all()


# ------------------------------------------ one replica per device --------
_REPLICA_PLACEMENT = r'''
import jax
from repro.configs.nerf_icarus import tiny
from repro.core.pipeline import PackedPlcore
from repro.launch.serve import host_devices, make_scene_loader
from repro.serving import ClusterEngine, RenderRequest, SceneCache, split_devices

devs = jax.devices()
assert len(devs) == 4, devs
groups = split_devices(4)
devices = host_devices(groups)
assert devices == devs, devices

# every dispatched tile: the devices its rays and pixels sit on, keyed by
# the device its scene was loaded onto
seen = {}
dispatch = PackedPlcore.dispatch_tile
def spy(self, o, d, **kw):
    rgb, rest = dispatch(self, o, d, **kw)
    seen.setdefault(self.device, set()).update(
        o.devices() | d.devices() | rgb.devices())
    return rgb, rest
PackedPlcore.dispatch_tile = spy

cfg = tiny()
scene_ids = [f"scene{i}" for i in range(4)]
caches = [SceneCache(make_scene_loader(cfg, scene_ids, device=dev),
                     capacity_mb=64.0) for dev in devices]
for cache, sid in zip(caches, scene_ids):
    cache.get(sid)                    # one scene per replica
eng = ClusterEngine(caches, device_groups=groups, tile_rays=64)
rids = [eng.submit(RenderRequest(sid, hw=8, theta=40.0 * i))
        for i, sid in enumerate(scene_ids * 2)]
eng.drain()
assert all(eng.completed[r].status == "ok" for r in rids)
for host, dev in zip(eng.pool.hosts, devs):
    assert host.dispatches > 0, host.id
    for sid in host.cache.resident_scenes:
        pp = host.cache.get(sid)
        for a in jax.tree.leaves((pp.params, pp.quant, pp.packed)):
            assert a.devices() == {dev}, (host.id, sid, a.devices())
    assert seen[dev] == {dev}, (host.id, seen[dev])
print("ALL OK")
'''


def test_replicas_keep_weights_and_tiles_on_own_device(fake_devices):
    """Four hosts on four devices, weights replicated (no mesh): each
    host's resident scene weights and every tile it dispatches — rays in,
    pixels out — sit on that host's own device, not on device 0."""
    fake_devices(_REPLICA_PLACEMENT, n_devices=4)
