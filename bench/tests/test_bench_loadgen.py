"""The traffic generator and its drivers, on a fake clock."""
import itertools
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import loadgen


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Serves one request per step, ``service_s`` each, in FIFO order;
    requests whose hw is in ``fail`` end ``rejected``."""

    def __init__(self, clock, service_s, fail=()):
        self.clock, self.service_s, self.fail = clock, service_s, fail
        self.queue, self.completed, self.n = [], {}, 0

    def submit(self, req):
        rid, self.n = self.n, self.n + 1
        self.queue.append((rid, req))
        return rid

    def step(self):
        if not self.queue:
            return False
        rid, req = self.queue.pop(0)
        start = self.clock()
        self.clock.t += self.service_s
        status = "rejected" if req.hw in self.fail else "ok"
        self.completed[rid] = SimpleNamespace(
            status=status, service_start_s=start, complete_s=self.clock())
        return True


TRAFFIC = {"loop": "open", "scenes": 4, "hw": [16, 32],
           "poses": {"kind": "uniform", "theta": [0, 360],
                     "phi": [-35, -15], "radius": 4.0}}


def drive(engine, clock):
    return loadgen.Driver(engine, lambda s: s, clock=clock,
                          sleep=clock.sleep)


def test_latency_runs_from_due_time_and_lateness_is_reported():
    clock = FakeClock()
    engine = FakeEngine(clock, service_s=0.3)
    due = np.array([0.0, 0.1, 0.2])      # a burst the engine cannot keep up
    w = drive(engine, clock).open(loadgen.requests(TRAFFIC, 1), due, 1.0,
                                  grace_s=5.0)
    assert [r.status for r in w.records] == ["ok"] * 3
    lat = loadgen.latencies_s(w, clock())
    # request k is due at 0.1 k, submitted after the steps before it,
    # and done after k + 1 services of 0.3 s
    assert lat == pytest.approx([0.3, 0.3 * 2 - 0.1, 0.3 * 3 - 0.2])
    assert w.lateness_s == pytest.approx([0.0, 0.2, 0.1])
    assert w.t1 - w.t0 == pytest.approx(1.0, abs=0.3)


def test_failed_requests_count_as_missing_the_limit():
    clock = FakeClock()
    engine = FakeEngine(clock, service_s=0.01, fail=(32,))
    due = np.arange(10) * 0.1
    w = drive(engine, clock).open(loadgen.requests(TRAFFIC, 2), due, 1.0,
                                  grace_s=5.0)
    gave_up = clock() + 10.0
    lat = loadgen.latencies_s(w, gave_up)
    for r, l in zip(w.records, lat):
        if r.spec.hw == 32:
            assert r.status == "rejected" and l == pytest.approx(
                gave_up - r.due)
        else:
            assert l == pytest.approx(0.01)


def test_unanswered_requests_wait_out_the_grace_period():
    clock = FakeClock()

    class Stuck(FakeEngine):
        def step(self):
            return False

    w = drive(Stuck(clock, 0.0), clock).open(
        loadgen.requests(TRAFFIC, 3), np.array([0.5]), 1.0, grace_s=2.0)
    assert w.records[0].status is None
    assert clock() - w.t1 >= 2.0


def test_closed_loop_keeps_its_requests_outstanding():
    clock = FakeClock()
    engine = FakeEngine(clock, service_s=0.125)
    traffic = {**TRAFFIC, "loop": "closed",
               "poses": {"kind": "orbit", "count": 200, "phi": -30.0,
                         "radius": 4.0}}
    w = drive(engine, clock).closed(loadgen.requests(traffic, 4), 2, 1.0)
    # one request finishes per step, and a new one enters before the
    # next: two are outstanding at every step, one when the window closes
    assert len(w.records) == 8 + 1
    assert sum(r.delivered for r in w.records) == 8
    thetas = [r.spec.theta for r in w.records]
    assert np.allclose(np.diff(thetas) % 360.0, 1.8)


def test_every_seed_offers_the_same_arrivals_and_sizes():
    a = loadgen.arrivals({"rate_per_s": 6.0}, 35.0)
    assert len(a) == 210 and np.all(np.diff(a) > 0) and a[-1] < 35.0
    # the gaps are the exponential quantiles, shuffled
    gaps = np.sort(np.diff(a, prepend=0))
    assert gaps.mean() == pytest.approx(1 / 6.0, rel=0.05)
    assert not np.allclose(np.diff(a, prepend=0), gaps)
    specs = [[s for _, s in zip(range(60), loadgen.requests(TRAFFIC, k))]
             for k in (1, 2 ** 40)]
    assert [(s.hw, s.scene) for s in specs[0]] == \
        [(s.hw, s.scene) for s in specs[1]]
    assert [s.theta for s in specs[0]] != [s.theta for s in specs[1]]
    hw = [s.hw for s in specs[0]]
    assert all(hw.count(h) == hw.count(hw[0]) for h in set(hw))
    # a mix with another order offers another sequence of the same work
    other = {**TRAFFIC, "rate_per_s": 6.0, "order": 1}
    b = loadgen.arrivals(other, 35.0)
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(b, prepend=0)), gaps)
    assert [s.hw for s in take(other, 60)] != hw


def take(traffic, n, seed=5):
    return list(itertools.islice(loadgen.requests(traffic, seed), n))


@pytest.mark.parametrize("path", sorted(
    (Path(loadgen.__file__).parent / "traffic").glob("*.json")),
    ids=lambda p: p.stem)
def test_every_traffic_file_generates_its_mix(path):
    traffic = json.loads(path.read_text())
    specs = take(traffic, 50)
    sizes = {c["hw"] for c in loadgen.classes(traffic)}
    assert {s.hw for s in specs} == sizes
    assert all(0 <= s.scene < traffic["scenes"] for s in specs)
    if traffic["loop"] == "open":
        due = loadgen.arrivals({"rate_per_s": 5.0, **traffic}, 20.0)
        assert len(due) > 50 and np.all(np.diff(due) >= 0)


def test_scene_popularity_follows_zipf():
    specs = take({**TRAFFIC, "scenes": 2000, "zipf_s": 1.1}, 20000)
    counts = np.bincount([s.scene for s in specs], minlength=2000)
    p = 1.0 / np.arange(1, 2001) ** 1.1
    p /= p.sum()
    assert counts[0] / len(specs) == pytest.approx(p[0], rel=0.05)
    assert counts[:10].sum() / len(specs) == pytest.approx(p[:10].sum(),
                                                            rel=0.05)


def test_request_classes_come_in_their_shares_with_their_limits():
    traffic = {**TRAFFIC, "classes": [
        {"hw": 64, "share": 9, "priority": 1, "deadline_ms": 500},
        {"hw": 800, "share": 1}]}
    specs = take(traffic, 100)
    assert sum(s.hw == 800 for s in specs) == 10
    for rnd in range(10):
        assert sum(s.hw == 800 for s in specs[10 * rnd:10 * rnd + 10]) == 1
    small = [s for s in specs if s.hw == 64]
    assert {(s.priority, s.deadline_ms) for s in small} == {(1, 500)}
    assert {(s.priority, s.deadline_ms) for s in specs if s.hw == 800} == \
        {(0, None)}


def test_views_give_each_pose_a_request_per_eye():
    traffic = {**TRAFFIC, "views": [-1.0, 1.0],
               "poses": {"kind": "orbit", "count": 200, "phi": -30.0,
                         "radius": 4.0}}
    specs = take(traffic, 6)
    thetas = [s.theta for s in specs]
    assert np.allclose(np.diff(thetas)[::2], 2.0)
    assert np.allclose((thetas[2] - thetas[0]) % 360.0, 1.8)


@pytest.mark.parametrize("arrivals, extra, n", [
    ("periodic", {"per_arrival": 2}, 2 * 30 * 10),
    ("onoff", {"on_s": 2.0, "off_s": 3.0}, 30 * 2 * 2),
])
def test_arrival_kinds(arrivals, extra, n):
    due = loadgen.arrivals({"rate_per_s": 30.0, "arrivals": arrivals,
                            **extra}, 10.0)
    assert len(due) == n and np.all(np.diff(due) >= 0) and due[-1] < 10.0
    if arrivals == "periodic":
        assert np.array_equal(due[::2], due[1::2])
        assert np.allclose(np.diff(due[::2]), 1 / 30.0)
    else:
        # nothing arrives in the silent spells [2, 5) and [7, 10)
        assert not np.any(((due % 5.0) >= 2.0))


def test_an_unknown_arrival_kind_is_an_error():
    with pytest.raises(ValueError, match="arrivals"):
        loadgen.arrivals({"rate_per_s": 1.0, "arrivals": "bursty"}, 5.0)
