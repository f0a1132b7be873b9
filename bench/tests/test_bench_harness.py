"""A whole run of a cell on the CPU at a tiny size, past the harness's
look for a chip: the comparison that decides ``correct`` passes the
program, and fails it with a fault planted in the timed path and with
the control (the reference in bfloat16) in the program's place."""
import time

import jax
import numpy as np
import pytest

from bench import control, harness

TINY = dict(trunk_layers=4, trunk_width=64, skip_at=[2], color_width=32,
            pos_freqs=6, dir_freqs=3, n_coarse=16, n_fine=16,
            image_hw=[24, 24], kernel_interpret=None)
# the program's mean gap at this size is under 1e-5 (f32 on the CPU);
# the control's is above 1e-3 on these seeds
TINY_LIMITS = {"mean_abs_err": 2e-4, "share_over_1e-3": 0.15}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config["nerf"].update(TINY)
    cell.traffic.update(scenes=min(cell.traffic["scenes"], 2))
    if cell.traffic["loop"] == "open":
        cell.traffic.update(hw=[16, 24], rate_per_s=20.0)
    else:
        cell.traffic.update(hw=[24])
    cell.limits = dict(TINY_LIMITS)
    return cell


def run(cell, seed=2 ** 33 + 3):
    return harness.run_cell(cell, seed, 1.0, False, jax.devices(),
                            time.perf_counter(), grace_s=20.0)


@pytest.mark.parametrize("name", ["icarus.batch", "icarus.live"])
def test_a_sound_run_is_correct(name):
    out = run(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["mean_abs_err"]["value"] < 1e-5
    assert out["metrics"]["setup_s"]["value"] > 0


def _altered(rgb, state):
    """A pixel channel altered where the tile is produced."""
    return rgb.at[::7, 1].add(0.02)


def _half_left_out(rgb, state):
    """Half of each tile's rays never rendered: the background shows."""
    return rgb.at[rgb.shape[0] // 2:].set(1.0)


def _stale(rgb, state):
    """Every tile returns the first tile's pixels: the state never moves
    on from the first dispatch."""
    state.setdefault("first", rgb)
    return state["first"]


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _stale],
                         ids=["altered", "half_left_out", "stale"])
@pytest.mark.parametrize("name", ["icarus.batch", "icarus.live"])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.core.pipeline import PackedPlcore
    produce = PackedPlcore.dispatch_tile
    state = {}

    def broken(self, *a, **kw):
        rgb, cost = produce(self, *a, **kw)
        return fault(rgb, state), cost

    monkeypatch.setattr(PackedPlcore, "dispatch_tile", broken)
    out = run(tiny_cell(name))
    assert not out["correct"]
    assert (out["checks"]["mean_abs_err"]["value"]
            > TINY_LIMITS["mean_abs_err"])


@pytest.mark.parametrize("seed", [5, 2 ** 35 + 1])
def test_the_control_is_not_correct(seed):
    cell = tiny_cell("icarus.live")
    c, _ = control.reading(cell, seed, "bfloat16")
    assert c["pixels_checked"]["value"] >= c["pixels_checked"]["at_least"]
    assert not harness.passed(c), c


def test_a_run_that_delivers_nothing_is_not_correct():
    cell = tiny_cell("icarus.live")
    window = control.control_window(cell, 1)
    for r in window.records:
        r.status, r.due = "rejected", 0.0
    c = harness.check(cell, window, harness.pixel_gaps(cell, 1, window, {}),
                      0)
    assert c["pixels_checked"]["value"] == 0
    assert c["unanswered"]["value"] == len(window.records)
    assert not harness.passed(c)
    assert np.isinf(c["mean_abs_err"]["value"])


@pytest.mark.parametrize("status, due, deadline_ms, counts", [
    (None, 1.0, None, True),          # due and never answered
    (None, None, None, False),        # closed loop, still in flight
    ("ok", 1.0, None, False),
    ("rejected", 1.0, None, True),    # no deadline explains it
    ("expired", 1.0, 250.0, False),   # its own deadline passed
    ("rejected", None, 250.0, False),
])
def test_only_unexplained_failures_count_against_correct(status, due,
                                                         deadline_ms,
                                                         counts):
    from bench import loadgen
    spec = loadgen.Spec(0, 16, 0.0, -30.0, 4.0, deadline_ms=deadline_ms)
    rec = loadgen.Record(spec, due, 0.0, 0, status)
    assert harness.unexplained(rec) is counts


@pytest.mark.parametrize("trace, window",
                         [(0, 51.0), (1, harness.TRACE_WINDOW_S)])
def test_main_runs_the_window_and_caps_a_traced_one(trace, window,
                                                    monkeypatch, capsys):
    from types import SimpleNamespace
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    seen = {}

    def fake_run(cell, seed, seconds, traced, devices, t_start):
        seen.update(seconds=seconds, traced=traced)
        return {"correct": True}

    monkeypatch.setattr(harness, "run_cell", fake_run)
    rc = harness.main(["--workload", "icarus.live", "--seed", str(2 ** 40),
                       "--seconds", "51", "--trace", str(trace)])
    assert rc == 0 and seen == {"seconds": window, "traced": bool(trace)}
    assert capsys.readouterr().out.strip().endswith('{"correct": true}')


def test_main_without_a_tpu_prints_no_result(capsys):
    rc = harness.main(["--workload", "icarus.live", "--seed", "1",
                       "--seconds", "1"])
    assert rc == 2 and capsys.readouterr().out == ""
