"""The control of the comparison that decides ``correct``.

The control is the plain reference put in the program's place and
computed one precision step below what the configuration states: its
pixels, for the requests and pixels a run of the cell would compare,
are held against the float32 reference by the same ``check``. A limit
is sound only where the control fails it. The benchmark's own runs do
not run this.

    python3 bench/control.py --workload icarus.batch --seeds 11 12 13

prints, per seed and per precision (``bfloat16``: operands rounded once;
``high``: three bfloat16 passes), the widest gap and whether ``check``
passed it, and exits 2 without a TPU.
"""
from __future__ import annotations

import itertools
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, loadgen  # noqa: E402


def control_window(cell: harness.Cell, seed: int) -> loadgen.Window:
    """The requests a run would deliver first, as delivered records:
    the closed loop's first ``in_flight`` frames, or the open loop's
    first ``check.requests`` arrivals."""
    chk = {**harness.DEFAULT_CHECK, **cell.traffic.get("check", {})}
    n = (cell.traffic["in_flight"] if cell.traffic["loop"] == "closed"
         else chk["requests"])
    recs = []
    for rid, spec in enumerate(itertools.islice(
            loadgen.requests(cell.traffic, seed), n)):
        recs.append(loadgen.Record(spec, None, 0.0, rid, "ok", 0.0, 0.0))
    return loadgen.Window(recs, 0.0, 0.0)


def control_images(cell: harness.Cell, seed: int, window, precision: str):
    """Each record's frame with the control's colours at the pixels a run
    compares (NaN elsewhere, never read)."""
    picked = harness.sample(window, cell, seed)
    pix = harness.reference_pixels(cell, seed, picked, precision)
    images = {}
    for (r, px), rgb in zip(picked, pix):
        img = np.full((r.spec.hw ** 2, 3), np.nan, np.float32)
        img[px] = rgb
        images[r.rid] = img.reshape(r.spec.hw, r.spec.hw, 3)
    return images


def reading(cell: harness.Cell, seed: int, precision: str):
    """(checks, per-pixel gaps) of the control at ``precision``."""
    window = control_window(cell, seed)
    images = control_images(cell, seed, window, precision)
    gaps = harness.pixel_gaps(cell, seed, window, images)
    return harness.check(cell, window, gaps, 0), gaps


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", nargs="+", default=["bfloat16", "high"])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.REPO / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.say("control: FAIL: no TPU; the control is read on the chip")
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        for prec in args.precision:
            t0 = time.perf_counter()
            c, gaps = reading(cell, seed, prec)
            print(f"control {cell.name} seed {seed} {prec}: passed "
                  f"{harness.passed(c)}; pixel gaps "
                  f"{harness.gap_summary(gaps)} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
