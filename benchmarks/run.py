"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table1     # one

Prints ``name,us_per_call,derived`` CSV lines. The ``fusion`` suite
persists its serving-pipeline comparison (seed tile loop vs single
dispatch vs kernel paths: wall_s / rays_per_s / samples_per_s), and the
``serving`` suite its multi-tenant engine numbers (req/s, p50/p95/p99
latency split into queueing vs service, dispatch savings, cache hit
rate, the depth>=2 pipelined-executor pass — under the ``serving``
key), into ``BENCH_plcore.json`` at the repo root: the top-level fields
are the LATEST run, and the append-only ``history`` list (git SHA, date,
plus whichever suites ran) records every canonical-scale run so the
cross-PR perf trajectory survives re-runs instead of being overwritten.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time


def _git_sha(root: pathlib.Path):
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, check=True).stdout.strip()
    except Exception:
        return None


def main() -> None:
    from benchmarks import (fig8_rmcm_psnr, plcore_fusion, roofline,
                            sampling_twopass, serving_engine, table1_energy)
    suites = {
        "table1": table1_energy.run,
        "fig8": fig8_rmcm_psnr.run,
        "psnr": fig8_rmcm_psnr.run,     # alias; persists into BENCH json
        "sampling": sampling_twopass.run,
        "fusion": plcore_fusion.run,
        "serving": serving_engine.run,
        "roofline": roofline.run,
    }
    # "fig8" and "psnr" are one suite: normalize so results persist once
    pick = [("psnr" if a == "fig8" else a)
            for a in sys.argv[1:] if not a.startswith("-")]
    names = list(dict.fromkeys(pick)) or [
        n for n in suites if n != "fig8"]
    print("name,us_per_call,derived")
    results = {}
    for n in names:
        t0 = time.time()
        out = suites[n]()
        if isinstance(out, dict):
            results[n] = out
        print(f"# suite {n} done in {time.time() - t0:.1f}s", flush=True)
    # CI smoke runs (BENCH_PLCORE_HW / BENCH_SERVING_*) must not clobber
    # the canonical cross-PR trajectory numbers with shrunken-scale timings
    smoke = any(os.environ.get(k) is not None
                for k in ("BENCH_PLCORE_HW", "BENCH_SERVING_SCENES",
                          "BENCH_SERVING_REQUESTS", "BENCH_SERVING_TILE",
                          "BENCH_FIG8_STEPS", "BENCH_FIG8_HW"))
    persist = {k: results[k] for k in ("fusion", "serving", "psnr")
               if k in results}
    if persist and not smoke:
        root = pathlib.Path(__file__).resolve().parent.parent
        path = root / "BENCH_plcore.json"
        prev, history = {}, []
        if path.exists():
            try:
                prev = json.loads(path.read_text())
                history = prev.pop("history", [])
                if not history and "variants" in prev:
                    # pre-history file: fold its latest run in so the
                    # trajectory keeps the earliest data point
                    history = [{"sha": None, "date": None, **prev}]
            except Exception:
                prev, history = {}, []
        # history entries carry ONLY what this run measured; the
        # top-level latest doc updates per-suite (fusion fields at the
        # top level, engine numbers under "serving") and keeps the other
        # suite's previous latest
        entry = {"sha": _git_sha(root), "date": time.strftime("%Y-%m-%d")}
        doc = dict(prev)
        if "fusion" in persist:
            entry.update(persist["fusion"])
            kept = {k: doc[k] for k in ("serving", "psnr") if k in doc}
            doc = dict(persist["fusion"])
            doc.update(kept)
        if "serving" in persist:
            entry["serving"] = persist["serving"]
            doc["serving"] = persist["serving"]
        if "psnr" in persist:
            entry["psnr"] = persist["psnr"]
            doc["psnr"] = persist["psnr"]
        doc["history"] = history + [entry]
        path.write_text(json.dumps(doc, indent=2) + "\n")
        print(f"# wrote {path} ({len(doc['history'])} history entries)",
              flush=True)


if __name__ == "__main__":
    main()
