#!/usr/bin/env bash
# Tier-1 CI gate: full test suite + model-zoo smoke + a tiny-scale run of
# the serving-pipeline benchmark (seed loop vs single dispatch vs +ERT).
#
#   bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests =="
# coverage gate (when pytest-cov is available): line coverage of the
# repro package must not drop below COV_MIN, and the XML report lands in
# runs/coverage.xml as a CI artifact. The floor is a ratchet — set below
# the suite's measured coverage when introduced; raise it as the suite
# grows, never lower it to make a PR pass. Boxes without pytest-cov
# (the pinned CI image bakes no extra wheels) run the suite uncovered.
COV_MIN="${COV_MIN:-75}"
if python -c "import pytest_cov" 2>/dev/null; then
    mkdir -p runs
    python -m pytest -x -q --cov=repro \
        --cov-report=xml:runs/coverage.xml \
        --cov-report=term --cov-fail-under="$COV_MIN"
    echo "coverage gate OK (>= ${COV_MIN}%, report: runs/coverage.xml)"
else
    echo "pytest-cov not installed; running suite without coverage gate"
    python -m pytest -x -q
fi

echo "== model-zoo smoke =="
python scripts/smoke_check.py

echo "== plcore pipeline benchmark (tiny smoke; two_pass_fused gate) =="
# ENFORCE makes the run fail if the one-kernel two_pass_fused variant
# regresses below single_dispatch throughput on the same run
BENCH_PLCORE_HW=16 BENCH_PLCORE_ENFORCE=1 python -m benchmarks.run fusion

echo "== serving engine smoke (3 scenes, deterministic trace) =="
# fixed-seed closed-loop trace through the multi-tenant engine at its
# default pipeline depth (2); --check fails the run unless every request
# completed, the scene-cache hit rate is > 0, coalescing issued no more
# dispatches than per-request, >= 2 tiles were in flight, and the
# framebuffers are BIT-IDENTICAL to a synchronous depth=1 rerun
python -m repro.launch.serve --mode engine --scenes 3 --requests 9 \
    --hw-mix 12,16 --tile-rays 128 --loop closed --seed 0 --check

echo "== pipelined engine smoke (depth-3 async executor) =="
# same trace through the double-buffered executor; --check additionally
# asserts pipelining engaged (>= 2 tiles in flight) and that the
# framebuffers are BIT-IDENTICAL to a synchronous depth=1 rerun
python -m repro.launch.serve --mode engine --scenes 3 --requests 9 \
    --hw-mix 12,16 --tile-rays 128 --loop closed --seed 0 \
    --pipeline-depth 3 --check

echo "== chaos smoke (seeded fault injection through the engine) =="
# fixed-seed chaos plan (injected dispatch errors, corrupted tiles,
# loader failures, stragglers) over the deterministic closed-loop trace;
# --check fails the run unless every request reached a terminal status,
# >= 1 fault was actually injected, goodput >= 0.75, and every request
# that ended ok is BIT-IDENTICAL to a clean (no-fault) rerun — i.e. the
# retry -> oracle recovery ladder reconstructs exact pixels
python -m repro.launch.serve --mode engine --scenes 3 --requests 9 \
    --hw-mix 12,16 --tile-rays 128 --loop closed --seed 0 \
    --inject-faults --fault-seed 0 --check

echo "== 2-host cluster chaos smoke (8 fake devices split 4+4, host kill) =="
# multi-host fabric: two per-host executors + caches, each a replica on
# its own device group's first device, behind the global scheduler; host
# 1 (the residency-affinity winner for this seed) is killed at global
# dispatch 6 with depth-2 pipelining, so in-flight tiles MUST fail over.
# --check fails the run unless every submit reached exactly one terminal
# status, goodput >= 0.75, >= 1 host kill fired, >= 1 tile was
# redispatched cross-host, and every ok request is BIT-IDENTICAL to a
# clean single-host rerun
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.serve --mode engine --scenes 3 --requests 10 \
    --hw-mix 12,16 --tile-rays 128 --loop closed --seed 0 \
    --hosts 2 --host-kill "1:@6" \
    --pipeline-depth 2 --check

echo "== observability chaos smoke (trace + metrics export, span-chain gate) =="
# the chaos trace rerun with lifecycle tracing armed: --check additionally
# gates span-chain integrity in-process (every dispatched tile reaches a
# terminal scatter/drop, every submit exactly one terminal request span),
# then check_trace.py re-validates the WRITTEN artifacts — Chrome trace
# JSON schema + the same chain check replayed from the file, and the
# Prometheus text parses with the engine registry merged in
python -m repro.launch.serve --mode engine --scenes 3 --requests 9 \
    --hw-mix 12,16 --tile-rays 128 --loop closed --seed 0 \
    --inject-faults --fault-seed 0 --check \
    --trace-out runs/ci_trace.json --metrics-out runs/ci_metrics.prom
python scripts/check_trace.py runs/ci_trace.json runs/ci_metrics.prom

echo "== adaptive sampling smoke (ASDR: budget classes + trunk memo) =="
# per-scene density calibration + budget-bucketed dispatch + cross-ray
# trunk memoization over the fused-kernel engine. --scene-bias -0.5
# carves the canonical mixed scene (real empty space, all classes
# populated). --check fails the run unless every tile took the adaptive
# path, the trunk memo served >= 1 hit, EVERY budget class was exercised
# by real rays, and an adaptive-OFF rerun of the same trace is
# BIT-IDENTICAL to the synchronous current pipeline (the flag off must
# change nothing)
python -m repro.launch.serve --mode engine --scenes 3 --requests 10 \
    --loop closed --seed 0 --kernel --fuse-two-pass \
    --adaptive-sampling --scene-bias -0.5 --memo-mb 8 \
    --hw-mix 16 --tile-rays 128 --check

echo "== adaptive PSNR gate (fig8 smoke: drop vs static fused <= 0.1 dB) =="
# QAT-trains the tiny scene at smoke scale and renders it through the
# static fused kernel vs the adaptive path; the adaptive render may cost
# at most PSNR_DROP_GATE_DB (0.1 dB) of PSNR-vs-GT
BENCH_FIG8_STEPS=120 BENCH_FIG8_HW=20 python - <<'EOF'
from benchmarks import fig8_rmcm_psnr as f
out = f.run()
drop, gate = out["adaptive_psnr_drop_db"], out["psnr_drop_gate_db"]
assert drop <= gate, (
    f"adaptive PSNR drop {drop} dB exceeds the {gate} dB gate "
    f"(fused_vs_gt={out['fused_vs_gt']}, "
    f"adaptive_vs_gt={out['adaptive_vs_gt']})")
print(f"adaptive PSNR gate OK (drop {drop} dB <= {gate} dB)")
EOF

echo "== docs link check =="
python scripts/check_docs_links.py

echo "CI OK"
