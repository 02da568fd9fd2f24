#!/usr/bin/env bash
# Tier-1 gate — the ROADMAP.md "Tier-1 verify" command VERBATIM, so local
# builders and CI run the identical check (same timeout, same marker
# filter, same pass-count accounting).  Run from anywhere:
#
#     bash scripts/ci.sh
#
cd "$(dirname "$0")/.." || exit 1

# Static analysis (r11, +concurrency r15): dryadlint + the jaxpr auditor
# + the schedule harness.  Layer 1 replaces the r6-r10 grep lints and (r15)
# machine-checks the threaded host plane's lock discipline (guarded-by
# declarations, no blocking under a lock, the committed lock partial
# order in analysis/goldens/lock_order.json) with the waiver count
# RATCHETED against analysis/goldens/waiver_budget.json.  Layer 2 checks
# the trip-weighted collective census against train._comm_stats, the
# wired-path zero-row-sort contract, kernel-boundary u8/u16 discipline,
# and the committed program digests.  Layer 3 (r15) runs the recorded
# race classes as seed-deterministic schedule drills (batcher stop/start,
# supervisor recovery, rolling push vs death, registry snapshot tearing,
# injector concurrent fire) with runtime deadlock/lock-cycle verdicts.
# Exit codes: 2 = lint/ratchet, 3 = IR invariant, 4 = digest drift,
# 5 = crash, 6 = concurrency contract (static rule or failing drill).
# Intentional program changes: python -m dryad_tpu.analysis --update-goldens
# and commit the goldens diff; new lock nestings edit lock_order.json in
# the same spirit.  CPU-only (traces, never compiles).
env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m dryad_tpu.analysis --ci -q > /tmp/_analysis.log 2>&1
analysis_rc=$?
if [ $analysis_rc -ne 0 ]; then
  echo "ANALYSIS FAIL (exit $analysis_rc): python -m dryad_tpu.analysis --ci (see /tmp/_analysis.log)" >&2
  tail -15 /tmp/_analysis.log >&2
  exit 1
fi
tail -2 /tmp/_analysis.log

# Bench trend ledger (r12): the committed BENCH_r*.json history must be
# regression-free under the spread-aware median check, and the checker
# must actually FLAG a seeded regression (--selftest proves the gate
# fires in both directions, including the suspect-capture veto).
if ! PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/bench_trend.py --check > /tmp/_trend.log 2>&1; then
  echo "TREND FAIL: bench_trend.py --check (see /tmp/_trend.log)" >&2
  tail -8 /tmp/_trend.log >&2
  exit 1
fi
if ! PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/bench_trend.py --selftest > /tmp/_trend_self.log 2>&1; then
  echo "TREND SELFTEST FAIL: the seeded regression was not flagged" >&2
  tail -5 /tmp/_trend_self.log >&2
  exit 1
fi
tail -1 /tmp/_trend_self.log

# Stage-profiler selftest (r13): the timed-fori harness's runtime
# liveness proof must FIRE on the seeded dead-perturbation probe (the
# r5/r10 2x-fast class the AST lint cannot fully catch) and PASS on
# every shipped stage probe — CPU, seconds.
if ! env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m dryad_tpu profile --selftest --quiet > /tmp/_profile_self.log 2>&1; then
  echo "PROFILE SELFTEST FAIL: python -m dryad_tpu profile --selftest (see /tmp/_profile_self.log)" >&2
  tail -5 /tmp/_profile_self.log >&2
  exit 1
fi
tail -1 /tmp/_profile_self.log

# Observability smoke (r9; r12 adds the device-truth families): the CLI's
# live metrics endpoint — train 5 trees through the DEVICE trainer with
# --metrics-port, scrape /healthz + /stats + /metrics while the run is
# up, assert span series non-empty, counters monotone, and the
# dryad_prog_* / dryad_fetch_* families live on the same scrape.
if ! env JAX_PLATFORMS=cpu DRYAD_OBS=1 \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/smoke_obs.py > /tmp/_obs_smoke.log 2>&1; then
  echo "OBS SMOKE FAIL: scripts/smoke_obs.py (see /tmp/_obs_smoke.log)" >&2
  tail -5 /tmp/_obs_smoke.log >&2
  exit 1
fi
tail -1 /tmp/_obs_smoke.log

# Supervisor smoke (r8): two injected faults (one fetch-death) through a
# short supervised run — exactly-once resume per fault, chunk backoff to
# the known-safe 2, well-formed journal, bitwise-equal final model.
if ! env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/smoke_supervisor.py > /tmp/_sup_smoke.log 2>&1; then
  echo "SUPERVISOR SMOKE FAIL: scripts/smoke_supervisor.py (see /tmp/_sup_smoke.log)" >&2
  tail -5 /tmp/_sup_smoke.log >&2
  exit 1
fi
tail -1 /tmp/_sup_smoke.log

# Fleet smoke (r14): REAL subprocess serve replicas behind the router —
# an injected replica_crash (DRYAD_REPLICA_FAULTS drill wire) mid-load
# must cost ZERO failed interactive requests (single-retry budget), and
# the supervisor must journal the crash and respawn the slot.
if ! env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/smoke_fleet.py > /tmp/_fleet_smoke.log 2>&1; then
  echo "FLEET SMOKE FAIL: scripts/smoke_fleet.py (see /tmp/_fleet_smoke.log)" >&2
  tail -5 /tmp/_fleet_smoke.log >&2
  exit 1
fi
tail -1 /tmp/_fleet_smoke.log

# Continual smoke (r19): the multi-generation drill on REAL replicas —
# a sustained covariate shift journals drift_breach, the RetrainScheduler
# append-trains gen-1 (warm-start init_model subprocess), the rolling
# push clears the breach in probation (generation_promoted), and a
# forced bad_generation retrain (DRYAD_CONTINUAL_FAULTS drill wire)
# auto-rolls back by re-pushing the gen-1 artifact — zero failed
# interactive requests, zero unexpected recompiles across the swaps.
if ! env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/smoke_continual.py > /tmp/_continual_smoke.log 2>&1; then
  echo "CONTINUAL SMOKE FAIL: scripts/smoke_continual.py (see /tmp/_continual_smoke.log)" >&2
  tail -5 /tmp/_continual_smoke.log >&2
  exit 1
fi
tail -1 /tmp/_continual_smoke.log

# Serving bench smoke (r7): zero recompiles after warmup across BOTH the
# bucketed (forced-CPU) and sharded (8 fake devices) compiled-entry
# families — warm traffic must be structurally recompile-free.
if ! env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python scripts/bench_serve.py --smoke --sharded > /tmp/_serve_smoke.log 2>&1; then
  echo "SERVE SMOKE FAIL: bench_serve --smoke --sharded (see /tmp/_serve_smoke.log)" >&2
  tail -5 /tmp/_serve_smoke.log >&2
  exit 1
fi
tail -1 /tmp/_serve_smoke.log

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
