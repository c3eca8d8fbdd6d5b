#!/bin/bash
# Tier-1 verify gate — run from the repo root (or anywhere; this cd's
# home first).  Prints DOTS_PASSED=<n> at the end and exits with
# pytest's status, so CI and humans invoke the exact same command the
# roadmap promises (the pytest line below is verbatim ROADMAP.md).
#
# Smoke-budget audit (PR 13, re-audited PR 20): the non-gating smokes
# below carry their own wrappers (900+420+300+420+420+420+420+420+
# 420+420+300+900+720+720+600+780+600 ≈ 160 min worst case) — far past the
# 870 s the GATING pytest line gets.  Each wrapper deliberately EXCEEDS
# its tool's documented internal budget contract (serve_smoke sums to
# ~300 s under its 420 s wrapper, health 900, fleet 720, stream ~560
# under 720, slo 600, chaos 780, ctrl 600): a stalled smoke must die to
# its OWN deadline
# with its own JSON diagnostic, never to the outer timeout — so the
# wrappers must not be trimmed below the contracts.
# The starvation fix is the gate instead: set DSOD_T1_FAST=1 and every
# non-gating smoke is skipped, so a machine that wants only the 870 s
# gating wrapper runs exactly it.
cd "$(dirname "$0")/.." || exit 1
echo "== dsodlint: AST invariant lint — traced-purity / lock-discipline / env + metrics coherence / accounting seams (GATING; pure-CPU, runs under DSOD_T1_FAST too) =="
timeout -k 10 120 python tools/dsodlint.py --fail-on-new
dsodlint_rc=$?
if [ "$dsodlint_rc" -ne 0 ]; then
  echo "dsodlint FAILED (rc=$dsodlint_rc) — fix the finding, add a reasoned pragma, or (for an INTENDED new invariant surface) --update-baseline; see docs/STATIC_ANALYSIS.md"
fi
if [ -n "${DSOD_T1_FAST:-}" ]; then
  echo "== DSOD_T1_FAST set: skipping all non-gating smokes =="
else
echo "== HLO relayout guard incl. conv_impl + grad-collective comm arms (recorded, non-gating) =="
timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/hlo_guard.py \
  || echo "hlo_guard smoke failed (non-gating)"
echo "== fused-conv interpret exactness smoke: kernel vs XLA arm bitwise/1-ulp on CPU (recorded, non-gating; the full suite below gates it) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_pallas_conv.py -q -p no:cacheprovider \
  -k "bitwise or one_ulp or int8_dequants" \
  || echo "fused-conv exactness smoke failed (the main suite below still gates it)"
echo "== roofline --xla-check (recorded, non-gating) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/roofline.py --xla-check \
  || echo "roofline xla-check smoke failed (non-gating)"
echo "== step-chunking k-equivalence smoke (recorded; the full suite below gates it) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_step_chunking.py -q -k bitwise_smoke -p no:cacheprovider \
  || echo "step-chunking smoke failed (the main suite below still gates it)"
echo "== sharding-engine equivalence smoke: bucketed/fused DP reduce bitwise the monolithic pmean on the (only) rules engine (recorded; the full suite below gates it) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_sharding_rules.py -q -k rules_smoke -p no:cacheprovider \
  || echo "sharding-engine smoke failed (the main suite below still gates it)"
echo "== serve smoke: real-process server @ bf16 arm, one loadgen round-trip, clean SIGTERM drain (recorded, non-gating) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/serve_smoke.py --precision bf16 \
  || echo "serve smoke failed (non-gating; tests/test_serving.py below gates the in-process side)"
echo "== precision quality gate: per-arm max-Fbeta/MAE deltas vs f32 on the tiny synthetic set (recorded, non-gating) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/precision_gate.py \
  || echo "precision gate smoke failed (non-gating; --fail-on-increase gates locally)"
echo "== gradient wire-compression quality gate: f32 vs bf16 AND int8_ef (error-feedback) trajectory deltas vs the recorded budgets (recorded, non-gating) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/grad_comm_gate.py --arm both \
  || echo "grad comm gate smoke failed (non-gating; --fail-on-increase gates locally)"
echo "== near-dup cache-serving quality gate: near arm max-Fbeta/MAE deltas vs the exact forward on the tiny synthetic set (recorded, non-gating) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/cache_gate.py \
  || echo "cache gate smoke failed (non-gating; --fail-on-increase gates locally)"
echo "== stream-serving quality gate: temporal-replay + EMA-blend max-Fbeta/MAE deltas vs the exact forward on synthetic frame trains (recorded, non-gating) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/stream_gate.py \
  || echo "stream gate smoke failed (non-gating; --fail-on-increase gates locally)"
echo "== metrics-family inventory lint: fleet + trainer /metrics surfaces + flight-recorder ring schema vs tools/metrics_inventory.json (recorded, non-gating) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/metrics_lint.py \
  && timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/metrics_lint.py --ring-selftest \
  || echo "metrics lint failed (non-gating; --update-baseline re-seeds after an INTENDED surface change)"
echo "== model-health smoke: real trainer sidecar under an injected NaN (provenance-attributed alert fire/clear) + real server with quality monitors, shadow scoring, injected drift alert (recorded, non-gating) =="
timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/health_smoke.py \
  || echo "health smoke failed (non-gating; tests/test_modelhealth.py + tests/test_quality_monitor.py below gate the in-process side)"
echo "== fleet smoke: real-process router + remote replica, mixed-tenant loadgen, SIGKILL-mid-fleet degraded health, fleet accounting, clean SIGTERM drain (recorded, non-gating) =="
timeout -k 10 720 env JAX_PLATFORMS=cpu python tools/fleet_smoke.py \
  || echo "fleet smoke failed (non-gating; tests/test_fleet.py below gates the in-process side)"
echo "== stream smoke: real two-replica fleet with streaming armed — per-stream sessions on distinct replicas, temporal-coherence reuse serving, SIGKILL the home replica mid-session → counted re-home, exact six-term accounting, clean SIGTERM drain (recorded, non-gating) =="
timeout -k 10 720 env JAX_PLATFORMS=cpu python tools/stream_smoke.py \
  || echo "stream smoke failed (non-gating; tests/test_streams.py below gates the in-process side)"
echo "== slo smoke: real router + always-500 remote replica, synthetic prober detects the outage via burn-rate alert at ZERO live traffic, /slo consistent with the router book, capacity ledger live on the replica (recorded, non-gating) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/slo_smoke.py \
  || echo "slo smoke failed (non-gating; tests/test_slo.py + tests/test_capacity.py below gate the in-process side)"
echo "== fleet chaos: SIGKILL a replica under open-loop load — zero lost responses, exact accounting, breaker half-open re-admission, flight-recorder pre-kill segments replay + router incident bundle, controller heals the hole under ramped load + supervised replica dies with its controller (recorded, non-gating) =="
timeout -k 10 780 env JAX_PLATFORMS=cpu python tools/fleet_chaos.py \
  || echo "fleet chaos failed (non-gating; tests/test_failover.py + tests/test_serve_chaos.py + tests/test_controller.py + tests/test_flightrecorder.py below gate the in-process side)"
echo "== rollout smoke: canary-gated checkpoint delivery across real subprocesses — NaN-poisoned step rolled back + denylisted + incident bundle, good step promoted fleet-wide (recorded, non-gating) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/ctrl_smoke.py \
  || echo "rollout smoke failed (non-gating; tests/test_controller.py below gates the state-machine side)"
fi
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); if [ "$dsodlint_rc" -ne 0 ]; then echo "t1: FAILING on dsodlint rc=$dsodlint_rc (gating leg)"; exit "$dsodlint_rc"; fi; exit $rc
