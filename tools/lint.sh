#!/usr/bin/env bash
# Static-analysis entry point: rule self-test corpus first (a lobotomized
# rule must not green-light the tree scan; the selftest also fails any
# ORPHANED corpus file no registered rule claims), then the full-tree
# two-phase scan — all 32 rules incl. the lockset family (GL121-GL123
# data-race/deadlock detection over per-object lock identity, GL125
# callback-under-lock, GL126 check-then-act split across two guarded
# regions, GL127 blocking waits under a contended lock identity) and
# GL124 committed-JSON hygiene run in this default pass. The summary
# prints the per-phase timing split (phase1 parse+index, phase2 rules)
# so a gate-cost regression is attributable at a glance. Extra args
# pass through to the tree scan (e.g. --sarif for CI annotation):
#   tools/lint.sh --show-baselined
#   tools/lint.sh --write-baseline      # triage mode: regenerate baseline
# Fast pre-commit loop (diff-scoped phase 2, full-tree phase 1):
#   python -m tools.graftlint --changed
set -euo pipefail
cd "$(dirname "$0")/.."

python tools/metrics_snapshot.py --selfcheck
python -m tools.graftlint --selftest
python -m tools.graftlint paddle_tpu/ tests/ tools/ "$@"
# serving gates (host-deterministic step/chunk/span accounting; a few
# minutes total on CPU via interpret mode). Skip with LINT_SKIP_SERVE=1
# when iterating on pure static-analysis changes.
if [ "${LINT_SKIP_SERVE:-0}" != "1" ]; then
  python tools/serve_bench.py --check tools/serve_ragged.json
  python tools/serve_bench.py --check tools/serve_spec.json
  python tools/serve_bench.py --check tools/serve_prefix.json
  # tensor-parallel gate: on the virtual 8-device mesh the kv-head-
  # sharded engine must stay token-exact vs single-chip at TP=2/4/8
  # across plain/chunked/spec/prefix, per-device KV high-water bytes
  # must be exactly 1/tp, the per-step psum payload must match the
  # committed aval math, and warmup must cover every compile bucket
  # per mesh shape
  python tools/serve_bench.py --check tools/serve_tp.json
  # SLO-monitor gate: heavy-tail workload, windowed p99s under the
  # declared objectives, zero burn-rate breaches, monitor neutrality
  python tools/serve_monitor.py --check tools/serve_slo.json \
    --no-flight-recorder
  # chaos gate: injected alloc outages / dispatch stalls / dump-write
  # failures / mid-stream cancels + priority preemption — the engine
  # must degrade per-request (never crash), survivors and preempted-
  # and-resumed requests stay token-exact, KV/refcount gauges return
  # to baseline, 0 new compile buckets after warmup
  python tools/serve_chaos.py --check tools/serve_chaos.json
  # gateway gate: the HTTP/SSE front door — concurrent streams (token-
  # exact vs engine.generate(), SSE order == span ring), a mid-stream
  # cancel (KV gauges back to baseline), a deadline, a shed + /healthz
  # degradation, a structured rejection, control-plane schema parses,
  # 0 new compile buckets after warmup
  python tools/serve_gateway.py --check tools/serve_gateway.json
  # multi-replica router gate: N independent engine replicas behind one
  # EngineRouter — every policy (round_robin / least_loaded /
  # prefix_affinity) token-exact vs a single-engine reference on a
  # shared-prefix workload, prefix_affinity strictly beats round_robin
  # on cached-prefix tokens AND prefill sweep tokens (committed exact
  # counts), a crashed replica's queued request resubmits to a survivor
  # token-exact, 0 new compile buckets after per-replica warmup
  python tools/serve_replica.py --check tools/serve_replica.json
  # train_obs gate: per-program cost/memory attribution (FLOPs, bytes,
  # peak HBM, MFU for the paged step / rewind / COW copy / pretrain
  # step), token-exact-neutral telemetry, census leak check — "MFU is
  # a number the CI checks", the training-side serve-gate analogue
  python tools/cost_report.py --check tools/train_obs.json
  # train_health gate: per-layer-group gradient telemetry + divergence
  # detection on a sharded pretrain — telemetry-on loss-bit-exact and
  # compile-neutral, healthy run breach-free, and each injected fault
  # (NaN batch, lr spike, throttled loader) fires exactly its
  # detector(s) once with a schema-valid flight dump
  python tools/train_monitor.py --check tools/train_health.json
  # autotune + quantized-serving gate: the committed winner table must
  # reproduce bit-for-bit from the interpret-mode cost model (sweep is
  # host-deterministic), the tuned engine stays token-exact vs the
  # default config with 0 new compile buckets after warmup, and int8/
  # int4 weight-only engines under continuous batching match the dense
  # weight_quant generate() across all scheduler modes
  python tools/serve_bench.py --check tools/serve_autotune.json
  # fleet-observability gate: REAL multi-process ranks (serving stepper
  # + dp-sharded pretrain) mirroring through RankExporter into one
  # fleet dir while the parent FleetMonitor polls live — healthy leg
  # breach-free with merged counters bit-equal the per-rank sums and
  # merged-histogram quantiles equal pooled ground truth; injected
  # set_dispatch_delay leg fires the straggler detector on exactly
  # that rank with a request_trace-loadable fleet_straggler dump
  python tools/fleet_obs.py --check tools/fleet_obs.json
fi
