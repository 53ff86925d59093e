#!/usr/bin/env bash
# CI entry point (ref: ci/docker/runtime_functions.sh — the executable
# spec of the reference's test matrix). Tiered like the reference's
# sanity_check / unittest / nightly split:
#
#   ci/run_tests.sh sanity          tier-0 static analysis only (graftlint:
#                                   ci/lint.py path-loads mxnet_tpu/analysis
#                                   without executing the runtime package —
#                                   JAX-hazard G-rules + generic W-rules,
#                                   new-vs-baseline gated; still runs when
#                                   the runtime or jax itself is broken)
#   ci/run_tests.sh fast            tier-0 + the quick unit tier
#   ci/run_tests.sh sanitize        native runtime under ASAN/UBSAN + TSAN
#                                   (ref: runtime_functions.sh sanitizer
#                                   builds — SURVEY §5.2)
#   ci/run_tests.sh [full]          lint + the whole suite (default)
#   ci/run_tests.sh full -k expr    extra args go to pytest
#
# Sets the conftest mesh environment explicitly, so that it holds for
# the lint and native tiers too.
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

# 8-device virtual CPU mesh: exercises every dp/tp/sp/pp/ep sharding path
# without TPU hardware (SURVEY §4 distributed-tests row)
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
export PYTHONPATH="$REPO"

cd "$REPO"

TIER="full"
case "${1:-}" in
  sanity|fast|full|sanitize) TIER="$1"; shift ;;
esac

if [ "$TIER" = "sanitize" ]; then
  echo "== tier: sanitize (native ASAN/UBSAN + TSAN) =="
  cd native
  CXX="${CXX:-g++}"
  COMMON="-O1 -g -std=c++17 -fno-omit-frame-pointer -pthread"
  SRCS="test_sanitize.cc engine.cc recordio.cc predict.cc"
  WORK="$(mktemp -d)"          # run-scoped: concurrent CI jobs don't collide
  trap 'rm -rf "$WORK"' EXIT
  "$CXX" $COMMON -fsanitize=address,undefined -fno-sanitize-recover=all \
      -o "$WORK/asan" $SRCS
  ASAN_OPTIONS=detect_leaks=1 "$WORK/asan" "$WORK/roundtrip.rec"
  "$CXX" $COMMON -fsanitize=thread -o "$WORK/tsan" $SRCS
  TSAN_OPTIONS=halt_on_error=1 "$WORK/tsan" "$WORK/roundtrip.rec"
  echo "sanitize tier PASS"
  exit 0
fi

echo "== tier 0: graftlint static analysis (docs/static_analysis.md) =="
# shared-AST + summary-cache + --jobs keep the full scan (incl. the
# interprocedural G15-G19 tier) inside a hard wall-clock budget; on
# failure a SARIF artifact lands next to the baseline for the review UI
LINT_BUDGET_S="${MXNET_TPU_LINT_BUDGET_S:-120}"
LINT_T0=$SECONDS
if ! python ci/lint.py --jobs 0; then
  python ci/lint.py --jobs 0 --format=sarif > ci/graftlint.sarif || true
  echo "graftlint FAILED — SARIF artifact: ci/graftlint.sarif"
  exit 1
fi
LINT_WALL=$((SECONDS - LINT_T0))
echo "graftlint wall-clock: ${LINT_WALL}s (budget ${LINT_BUDGET_S}s)"
if [ "$LINT_WALL" -gt "$LINT_BUDGET_S" ]; then
  echo "tier-0 lint exceeded its ${LINT_BUDGET_S}s budget — the CI" \
       "contract is fast lint; check the summary cache + --jobs path"
  exit 1
fi

if [ "$TIER" = "sanity" ]; then
  exit 0
fi

# chaos smoke: a fast crash-matrix subset (kill the checkpoint writer at
# key phases, prove old-or-new recovery) so a torn-file regression fails
# in seconds, before the unit tiers spend minutes (docs/checkpointing.md)
echo "== tier 0.5: chaos smoke (crash-matrix subset) =="
python -m pytest tests/test_crash_matrix.py -q -k smoke -p no:cacheprovider

# serving smoke: spin the dynamic-batching server on a real thread, push
# 50 mixed requests (incl. an oversized-shape reject), prove bounded
# compiles + clean shutdown (docs/serving.md); the soak test is `slow`
echo "== tier 0.5: serving smoke (dynamic batcher) =="
python -m pytest tests/test_serving.py -q -k smoke -p no:cacheprovider

# warm-start smoke: serve -> stop -> restart on the same AOT cache dir
# -> the second start performs ZERO XLA compiles for the warmed bucket
# set (compile_stats) with bit-identical responses, and a bit-flipped
# entry degrades to a compile with a journaled aot_fallback — the
# bounded-startup guarantee (docs/serving.md AOT cache)
echo "== tier 0.5: warm-start smoke (persistent AOT cache) =="
python -m pytest tests/test_aotcache.py -q -k smoke -p no:cacheprovider

# sharded-serving smoke: the SAME weights served through a 2-device
# tensor-parallel predictor and a plain single-device server answer
# bit-identically (the default plan column-shards the output dim — no
# cross-shard reduction), with the placement journaled shard_place
# (docs/serving.md tensor-parallel predictors)
echo "== tier 0.5: sharded-serving smoke (tensor-parallel bit parity) =="
python -m pytest tests/test_serving_sharded.py -q -k smoke -p no:cacheprovider

# decode smoke: a tensor-parallel server on a 2-device CPU mesh runs 8
# concurrent autoregressive streams with staggered lengths through the
# continuous batcher -> every stream bit-identical to the reference
# within its deadline, ZERO XLA compiles outside the warmed program
# set, and a cancelled stream frees its slot for a successor
# (docs/serving.md continuous batching)
echo "== tier 0.5: decode smoke (continuous batching, zero mid-run compiles) =="
python -m pytest tests/test_decode.py -q -k smoke -p no:cacheprovider

# tenant-fleet chaos smoke: tenant A fed a corrupt committed checkpoint
# + oversized-shape flood + predictor poison while tenant B runs
# closed-loop load on the SAME fleet -> B's p99 stays in its SLO bound
# with zero corruption errors, A quarantines itself with tenant-labeled
# structured errors, the quarantine->half-open->re-admit trail is
# trace-correlated, and the mixed-version reload keeps every response
# stamped with its own tenant's step (docs/serving.md tenant matrix)
echo "== tier 0.5: tenant-fleet chaos smoke (tenant isolation) =="
python -m pytest tests/test_serving_fleet.py -q -k smoke -p no:cacheprovider

# pool chaos smoke: 3 REAL replica worker processes behind the
# health-routed front door under closed-loop load; SIGKILL one ->
# detection within the heartbeat deadline, retries complete on
# survivors inside their deadline budget, zero corrupt responses, the
# respawned replica re-admitted through a half-open breaker probe, and
# the journal reduction (doctor --serving-journal) tells the story —
# bounded wall-clock end to end (docs/serving.md failure matrix)
echo "== tier 0.5: pool chaos smoke (replica SIGKILL -> reroute) =="
python -m pytest tests/test_serving_pool.py -q -k smoke -p no:cacheprovider

# canary deploy chaos smoke: a REGRESSED (CRC-valid, wrong-answer)
# step is canaried onto 1 of 3 replicas under closed-loop load -> the
# sampled output-parity gate trips, the fleet auto-rolls-back within
# the deadline budget, zero responses whose value contradicts their
# version stamp, control replicas never serve the bad root (blast
# radius = the canary set), the rolled-back store stays PINNED against
# the bad-but-newest commit, and the trace-correlated deploy trail is
# rendered by doctor --serving-journal (docs/serving.md canary
# deployment)
echo "== tier 0.5: canary deploy chaos smoke (parity gate -> rollback) =="
python -m pytest tests/test_serving_deploy.py -q -k smoke -p no:cacheprovider

# guardrail chaos smoke: poison a batch (NaN) -> the fused guard skips
# the step bitwise and journals it; a persistent-poison divergence drill
# rolls back bit-exact to the last committed step — the run stays green
# (docs/guardrails.md)
echo "== tier 0.5: guardrail chaos smoke (anomaly skip + rollback) =="
python -m pytest tests/test_guardrails.py -q -k smoke -p no:cacheprovider

# elastic chaos smoke: a real multi-process CPU cohort loses a rank to
# SIGTERM mid-run; the survivor detects it within the heartbeat
# deadline (no hung collective), resizes, restores the newest committed
# checkpoint RESHARDED onto the survivor mesh, and trains to completion
# — plus the 2->1/1->2 bit-exact reshard and corrupt-shard fallback
# (docs/elastic.md)
echo "== tier 0.5: elastic chaos smoke (rank loss -> resharded resume) =="
python -m pytest tests/test_elastic.py -q -k smoke -p no:cacheprovider

# chaos mini-campaign: the five single-fault drills above are also
# registered as conductor scenarios (mxnet_tpu/chaos/scenarios.py), so
# faults COMPOSE: here a seeded 2-fault schedule (torn heartbeat +
# disk_full at the replace phase — the seed pins both) lands mid-window
# on the same 3-replica pool the SIGKILL smoke drives, every declared
# invariant is evaluated, and the CHAOS_rNN.json artifact must
# parse-check; a failing invariant ships a shrunk reproducer and rc 1
# (docs/chaos.md).  Hard wall budget: a hung campaign is a failure,
# not a stall.
echo "== tier 0.5: chaos mini-campaign (composed faults via conductor) =="
CHAOS_DIR="$(mktemp -d)"
timeout -k 10 120 python -m mxnet_tpu.chaos run pool --seed 9 \
    --faults 2 --classes durability,resource --budget 5 \
    --out-dir "$CHAOS_DIR" > /dev/null
python - "$CHAOS_DIR" <<'EOF'
import sys
from mxnet_tpu.chaos.artifact import latest_artifact, read_artifact
path = latest_artifact(sys.argv[1])
doc = read_artifact(path)
kinds = [s["kind"] for s in doc["schedule"]]
assert "disk_full" in kinds, kinds
assert doc["ok"], f"failed invariants: {doc['failed']}"
print(f"chaos mini-campaign PASS: {len(kinds)} composed faults "
      f"({', '.join(kinds)}), artifact {path}")
EOF
rm -rf "$CHAOS_DIR"

# autotune smoke: the closed-loop autotuner's table discipline on CPU —
# a committed tuned table survives the corruption/truncation/envelope
# fuzz matrix (defaults + exact journaled tuned_fallback reason, zero
# crashes), runtime consumers (pallas.dispatch, Server) demonstrably
# load tuned knobs with a journaled tuned_load, and a tuned block is
# bit-identical to the default tiling; the full ≤8-trial search CLI
# loop is `slow` (docs/autotune.md)
echo "== tier 0.5: autotune smoke (tuned-table fuzz + consumer load) =="
python -m pytest tests/test_autotune.py -q -k smoke -p no:cacheprovider

# pallas interpret smoke: every registered custom kernel passes its CPU
# interpret-mode parity gate vs its XLA reference (forward AND custom_vjp
# gradients), the non-TPU fallback journals its reason, and dropout keys
# stay independent under the (layer, tick, shard) fold — a numerics
# regression in the hand-kernel tier fails in seconds (docs/pallas.md)
echo "== tier 0.5: pallas interpret smoke (kernel parity gate) =="
python -m pytest tests/test_pallas.py -q -k smoke -p no:cacheprovider

# observability smoke: one traced training step + one traced serving
# request -> the Chrome-trace/Perfetto export and the Prometheus
# exposition both parse, with compile events and linked request span
# trees present (docs/observability.md)
echo "== tier 0.5: observability smoke (trace + exporters) =="
python -m pytest tests/test_observability.py -q -k smoke -p no:cacheprovider

# distributed-trace smoke: a 3-replica pool under load sharing one
# trace run dir, SIGKILL one replica -> ONE trace_id links the router
# request root to worker-side request spans across the wire, the
# killed replica's flight-recorder dump is present and parseable, and
# the merged cross-process Perfetto trace + doctor --timeline critical
# path assemble from per-process files alone (docs/observability.md)
echo "== tier 0.5: distributed-trace smoke (SIGKILL -> assembled story) =="
python -m pytest tests/test_distributed_trace.py -q -k smoke -p no:cacheprovider

# quick unit tier: core ndarray/op/autograd/gluon/io surface, no
# model-zoo or multi-process tests (ref: runtime_functions.sh unittest
# vs nightly split)
FAST_TESTS=(tests/test_ndarray.py tests/test_operator.py
            tests/test_autograd.py tests/test_io.py tests/test_gluon.py
            tests/test_aux.py tests/test_numpy_ns.py)

if [ "$TIER" = "fast" ]; then
  echo "== tier: fast =="
  exec python -m pytest "${FAST_TESTS[@]}" -q "$@"
fi

echo "== tier: full =="
# slow-marked tests (soak / subprocess CLIs) stay out of the default
# budget; append `-m ''` (or `-m slow`) to opt back in — later -m wins
exec python -m pytest tests/ -q -m "not slow" "$@"
