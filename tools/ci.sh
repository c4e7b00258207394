#!/usr/bin/env bash
# One-command CI entry (the [U:ci/build.py] + runtime_functions.sh analog).
#
# Runs the evidence tiers in order and prints a per-tier summary:
#   1. unit1     — CPU suite, operator/gluon half (8-device virtual mesh)
#   2. unit2     — CPU suite, remaining fast tiers
#   2b. zoo      — all vision-zoo entries (own tier: ~8 min on 1 core)
#   3. dist      — multi-process kvstore/launcher tier (incl. dist_async)
#   4. examples  — example-script smoke tier
#   5. bench     — CPU rehearsal of the two measurement entry points:
#                  `bench.py --dry-run-cpu` (the headline path runs; prints
#                  "value": null, never a rate) and tests/test_chip_smoke.py
#                  INCLUDING its slow full `chip_smoke.py --dry-run-cpu` run
#                  (train + serve + interpreted kernels at tiny widths).
#                  The real thing is `python chip_smoke.py` on a TPU,
#                  reached only through the chip tool.
#   6. profiler  — tracing-subsystem smoke: tiny train loop with the span
#                  recorder on, chrome-trace file must parse, trace_report
#                  must exit 0, every profiler.incr(...) literal in the
#                  tree must name a declared counter AND the
#                  docs/observability.md counter table must match it
#                  (lint_counters.py), plus the 2-process cluster smoke
#                  (dist_trace_smoke.py): per-rank traces merge into one
#                  offset-corrected timeline and rank 0's /metrics scrape
#                  aggregates every rank; memory_smoke.py: the device-
#                  memory ledger must attribute the train+serve footprint
#                  to named owners, the trace must carry a memory counter
#                  track, and a forced budget breach must produce exactly
#                  one postmortem
#   7. chaos     — fault-injection tier (fixed seed): wire drops/dups/kills
#                  against the async PS with exactly-once accounting, the
#                  2-worker chaos training acceptance run, the
#                  standalone-server SIGKILL+resume subprocess test, and
#                  the elastic dist_sync tier (tests/test_elastic.py):
#                  supervisor kill/resume smoke with exact-loss resume
#                  and the torn-checkpoint restore-refusal matrix
#   8. serving   — inference serving tier: the open-loop throughput-at-SLO
#                  harness in --smoke mode (exits non-zero if any batch
#                  recompiled after warmup — the bucket-miss regression
#                  guard), the continuous-batching generation harness in
#                  --smoke mode (guard raise mode armed; non-zero exit on
#                  any post-warmup compile in the decode loop), plus the
#                  non-slow serving + generation tests
#   9. io        — input-pipeline tier: the synthetic host-bound harness in
#                  --smoke mode (exits non-zero if the async infeed's
#                  consumer stalled after warmup — the host-starvation
#                  regression guard) plus the fast pipeline tests
#  10. parallel  — pipeline/expert-parallel tier: the schedule harness in
#                  --smoke mode (exits non-zero on post-warmup recompiles
#                  in a scheduled step or a bubble-acceptance failure)
#                  plus the fast schedule + MoE + SPMD-parallel tests
#  11. comm      — quantized-collectives tier: the collectives harness in
#                  --smoke mode (exits non-zero on post-warmup recompiles
#                  in the compressed SPMD step, or if the int8 tier stops
#                  moving >= 3.5x fewer gradient bytes than fp32 on either
#                  path — counter-verified) plus the compression tests
#  12. fold      — step-fold tier: the opperf harness in --smoke mode
#                  (exits non-zero if a steady-state folded step is ever
#                  more than ONE host dispatch or recompiles after
#                  warmup) plus the fast fold/overlap tests
#  13. scaling   — goodput/scaling tier: the scaling-curve harness in
#                  --smoke mode (samples/sec-vs-N over the CPU mesh with
#                  per-point goodput ledgers; exits non-zero on a
#                  post-warmup recompile, an efficiency-floor miss, or a
#                  live-vs-merged-trace attribution mismatch), the fast
#                  goodput-ledger tests, then tools/perf_history.py
#                  gating the fresh evidence structurally
#  14. tpu       — (opt-in: CI_TPU=1) on-chip correctness tier; fails, not
#                  skips, when JAX finds no accelerator
#
# The unit tier is split in two so each invocation fits a ~10 min shell on
# a 1-core box (the full suite exceeds one 600 s window there); `unit` is
# accepted as an alias for both halves.
#
# All output is tee'd to ci_logs/ci_<timestamp>.log and the final summary
# is ALSO written to ci_logs/last_summary.txt, so a round's evidence
# survives a dead terminal.
#
# Usage:  tools/ci.sh [tier ...]   # default: all but the opt-in tpu tier
# Env:    CI_TPU=1 adds the tpu tier; CI_PYTEST_ARGS extra pytest flags.
set -u -o pipefail

cd "$(dirname "$0")/.."

mkdir -p ci_logs
STAMP=$(date -u +%Y%m%d_%H%M%S)
LOG="ci_logs/ci_${STAMP}.log"
exec > >(tee -a "$LOG") 2>&1
TEE_PID=$!
# drain the tee before exiting or the log loses its tail (the summary)
finish() { exec >&- 2>&-; [ -n "${TEE_PID:-}" ] && wait "$TEE_PID" 2>/dev/null; }
trap finish EXIT

# CI tiers other than `tpu` run on the virtual CPU mesh whatever the
# machine holds.
CPU_ENV=(env JAX_PLATFORMS=cpu
         XLA_FLAGS="--xla_force_host_platform_device_count=8")

# the operator/gluon half of the suite — the slow compile-heavy files
UNIT1_FILES=(tests/test_operator.py tests/test_operator_core.py
             tests/test_operator_nn.py tests/test_gluon.py
             tests/test_gluon_contrib.py tests/test_rnn.py
             tests/test_optimizer.py)

TIERS=()
for t in "$@"; do
    if [ "$t" = unit ]; then TIERS+=(unit1 unit2); else TIERS+=("$t"); fi
done
[ ${#TIERS[@]} -eq 0 ] && TIERS=(unit1 unit2 zoo dist examples bench profiler chaos serving io parallel comm fold scaling)
[ "${CI_TPU:-0}" = "1" ] && TIERS+=(tpu)

declare -A RESULT
FAIL=0

run_tier() {
    local name="$1"; shift
    echo "===================================================================="
    echo "== tier: $name"
    echo "===================================================================="
    local t0=$SECONDS
    "$@"
    local rc=$?
    if [ $rc -eq 0 ]; then
        RESULT[$name]="PASS ($((SECONDS - t0))s)"
    elif [ $rc -eq 5 ]; then
        # pytest 5 = nothing collected (e.g. a -k filter matching only the
        # other unit half) — not a failure of the selected tests
        RESULT[$name]="PASS/no-tests ($((SECONDS - t0))s)"
    else
        RESULT[$name]="FAIL ($((SECONDS - t0))s)"
        FAIL=1
    fi
}

IGNORE1=()
for f in "${UNIT1_FILES[@]}"; do IGNORE1+=(--ignore="$f"); done

for tier in "${TIERS[@]}"; do
    case "$tier" in
        unit1)
            run_tier unit1 "${CPU_ENV[@]}" python -m pytest "${UNIT1_FILES[@]}" -q \
                ${CI_PYTEST_ARGS:-}
            ;;
        unit2)
            run_tier unit2 "${CPU_ENV[@]}" python -m pytest tests/ -q \
                "${IGNORE1[@]}" \
                --ignore=tests/test_examples.py --ignore=tests/test_dist.py \
                --ignore=tests/test_gluon_model_zoo.py \
                ${CI_PYTEST_ARGS:-}
            ;;
        zoo)
            # all 34 vision-zoo entries (eval_shape at full size + one
            # numeric forward per family) — ~8 min on a 1-core box, so a
            # tier of its own
            run_tier zoo "${CPU_ENV[@]}" python -m pytest \
                tests/test_gluon_model_zoo.py -q ${CI_PYTEST_ARGS:-}
            ;;
        dist)
            run_tier dist "${CPU_ENV[@]}" python -m pytest tests/test_dist.py -q \
                ${CI_PYTEST_ARGS:-}
            ;;
        examples)
            run_tier examples "${CPU_ENV[@]}" python -m pytest tests/test_examples.py -q \
                ${CI_PYTEST_ARGS:-}
            ;;
        bench)
            # CPU rehearsal: tiny batch, 2 steps — proves the headline
            # path and the chip smoke's three phases run; no rate printed
            run_tier bench "${CPU_ENV[@]}" bash -c '
                set -e
                MXNET_TPU_BENCH_BATCH=8 python bench.py --dry-run-cpu
                python -m pytest tests/test_chip_smoke.py -q '"${CI_PYTEST_ARGS:-}"
            ;;
        profiler)
            # tracing smoke: recorder-on train loop -> valid chrome trace,
            # trace_report runs clean, counter-name lint passes (incl. the
            # docs/observability.md counter-table diff), the 2-process
            # cluster smoke: per-rank traces -> offset-corrected merge with
            # one process row per rank, rank-0 /metrics scrape sees both
            # ranks, straggler attribution fires exactly once — and the
            # compile-observability smoke: short train+serve run where
            # compile_report must list every jit site and attribute a
            # deliberately forced shape drift to the exact argument
            # per-run trace path: concurrent ci.sh runs on one box must
            # not race on a shared file
            run_tier profiler "${CPU_ENV[@]}" bash -c '
                set -e
                trace="/tmp/ci_profiler_trace_$$.json"
                trap "rm -f \"$trace\"" EXIT
                python tools/profiler_smoke.py --out "$trace"
                python tools/trace_report.py "$trace" --top 10 >/dev/null
                python tools/lint_counters.py
                python tools/dist_trace_smoke.py
                python tools/compile_smoke.py >/dev/null
                python tools/memory_smoke.py >/dev/null'
            ;;
        chaos)
            # deterministic fault injection: the seed pins the p= fault
            # schedules so a chaos failure reproduces exactly.
            # test_elastic.py adds the dist_sync elastic tier: the 2-proc
            # supervisor kill/resume acceptance (proc.kill_rank at a fixed
            # step, exact-loss resume, zero steady-state recompiles) and
            # the torn-checkpoint restore-refusal matrix (SIGKILL at every
            # elastic.kill_* point)
            run_tier chaos "${CPU_ENV[@]}" env MXNET_FAULT_SEED=0 \
                python -m pytest tests/test_chaos.py tests/test_elastic.py \
                -q ${CI_PYTEST_ARGS:-}
            ;;
        serving)
            # serving tier: the smoke harnesses ARE the regression guards
            # (serving.py exits non-zero if any batch bound/compiled after
            # warmup; generation.py exits non-zero if the continuous-
            # batching decode loop compiled anything post-warmup under
            # guard raise mode), then the fast serving + generation tests
            run_tier serving "${CPU_ENV[@]}" bash -c '
                set -e
                python benchmark/opperf/serving.py --smoke >/dev/null
                python benchmark/opperf/generation.py --smoke >/dev/null
                python -m pytest tests/test_serving.py tests/test_generation.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"
            ;;
        io)
            # input-pipeline tier: the smoke harness IS the
            # host-starvation regression guard (non-zero exit if the
            # infeed's consumer stalled after warmup at the autotuned
            # depth), then the fast pipeline tests
            run_tier io "${CPU_ENV[@]}" bash -c '
                set -e
                python benchmark/opperf/input_pipeline.py --smoke >/dev/null
                python -m pytest tests/test_io_pipeline.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"
            ;;
        parallel)
            # pipeline/expert-parallel tier: the opperf harness in
            # --smoke mode IS the regression guard (non-zero exit on any
            # post-warmup recompile in a scheduled step, or if 1F1B's
            # measured bubble stops beating GPipe's / leaves 1.5x of the
            # analytic (P-1)/(M+P-1) bound), then the fast schedule +
            # MoE + SPMD-parallel tests
            run_tier parallel "${CPU_ENV[@]}" bash -c '
                set -e
                python benchmark/opperf/pipeline.py --smoke >/dev/null
                python -m pytest tests/test_pipeline_moe.py tests/test_parallel.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"
            ;;
        comm)
            # quantized-collectives tier: the opperf harness in --smoke
            # mode IS the regression guard (non-zero exit on any
            # post-warmup recompile in the compressed SPMD step, or an
            # int8 bytes-on-wire ratio below the 3.5x acceptance floor on
            # either gradient path — per-HOP for the ring half of the
            # default psum/ring A/B), then the compression tests
            run_tier comm "${CPU_ENV[@]}" bash -c '
                set -e
                python benchmark/opperf/collectives.py --smoke >/dev/null
                python -m pytest tests/test_grad_compression.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"
            ;;
        fold)
            # step-fold tier: the opperf harness in --smoke mode IS the
            # regression guard (non-zero exit if the folded step stops
            # being exactly ONE host dispatch, or recompiles in steady
            # state after warmup), then the fast fold/overlap tests
            run_tier fold "${CPU_ENV[@]}" bash -c '
                set -e
                python benchmark/opperf/step_fold.py --smoke >/dev/null
                python benchmark/opperf/step_fold.py --k --smoke >/dev/null
                python -m pytest tests/test_step_fold.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"
            ;;
        scaling)
            # goodput/scaling tier: the harness in --smoke mode IS the
            # regression guard (each curve point is a fresh subprocess
            # under MXNET_COMPILE_GUARD=raise; non-zero exit on a
            # post-warmup recompile, an efficiency-floor miss, or if the
            # live numbers stop matching the merged per-rank trace
            # ledgers), the fast goodput-ledger tests, then perf_history
            # gates this evidence structurally
            run_tier scaling "${CPU_ENV[@]}" bash -c '
                set -e
                ev="/tmp/ci_scaling_evidence_$$.json"
                trap "rm -f \"$ev\"" EXIT
                python benchmark/opperf/scaling.py --smoke --json "$ev" >/dev/null
                python -m pytest tests/test_goodput.py -q -m "not slow" '"${CI_PYTEST_ARGS:-}"'
                python tools/perf_history.py --scaling "$ev"'
            ;;
        tpu)
            run_tier tpu env MXNET_TEST_CTX=tpu python -m pytest tpu_tests/ -q \
                ${CI_PYTEST_ARGS:-}
            ;;
        *)
            echo "unknown tier: $tier" >&2; exit 2
            ;;
    esac
done

{
    echo "===================================================================="
    echo "== CI summary ($STAMP, log: $LOG)"
    for tier in "${TIERS[@]}"; do
        printf '  %-10s %s\n' "$tier" "${RESULT[$tier]:-SKIPPED}"
    done
} | tee ci_logs/last_summary.txt
exit $FAIL
