#!/usr/bin/env bash
# Single build+test entry (reference: paddle/scripts/paddle_build.sh —
# SURVEY.md §2.4 "CI entry").  Builds the native core, runs its gtest,
# then the full Python suite on the 8-device CPU-sim mesh.  The benchmark
# (BENCHMARK.json, benchmarks/run.py) runs on the chip, not here.  Usage:
#   ./ci.sh [fast|chaos|chaos-serve|chaos-router]
#   fast         — skip slow tests, stop at first failure
#   chaos        — ONLY the slow-marked fault-domain drills (gang restart,
#                  heartbeat eviction, full restart-resume), each run under a
#                  hard external timeout so a broken watchdog cannot wedge CI
#   chaos-serve  — the SERVING fault-domain drills (prefill hang -> watchdog
#                  -> warm restart, NaN isolation, SIGTERM drain, deadline
#                  eviction), slow HTTP drill included, plus the speculative
#                  and 4-tenant mixed-adapter reruns and the ISSUE 20
#                  session repin drill (kill -9 the pinned replica), under
#                  a hard timeout
#   chaos-router — the MULTI-REPLICA router drills (ISSUE 9): 2 replicas,
#                  injected probe flap + kill -9 under Poisson load, breaker
#                  cycle, rolling drain — exactly-once resolution end to end
#   chaos-router-ha — the FRONT-DOOR kill -9 drill (ISSUE 17): kill the
#                  router ITSELF mid-soak under the runtime sanitizer; the
#                  warm standby replays the durable journal, re-probes the
#                  fleet, and resumes serving — exactly-once, bit-identical
#                  tokens, breaker/band state survives the takeover
#   soak         — the ISSUE 16 acceptance soak: ~10 minutes of step-function
#                  traffic (diurnal Poisson + 4x burst + adversarial mix)
#                  against subprocess replicas while the closed-loop
#                  autoscaler scales 1 -> N -> 1 through scheduled kill -9 /
#                  hang / flap / failed-spawn chaos; exactly-once resolution,
#                  miss rate under the bar, flight dump replays the decisions.
#                  Runs over a TP-sharded fleet (SOAK_TP, default 2): every
#                  worker boots --tp N on the 8-device CPU-sim mesh
#   chaos-disagg — the DISAGGREGATED-serving drills (ISSUE 19): the full
#                  prefill/decode handoff suite plus the slow kill -9 drill —
#                  2 prefill + 2 TP-sharded decode subprocess workers under
#                  concurrent load, SIGKILL one of each mid-handoff /
#                  mid-stream; every request resolves exactly once with
#                  tokens bit-identical to the single-engine reference
set -euo pipefail
cd "$(dirname "$0")"

MODE="${1:-}"
case "${MODE:-}" in
  ""|fast|chaos|chaos-serve|chaos-router|chaos-router-ha|soak|chaos-disagg) ;;
  *)
    echo "usage: ./ci.sh [fast|chaos|chaos-serve|chaos-router|chaos-router-ha|soak|chaos-disagg]" >&2
    exit 2
    ;;
esac

echo "== static analysis (trace-purity + concurrency lint, GRAFT0xx) =="
# the cheapest gate runs first in EVERY tier: pure-AST, no accelerator,
# seconds — a recompile hazard or unlocked cross-thread mutation fails CI
# before a single test collects
env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis paddle_tpu/ tests/

if [ "$MODE" = "chaos-serve" ]; then
  echo "== serving chaos suite (fault drills + slow HTTP drill, hard 15min cap) =="
  # the drills assert the engine-level watchdog/supervisor recovery; the
  # timeout(1) wrapper is the layer above it — a wedged restart path must
  # fail CI, not hang it.  PADDLE_OBS_DIR collects the flight-recorder
  # dumps the watchdog trips / engine restarts write (asserted below)
  OBS_DIR="$(mktemp -d)/flightrec"
  timeout -k 30 900 env JAX_PLATFORMS=cpu \
      PADDLE_OBS_DIR="$OBS_DIR" \
      python -m pytest tests/test_serving_fault.py \
      -q -p no:cacheprovider
  ls "$OBS_DIR"/flight-*.jsonl >/dev/null 2>&1 \
      || { echo "FAIL: no flight-recorder dump after the watchdog drills" >&2; exit 1; }
  echo "flight-recorder dumps: $(ls "$OBS_DIR" | wc -l) in $OBS_DIR"
  echo "== paged-KV warm-restart drill (ISSUE 7) =="
  # warm restart must preserve the prefix cache AND the compiled set: the
  # first shared-prefix request after restart() is a cache hit served with
  # 0 fresh compiles
  timeout -k 30 600 env JAX_PLATFORMS=cpu \
      python -m pytest \
      "tests/test_paged_kv.py::test_warm_restart_preserves_prefix_cache_no_recompile" \
      -q -p no:cacheprovider
  echo "== fault drills under speculation (ISSUE 11) =="
  # rerun the deterministic serving-fault core with the engine speculating
  # (FLAGS_serve_spec_k=3, env-var override): watchdog warm restart and NaN
  # isolation must hold when the decode path is the batched verify step —
  # restart drops drafter state with the slot table, the replayed request
  # is still bit-identical, and a poisoned slot's NaN cannot leak into a
  # neighbour through the [slots, k+1] verify forward
  timeout -k 30 600 env JAX_PLATFORMS=cpu \
      FLAGS_serve_spec_k=3 \
      python -m pytest \
      "tests/test_serving_fault.py::test_prefill_hang_watchdog_restart_bit_identical" \
      "tests/test_serving_fault.py::test_decode_nan_poisons_only_target_slot" \
      -q -p no:cacheprovider
  echo "== mixed-adapter chaos drill (ISSUE 12) =="
  # the kill -9 drill rerun with 4 LoRA tenants: both subprocess replicas
  # boot --lora a1,a2,a3,a4 (position-seeded -> bit-identical adapter
  # weights fleet-wide), Poisson load cycles the tenants, SIGKILL takes one
  # replica mid-stream — exactly-once resolution, per-tenant outputs
  # bit-identical to a single-process LoRA engine, survivor residency
  # drives adapter-aware pick(), unknown tenant fails typed 404
  timeout -k 30 600 env JAX_PLATFORMS=cpu \
      python -m pytest \
      "tests/test_serving_router.py::test_kill9_chaos_drill_mixed_adapters" \
      -q -p no:cacheprovider
  echo "== session repin drill (ISSUE 20) =="
  # kill -9 the replica holding a session's pinned pages mid-conversation:
  # the router must break the pin (session_repins counter), fall back to a
  # stateless re-prefill on the survivor, and answer the next turn with a
  # 200 bit-identical to a fresh stateless engine — exactly-once preserved
  timeout -k 30 600 env JAX_PLATFORMS=cpu \
      python -m pytest \
      "tests/test_sessions.py::test_router_pins_sessions_and_repins_after_death" \
      -q -p no:cacheprovider
  echo "CHAOS-SERVE OK"
  exit 0
fi

if [ "$MODE" = "chaos-router" ]; then
  echo "== router chaos suite (2-replica failover drills + kill -9 drill, hard 15min cap) =="
  # the whole router file: probe flap -> breaker open/half-open/close,
  # mid-stream replica death -> exactly-once failover, rolling drain with
  # zero drops, and the slow drill — kill -9 of one subprocess replica
  # under Poisson load, survivor outputs bit-identical, Container respawn.
  # timeout(1) is the layer above the router's own deadlines: a wedged
  # replica boot or probe loop must fail CI, not hang it
  timeout -k 30 900 env JAX_PLATFORMS=cpu \
      python -m pytest tests/test_serving_router.py \
      -q -p no:cacheprovider
  echo "CHAOS-ROUTER OK"
  exit 0
fi

if [ "$MODE" = "chaos-router-ha" ]; then
  echo "== front-door HA chaos suite (router kill -9 + takeover, hard 15min cap) =="
  # the whole ISSUE 17 file under the runtime sanitizer: journal crash
  # signatures (torn tail, interior corruption, bit-for-bit compaction),
  # idempotent double-submit/join drills, successor rehydration, and the
  # slow acceptance drill — router.crash fires mid-soak, the standby
  # replays the journal and resumes exactly-once with bit-identical
  # tokens and 0 unexpected recompiles.  PADDLE_OBS_DIR collects the
  # flight dump the dying router writes (asserted below)
  OBS_DIR="$(mktemp -d)/flightrec"
  timeout -k 30 900 env JAX_PLATFORMS=cpu \
      PADDLE_OBS_DIR="$OBS_DIR" \
      FLAGS_debug_sanitize=1 \
      python -m pytest tests/test_router_ha.py \
      -q -p no:cacheprovider
  ls "$OBS_DIR"/flight-*.jsonl >/dev/null 2>&1 \
      || { echo "FAIL: no flight-recorder dump after the router kill -9 drill" >&2; exit 1; }
  echo "flight-recorder dumps: $(ls "$OBS_DIR" | wc -l) in $OBS_DIR"
  echo "CHAOS-ROUTER-HA OK"
  exit 0
fi

if [ "$MODE" = "soak" ]; then
  echo "== autoscaler chaos soak (ISSUE 16 acceptance, hard 18min cap) =="
  # SOAK_DURATION_S (default 600) sets the arrival-clock length; the
  # timeout(1) wrapper is the layer above every in-test deadline — a
  # wedged replica boot, drain, or control loop must fail CI, not hang
  # it.  PADDLE_OBS_DIR collects the post-mortem flight dump the test
  # writes (scaling decisions + chaos, asserted parseable below).
  # SOAK_TP (default 2, ISSUE 19 satellite) shards every worker --tp N
  # over the 8-device CPU-sim mesh, so the control loop's choose_tp
  # device-claim accounting runs against genuinely sharded replicas
  OBS_DIR="$(mktemp -d)/flightrec"
  timeout -k 30 1080 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      PADDLE_OBS_DIR="$OBS_DIR" \
      SOAK_DURATION_S="${SOAK_DURATION_S:-600}" \
      SOAK_TP="${SOAK_TP:-2}" \
      python -m pytest \
      "tests/test_autoscale_soak.py::test_soak_step_function_chaos" \
      -q -p no:cacheprovider
  ls "$OBS_DIR"/flight-*.jsonl >/dev/null 2>&1 \
      || { echo "FAIL: no flight-recorder dump after the soak" >&2; exit 1; }
  echo "flight-recorder dumps: $(ls "$OBS_DIR" | wc -l) in $OBS_DIR"
  echo "SOAK OK"
  exit 0
fi

if [ "$MODE" = "chaos-disagg" ]; then
  echo "== disaggregated-serving chaos suite (ISSUE 19, hard 20min cap) =="
  # the whole handoff file including the slow drill: wire-format typed
  # rejection, export -> reserve -> import bit-identity with frozen
  # compiles on both sides, the in-process crash/drop/decode-death
  # drills, and the subprocess kill -9 drill (2 prefill + 2 decode --tp 2
  # workers; SIGKILL one of each mid-flight, exactly-once resolution,
  # tokens bit-identical to the single-engine reference).  The module is
  # sanitized: an unexpected recompile on either handoff side fails CI
  timeout -k 30 1200 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m pytest tests/test_disagg_serving.py \
      -q -p no:cacheprovider
  echo "CHAOS-DISAGG OK"
  exit 0
fi

if [ "$MODE" = "chaos" ]; then
  echo "== chaos suite (slow fault-domain drills, hard 20min cap) =="
  # the drills themselves assert the in-process watchdog fires; the
  # timeout(1) wrapper is the belt-and-braces layer above it.
  # test_compile_cache.py's slow tests cover the cold-start acceptance:
  # warm gang restart resumes inside the tightened first-step deadline,
  # and a fresh process pays 0 fresh XLA compiles from the warm cache.
  # PADDLE_OBS_DIR collects the flight-recorder dumps the collective
  # watchdog and the gang-restart controller write (asserted below)
  OBS_DIR="$(mktemp -d)/flightrec"
  timeout -k 30 1200 env JAX_PLATFORMS=cpu \
      PADDLE_OBS_DIR="$OBS_DIR" \
      python -m pytest tests/test_fault_tolerance.py tests/test_compile_cache.py \
      -q -m slow -p no:cacheprovider
  ls "$OBS_DIR"/flight-*.jsonl >/dev/null 2>&1 \
      || { echo "FAIL: no flight-recorder dump after the gang-restart drills" >&2; exit 1; }
  echo "flight-recorder dumps: $(ls "$OBS_DIR" | wc -l) in $OBS_DIR"
  echo "CHAOS OK"
  exit 0
fi

echo "== native build =="
cmake -S csrc -B csrc/build -G Ninja -DCMAKE_BUILD_TYPE=Release
cmake --build csrc/build

echo "== native tests =="
./csrc/build/core_test

echo "== python suite (8-device CPU mesh) =="
# chaos/fault-tolerance tests (tests/test_fault_tolerance.py) run here too;
# the multi-process restart-resume test is @pytest.mark.slow and is skipped
# in fast mode (tier-1 runs with -m 'not slow' as well)
PYTEST_ARGS=()
[ "$MODE" = "fast" ] && PYTEST_ARGS=(-x -m "not slow")
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/ -q "${PYTEST_ARGS[@]+${PYTEST_ARGS[@]}}"

echo "== compile-cache cold-start proof (subprocess AOT round-trip, tmpdir cache) =="
# a fresh process must bind the previous process's snapshot: 0 traces,
# 0 fresh XLA compiles (ISSUE 3 acceptance; runs in every tier)
env JAX_PLATFORMS=cpu \
    python -m pytest "tests/test_compile_cache.py::test_second_process_train_step_zero_compiles" \
    -q -p no:cacheprovider

echo "== sync-fallback parity (FLAGS_max_inflight_steps=1) =="
# the async step pipeline must degrade to the strict per-step loop with
# identical behavior; fast mode re-runs the loop-adjacent suites, full
# mode re-runs the whole tier-1 shape under the fallback
SYNC_TESTS=(tests/)
[ "$MODE" = "fast" ] && SYNC_TESTS=(tests/test_async_pipeline.py tests/test_hapi_fleet.py tests/test_io_workers.py)
env JAX_PLATFORMS=cpu \
    FLAGS_max_inflight_steps=1 \
    python -m pytest "${SYNC_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== serving smoke (continuous-batching engine) =="
# the ISSUE 5 acceptance pair in every tier: steady-state decode stays ONE
# executable with zero recompiles under mixed-length traffic, and the HTTP
# front door completes overlapping requests token-exactly (503 on overload)
SERVE_TESTS=(tests/test_serving_engine.py::test_zero_recompiles_after_warmup
             tests/test_serving_engine.py::test_mixed_length_compile_count)
[ "$MODE" != "fast" ] && SERVE_TESTS=(tests/test_serving_engine.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${SERVE_TESTS[@]}" -q -p no:cacheprovider

echo "== paged-KV smoke (ISSUE 7 acceptance subset) =="
# both tiers: the engine token-identical to lock-step generate on mixed
# traffic, and zero recompiles under prefix-hit traffic (COW copies + chunk prefills
# ride warmed executables); fast mode runs that pair, full mode the file
PAGED_TESTS=(tests/test_paged_kv.py::test_paged_matches_lockstep_generate_mixed_traffic
             tests/test_paged_kv.py::test_zero_recompiles_with_prefix_traffic)
[ "$MODE" != "fast" ] && PAGED_TESTS=(tests/test_paged_kv.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${PAGED_TESTS[@]}" -q -p no:cacheprovider

echo "== speculative-decoding smoke (ISSUE 11 acceptance subset) =="
# both tiers: n-gram draft + batched verify emits token-identical greedy
# output vs the plain engine, and acceptance-rate churn (joins, finishes,
# per-request caps, hits AND misses) never grows the compiled set past the
# single warmed verify executable; fast mode runs that pair, full mode the
# whole file (EOS right-trim, mixed spec/plain co-batching, warm restart,
# drain-estimate EWMA, /metrics + trace-span surfaces)
SPEC_TESTS=(tests/test_spec_decode.py::test_spec_greedy_token_identical_to_plain
            tests/test_spec_decode.py::test_zero_recompiles_under_acceptance_churn)
[ "$MODE" != "fast" ] && SPEC_TESTS=(tests/test_spec_decode.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${SPEC_TESTS[@]}" -q -p no:cacheprovider

echo "== multi-tenant LoRA smoke (ISSUE 12 acceptance subset) =="
# both tiers: a mixed-adapter co-batch decodes in the SAME compiled
# executables with per-tenant outputs bit-identical to single-adapter
# engines, and 16 tenants share one compiled decode step with zero
# recompiles (adapter ids ride as traced data); fast mode runs that pair,
# full mode the whole file (arena refcount/LRU, churn-without-recompiles,
# warm restart residency, per-adapter prefix-cache isolation, spec-decode
# composition, HTTP adapter field + 404, adapter-aware router pick)
LORA_TESTS=(tests/test_lora_serving.py::test_mixed_cobatch_bit_identity_zero_recompiles
            tests/test_lora_serving.py::test_sixteen_adapters_cobatch_one_decode)
[ "$MODE" != "fast" ] && LORA_TESTS=(tests/test_lora_serving.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${LORA_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== fused paged-decode kernel smoke (ISSUE 13 acceptance subset) =="
# both tiers: the fused Pallas kernel (CPU: interpret mode — the same
# kernel code that compiles on TPU) is token-identical to the gather
# oracle on mixed ragged traffic with zero recompiles, and the widened
# dense-kernel gate keeps the retired fallback reasons ("seq not a
# 128-multiple", "attn_mask given") at zero; fast mode runs that pair,
# full mode the whole file (spec-verify window, LoRA co-batch, scratch
# overruns, key-padding-mask grads, table-bounds invariant)
FUSED_TESTS=(tests/test_fused_paged_attention.py::TestEngineFused::test_mixed_traffic_token_identity_zero_recompiles
             "tests/test_fused_paged_attention.py::TestWidenedGate::test_non_128_multiple_takes_pallas")
[ "$MODE" != "fast" ] && FUSED_TESTS=(tests/test_fused_paged_attention.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${FUSED_TESTS[@]}" -q -p no:cacheprovider

echo "== quantized KV serving smoke (ISSUE 18 acceptance subset) =="
# both tiers: the int8 page arena (quantize-on-write scatters, in-VMEM
# dequant in the fused kernel — CPU: interpret mode) matches the quantized
# gather oracle token-for-token with zero recompiles after warmup, and the
# mixed ragged replay holds the >= 0.95 token-match bar vs the full-
# precision engine; fast mode runs that pair, full mode the whole file
# (COW scale isolation, prefix-hit bit-reproducibility, spec + LoRA
# co-batch quality, warm-restart survival, pool auto-sizing, cache-key
# salting, /metrics + /healthz + flight surfaces)
KVQ_TESTS=(tests/test_kv_quant.py::TestQuantEngine::test_zero_recompiles_and_fused_token_identity
           tests/test_kv_quant.py::TestQuantEngine::test_tokens_match_full_precision)
[ "$MODE" != "fast" ] && KVQ_TESTS=(tests/test_kv_quant.py)
env JAX_PLATFORMS=cpu \
    python -m pytest "${KVQ_TESTS[@]}" -q -p no:cacheprovider

echo "== tensor-parallel smoke (ISSUE 14 acceptance subset) =="
# both tiers, pinned to the 8-device CPU-sim mesh: the TP=4 engine (column/
# row-sharded projections, mesh-sharded KV arena + decode kernel, all in the
# one compiled step) decodes mixed paged/prefix/spec traffic token-identical
# to TP=1 with the compiled budget frozen, and a bad model/tp pair fails at
# construction with a typed ShardingError naming the axis; fast mode runs
# that pair, full mode the whole file (warm-restart arena survival, LoRA
# co-batch under TP, shard_map kernel vs the gather oracle, mesh obs spine)
TP_TESTS=(tests/test_tp_serving.py::test_tp4_greedy_identical_on_mixed_traffic
          tests/test_tp_serving.py::test_validate_tp_rejects_indivisible_kv_heads)
[ "$MODE" != "fast" ] && TP_TESTS=(tests/test_tp_serving.py)
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest "${TP_TESTS[@]}" -q -p no:cacheprovider

echo "== serving fault drills (ISSUE 6 acceptance subset) =="
# both tiers run the deterministic core of the serving fault domain: the
# prefill-hang -> watchdog -> warm-restart drill (0 fresh compiles, bit-
# identical replay) and NaN isolation; fast mode skips the rest, full mode
# runs the whole non-slow file (the slow HTTP drill lives in chaos-serve)
SERVE_FAULT_TESTS=(tests/test_serving_fault.py::test_prefill_hang_watchdog_restart_bit_identical
                   tests/test_serving_fault.py::test_decode_nan_poisons_only_target_slot)
[ "$MODE" != "fast" ] && SERVE_FAULT_TESTS=(tests/test_serving_fault.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${SERVE_FAULT_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== router smoke (ISSUE 9 acceptance subset) =="
# both tiers run the deterministic core of the router contract: mid-stream
# replica death fails over with bit-identical outputs, the breaker walks
# its full open/half-open/close cycle, and two-hop deadline propagation
# shrinks the budget the engine sees; fast mode runs that trio, full mode
# the whole non-slow file (the kill -9 drill lives in chaos-router)
ROUTER_TESTS=(tests/test_serving_router.py::test_failover_retries_on_survivor_bit_identical
              tests/test_serving_router.py::test_breaker_open_half_open_close_cycle
              tests/test_serving_router.py::test_two_hop_deadline_propagation_shrinks_budget)
[ "$MODE" != "fast" ] && ROUTER_TESTS=(tests/test_serving_router.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${ROUTER_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== front-door HA smoke (ISSUE 17 acceptance subset) =="
# both tiers run the deterministic core of the crash-proof front door:
# a double-submitted idempotency key produces ONE generation with byte-
# identical replays, and a successor router rehydrated from the journal
# keeps the primary's open breaker (no re-closing onto a sick replica);
# fast mode runs that pair, full mode the whole non-slow file (torn-tail
# repair, bit-for-bit compaction, in-flight join, standby death
# detection; the router kill -9 soak lives in ./ci.sh chaos-router-ha)
HA_TESTS=(tests/test_router_ha.py::test_router_double_submit_one_generation
          tests/test_router_ha.py::test_successor_restores_breakers_and_drains)
[ "$MODE" != "fast" ] && HA_TESTS=(tests/test_router_ha.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${HA_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== autoscaler + mini-soak smoke (ISSUE 16 acceptance subset) =="
# both tiers run the closed-loop core under the runtime sanitizer (the
# module is sanitized: 0 unexpected recompiles through the whole cycle):
# the live 1 -> 2 -> 1 scale cycle with a parseable flight dump, and the
# sub-minute chaos mini-soak — 300 saturating requests, failed-spawn +
# NaN faults, exactly-once resolution, typed adversarial outcomes; fast
# mode runs that pair, full mode the whole non-slow file (control-law
# units, workload determinism, Prometheus monotonicity across a warm
# restart; the 10-minute acceptance soak lives in ./ci.sh soak)
AUTOSCALE_TESTS=(tests/test_autoscale_soak.py::test_autoscaler_live_scale_cycle_with_flight_dump
                 tests/test_autoscale_soak.py::test_mini_soak_chaos_scale_cycle)
[ "$MODE" != "fast" ] && AUTOSCALE_TESTS=(tests/test_autoscale_soak.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${AUTOSCALE_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== disaggregated-serving smoke (ISSUE 19 acceptance subset) =="
# both tiers run the disagg core under the runtime sanitizer: the router's
# (prefill, decode) pipeline streams tokens bit-identical to the colocated
# reference with frozen compiles on BOTH handoff sides, and the
# disagg.prefill.crash drill resolves as a zero-token retriable failover
# (exactly-once: the decode side imports exactly one handoff); fast mode
# runs that pair, full mode the whole non-slow file (wire-format typed
# rejection, reservations/TTL, /reserve + /prefill endpoints, pick_pair
# scoring + NoDecodeCapacity, handoff-drop + decode-death drills, role
# autoscaler bands; the subprocess kill -9 drill lives in chaos-disagg)
DISAGG_TESTS=(tests/test_disagg_serving.py::test_router_disagg_pipeline_bit_identical
              tests/test_disagg_serving.py::test_prefill_crash_drill_zero_token_failover)
[ "$MODE" != "fast" ] && DISAGG_TESTS=(tests/test_disagg_serving.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${DISAGG_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== long-context smoke (ISSUE 20 acceptance subset) =="
# both tiers, pinned to the 8-device CPU-sim mesh: the cp=2 engine (pages
# round-robin across shards, online-softmax partials merged via pmax/psum)
# decodes greedy token-identical to cp=1 with per-shard healthz geometry,
# a 20-turn session replay stays bit-identical to stateless while skipping
# >= 90% of its prefill tokens with 0 fresh compiles, and an over-capacity
# prompt fails typed ContextOverflow at admission; fast mode runs that
# trio, full mode both files (cp kernel vs gather oracle, q8-under-cp,
# indivisible-shape fallback, eviction under pressure, warm restart,
# HTTP 400 capacity body, router session pinning, obs surfaces)
LONGCTX_TESTS=(tests/test_cp_decode.py::test_cp_engine_greedy_identical_to_cp1_and_healthz
               tests/test_sessions.py::test_20_turn_session_replay_bit_identical_90pct_saved
               tests/test_sessions.py::test_context_overflow_typed_at_admission)
[ "$MODE" != "fast" ] && LONGCTX_TESTS=(tests/test_cp_decode.py tests/test_sessions.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest "${LONGCTX_TESTS[@]}" -q -m "not slow" -p no:cacheprovider

echo "== observability smoke (ISSUE 10 acceptance subset) =="
# both tiers scrape a live replica's /metrics (stable name set, replica
# label) and round-trip GET /trace/<id> over a traced request; fast mode
# runs that pair, full mode the whole file (span buffer bounds, flight
# ring/dumps, fit spans, router /metrics role label)
OBS_TESTS=(tests/test_observability.py::test_metrics_scrape_stable_names_and_format
           tests/test_observability.py::test_serve_trace_http_round_trip)
[ "$MODE" != "fast" ] && OBS_TESTS=(tests/test_observability.py)
timeout -k 30 600 env JAX_PLATFORMS=cpu \
    python -m pytest "${OBS_TESTS[@]}" -q -p no:cacheprovider

echo "CI OK"
