#!/usr/bin/env bash
# CI driver (≙ reference paddle/scripts/paddle_build.sh test shards): run the
# full suite — including the bench smoke tests that execute every bench_*
# code path on tiny shapes — and fail on any red. Run this before every
# snapshot/commit ritual.
#
#   tools/ci.sh            ptlint gate, then the full suite
#   tools/ci.sh lint       static analysis only: tools/ptlint.py over the
#                          package, failing on any non-baselined finding
#                          (add --stats to print findings-per-rule for
#                          BENCH tracking)
#   tools/ci.sh faults     fast fault-injection smoke: only the resilience /
#                          fault-injection tests (pytest -m faults), tier-1
#                          compatible (CPU, 'not slow') — proves every
#                          recovery path still recovers in a couple minutes
#   tools/ci.sh obs        observability smoke: runs a traced mini
#                          train+decode+checkpoint step and asserts a
#                          non-empty schema-valid trace file, serving
#                          percentiles, and a live statsz endpoint
#   tools/ci.sh serve      pipelined-serving smoke: decode under fault
#                          injection at in-flight depth 1 vs 3 must
#                          produce byte-identical survivor streams on
#                          every path (plain/chunked/spec/paged)
#   tools/ci.sh front      serving front-end smoke: fixed-seed load
#                          generator through the scheduler on a tiny
#                          model — stream bit-identity vs direct
#                          submission, nonzero backfill events, the
#                          fed-occupancy floor, and the queue-deadline
#                          reject path (~2 min)
#   tools/ci.sh paged      paged-serving smoke: tiny-model fused
#                          append+attend decode end to end on CPU plus
#                          the PD_PREFIX repeated-system-prompt sweep —
#                          fails if a warm shared-prefix submit() stops
#                          hitting the radix cache
#   tools/ci.sh comm       quantized-collective smoke: tiny host-platform
#                          mesh runs the int8/fp8 wire — convergence
#                          parity vs fp32, ≥3.5x bytes_wire cut, stage-3
#                          gather tolerance, the bitflipped-scale
#                          fail-loud guard, plus the overlap sweep below
#   tools/ci.sh overlap    overlap-scheduler smoke: 4-device CPU sweep of
#                          the bucketed train step — overlap on/off must
#                          leave params BIT-identical after 3 steps, the
#                          prefetch toggle inside a float-ulp envelope,
#                          and the overlap-on lowering must carry >1
#                          reduce-scatter (one per bucket, interleaved
#                          into backward) instead of one fused tail
#                          collective
#   tools/ci.sh fleetobs   fleet-observability smoke: one prefill + one
#                          decode replica (real processes) under load —
#                          the stitched per-request timeline carries all
#                          four segments summing to the client latency
#                          within 10%, the fleet /statsz serves the
#                          merged p99, and one injected SIGSTOP stall
#                          raises exactly one alert (~1 min)
#   tools/ci.sh disagg     disaggregated-serving smoke: one prefill + one
#                          decode replica (real processes via
#                          distributed/launch.py) behind the role-aware
#                          router — fixed-seed streams bit-identical to
#                          single-replica serving on the fp32 KV wire,
#                          fleet prefix-hit counter nonzero on a
#                          repeated-system-prompt workload (~1 min)
#   tools/ci.sh ha         control-plane HA smoke (~1 min): SIGKILL
#                          the router mid-traffic — the successor
#                          generation replays the request journal, the
#                          replicas reconnect via the endpoint file,
#                          and the client sees every request id with
#                          streams byte-identical to an undisturbed
#                          control fleet
#   tools/ci.sh elastic    elastic-fleet smoke (~90s): the controller
#                          spawns a 2-replica fleet under Poisson load,
#                          a SIGKILLed replica is healed with zero
#                          request-id loss and an idle drain retires the
#                          surplus gracefully; then a 4->2 worker
#                          reshape (PT_ELASTIC_RESHAPE) resumes training
#                          from the newest VERIFIED epoch on the
#                          re-planned mesh
#   tools/ci.sh reshard    live-reshard + drain-migration smoke (~2
#                          min): an in-process 4->2 ElasticTrainer
#                          reshape must move live state in HBM with a
#                          loss trajectory identical to the
#                          checkpoint-path control, and a drained
#                          serving replica must MIGRATE its in-flight
#                          decode requests to the survivor with zero id
#                          loss and byte-identical streams
#   tools/ci.sh numerics   training-numerics smoke (~1 min): tiny CPU
#                          train run with a scripted mid-run grad
#                          poison (PT_FAULTS step= rule) — the
#                          provenance header must name the planted
#                          layer + leaf family, EXACTLY one
#                          num/alert_nonfinite fires, and the
#                          auto-dumped flight record holds the clean
#                          pre-spike snapshots
#   tools/ci.sh benchdiff  bench regression sentinel: the checked-in
#                          synthetic snapshot
#                          (tests/fixtures/bench_snapshot.json) must
#                          self-diff clean and bench_diff's synthetic 20% tok/s
#                          regression must be caught by row name
#                          (seconds; also part of the default gate)
#   tools/ci.sh geom       kernel-geometry gate (ISSUE 20): sweep every
#                          registered Pallas launch at the bench ladder
#                          under jax.eval_shape (CPU, no execution) and
#                          fail on any non-baselined PT006–PT009
#                          finding — a kernel whose worst autotune
#                          geometry stops fitting VMEM fails in seconds
#   tools/ci.sh prof       device-time-attribution smoke (~1 min):
#                          tiny-model CPU prompt-length sweep through
#                          tools/profile_decode.py PD_SECTIONS=prof —
#                          roofline capture must produce nonzero
#                          flops/bytes per dispatch, the launch-tax
#                          fraction must land in (0,1], and the
#                          benchdiff sentinel must round-trip clean
#   tools/ci.sh shard      sharded-stacked smoke: 4-device CPU mesh runs
#                          the pre-stacked scan-over-layers train step
#                          under fsdp×tp (loss parity vs per-layer,
#                          stacked leaves provably sharded) plus the
#                          stacked↔per-layer checkpoint-reshard round
#                          trips — tier-1 fast
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# the tracked synthetic snapshot bench_diff self-diffs (chip records live
# in the driver's ledger, not in the tree)
BENCH_SNAPSHOT=tests/fixtures/bench_snapshot.json
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

if [[ "${1:-}" == "lint" ]]; then
    shift
    exec python tools/ptlint.py paddle_tpu tools --error-on-new "$@"
fi

if [[ "${1:-}" == "faults" ]]; then
    shift
    exec python -m pytest tests/ -q -m "faults and not slow" \
        --durations=10 -p no:cacheprovider "$@"
fi

if [[ "${1:-}" == "obs" ]]; then
    shift
    exec python tools/obs_smoke.py "$@"
fi

if [[ "${1:-}" == "serve" ]]; then
    shift
    exec python tools/serve_smoke.py "$@"
fi

if [[ "${1:-}" == "front" ]]; then
    shift
    exec python tools/front_smoke.py "$@"
fi

if [[ "${1:-}" == "paged" ]]; then
    shift
    PD_SIZE=tiny PD_SECTIONS=paged PD_PREFIX=1 \
        exec python tools/profile_decode.py "$@"
fi

if [[ "${1:-}" == "comm" ]]; then
    shift
    # comm_smoke forces its own 4-device host platform before importing jax
    exec python tools/comm_smoke.py "$@"
fi

if [[ "${1:-}" == "overlap" ]]; then
    shift
    # just the ISSUE-11 overlap sweep (bit-parity + interleaved lowering)
    exec python tools/comm_smoke.py --overlap "$@"
fi

if [[ "${1:-}" == "disagg" ]]; then
    shift
    exec python tools/disagg_smoke.py "$@"
fi

if [[ "${1:-}" == "fleetobs" ]]; then
    shift
    exec python tools/fleet_obs_smoke.py "$@"
fi

if [[ "${1:-}" == "ha" ]]; then
    shift
    exec python tools/ha_smoke.py "$@"
fi

if [[ "${1:-}" == "elastic" ]]; then
    shift
    exec python tools/elastic_smoke.py "$@"
fi

if [[ "${1:-}" == "reshard" ]]; then
    shift
    exec python tools/reshard_smoke.py "$@"
fi

if [[ "${1:-}" == "numerics" ]]; then
    shift
    exec python tools/numerics_smoke.py "$@"
fi

if [[ "${1:-}" == "benchdiff" ]]; then
    shift
    python tools/bench_diff.py "$BENCH_SNAPSHOT" "$BENCH_SNAPSHOT" "$@"
    exec python tools/bench_diff.py --selftest "$BENCH_SNAPSHOT"
fi

if [[ "${1:-}" == "geom" ]]; then
    shift
    exec python tools/ptgeom.py --error-on-new --stats "$@"
fi

if [[ "${1:-}" == "prof" ]]; then
    shift
    PD_SIZE=tiny PD_SECTIONS=prof python tools/profile_decode.py "$@"
    python tools/bench_diff.py "$BENCH_SNAPSHOT" "$BENCH_SNAPSHOT"
    exec python tools/bench_diff.py --selftest "$BENCH_SNAPSHOT"
fi

if [[ "${1:-}" == "shard" ]]; then
    shift
    # the acceptance topology: a 4-device host-platform mesh (the tests
    # carve their meshes from devices[:4], so the tier-1 8-device run
    # exercises the same paths)
    export XLA_FLAGS="--xla_force_host_platform_device_count=4"
    exec python -m pytest tests/test_sharded_stacked.py \
        tests/test_reshard.py -q -p no:cacheprovider "$@"
fi

# lint gate runs BEFORE the test shards: a host-sync or env-contract
# regression fails in seconds, not after a 30-minute suite
python tools/ptlint.py paddle_tpu tools --error-on-new
# bench regression sentinel (ISSUE 15): the checked-in baseline
# snapshot must self-diff clean and the synthetic-regression detector
# must fire — seconds, and it guards every future BENCH comparison
python tools/bench_diff.py "$BENCH_SNAPSHOT" "$BENCH_SNAPSHOT"
python tools/bench_diff.py --selftest "$BENCH_SNAPSHOT"
python -m pytest tests/ -q --durations=15 "$@"
