#!/bin/bash
# CPU-only test runner: forces the CPU platform (JAX_PLATFORMS=cpu) with
# an 8-device virtual mesh for the sharding tests. The program runs on
# the chip through `python chip_smoke.py` (see README "Running").
#
# Lanes:
#   run_tests.sh fast   — deselects the `slow`-marked files (multi-process
#                         clusters, XLA parity sweeps); target < 2 min
#   run_tests.sh chaos  — opt-in seeded fault-injection stage: the
#                         crash-recovery loop runs M3_TPU_CHAOS_ITERS
#                         (default 200) kill-mid-flush iterations per
#                         schedule, and the consensus sweep runs the same
#                         number of partition/leader-kill/heal rounds
#                         against the raft-lite metadata plane under a
#                         virtual clock; never part of tier-1. (PR 20)
#                         The lane arms M3_TPU_WIRE=packed so every
#                         inter-node RPC the schedules drive rides the
#                         binary frames; export M3_TPU_WIRE=json to rerun
#                         the identical schedules over the legacy JSON
#                         hatch (byte-identical results — the fallback
#                         contract tests/test_wire.py pins)
#   run_tests.sh rig    — opt-in PROCESS-LEVEL production rig: real
#                         spawned dbnodes + 3-replica quorum kvd +
#                         coordinator + aggregator under seeded
#                         kill/partition chaos and live load
#                         (M3_TPU_RIG_SECONDS schedule budget, ~60s wall
#                         with spawn/verify overhead). Asserts zero
#                         acked-write loss, the pair-median p99 SLO, AND
#                         (PR 9) the anti-entropy convergence audit:
#                         every replica pair reaches per-(shard, block)
#                         rollup-digest equality within the repair-cycle
#                         budget, driven by the nodes' own RepairDaemons.
#                         (PR 17) The lane also runs the topology
#                         ELASTICITY episode: add-node -> paced verified
#                         drain -> rolling restart under live load with
#                         chaos overlapping the placement changes, zero
#                         acked-write loss through every handoff, and the
#                         post-episode convergence audit. Both episodes
#                         share the M3_TPU_RIG_SECONDS budget; never
#                         tier-1. (PR 20) Like the chaos lane, the rig
#                         runs with M3_TPU_WIRE=packed armed, so repair
#                         streams, rollup digests, and coordinator reads
#                         all ride the binary frames under kill/partition
#                         chaos
#   run_tests.sh tsan   — opt-in ThreadSanitizer stage for the native
#                         layer: (1) pytest tests/test_race_native.py
#                         (uninstrumented pytest; its tests spawn their
#                         own libtsan-preloaded children — planted-race
#                         sensitivity + race_check's threaded workloads),
#                         then (2) tools/tsan_native.py re-runs the
#                         test_native*/test_native_hostops parity battery
#                         in a preloaded child with M3TSZ_SO/M3HOSTOPS_SO
#                         swapped to the native/tsan builds. pytest itself
#                         cannot run under the preload in this image (its
#                         capture layer deadlocks against the TSan
#                         runtime), which is why the lane splits this way;
#                         never tier-1
#   run_tests.sh [...]  — full suite (extra args pass through to pytest)
#
# Static analysis gate (every lane): tools/m3lint — lock discipline
# (order inversions, blocking calls under locks, unguarded mutation of
# guarded attrs), jax jit-purity/recompile hazards, and the project
# invariants (tracepoints, fault seams, exemplars, exporter, admission,
# histogram catalog, crash-swallowing excepts). Zero unwaived findings
# or the lane does not run. Budget ~10s; see README "Static analysis &
# concurrency checking".
cd "$(dirname "$0")" || exit 1
env JAX_PLATFORMS=cpu python -m tools.m3lint || exit 1
ARGS=("$@")
if [ "${1:-}" = "fast" ]; then
  shift
  ARGS=(-m "not slow" "$@")
elif [ "${1:-}" = "chaos" ]; then
  shift
  exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    M3_TPU_CHAOS_ITERS="${M3_TPU_CHAOS_ITERS:-200}" \
    M3_TPU_WIRE="${M3_TPU_WIRE:-packed}" \
    python -m pytest tests/test_crash_recovery.py tests/test_fault_injection.py \
    tests/test_consensus.py \
    -q -m chaos "$@"
elif [ "${1:-}" = "rig" ]; then
  shift
  exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    M3_TPU_RIG_SECONDS="${M3_TPU_RIG_SECONDS:-20}" \
    M3_TPU_WIRE="${M3_TPU_WIRE:-packed}" \
    python -m pytest tests/test_rig.py -q -m chaos "$@"
elif [ "${1:-}" = "tsan" ]; then
  shift
  env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_race_native.py -q "$@" || exit 1
  exec env JAX_PLATFORMS=cpu \
    python tools/tsan_native.py
fi
exec env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest tests/ -q "${ARGS[@]}"
