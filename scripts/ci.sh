#!/bin/bash
# Full local CI: tier-1 tests, then every regression gate, each reported
# with its own exit code so one failing stage doesn't mask the others.
#
#   tier-1        pytest tests/ -m 'not slow'  (the seed contract)
#   bytes_gate    HBM bytes/step vs scripts/BYTES_BASELINE.json
#   lint_gate     sharding/communication lint vs scripts/LINT_BASELINE.json
#   mem_gate      liveness peak + memory lint vs scripts/MEM_BASELINE.json
#   schedule_gate pipeline-schedule matrix + host self-lint
#   reshard_gate  resharding property suite + plan-peak audit vs
#                 scripts/RESHARD_BASELINE.json
#   overlap_gate  collective-overlap analyzer (exposed all-gather drop
#                 >= 50% + counts) vs scripts/OVERLAP_BASELINE.json
#   tune_gate     static auto-parallel tuner (chosen >= hand-picked by
#                 static score; HBM prune rejects the injected bad plan)
#                 vs scripts/TUNE_BASELINE.json
#   obs_gate      observability layer: Perfetto trace schema, trace-vs-
#                 analytic bubble crosscheck, tracing overhead <= 5%,
#                 bit-identical serving vs scripts/OBS_BASELINE.json
#   kernel_gate   Pallas kernel verifier: every registered kernel clean
#                 (write-race/coverage/OOB/carry/alias/VMEM), seeded
#                 defects refused vs scripts/KERNEL_BASELINE.json
#   fuse_gate     fusion transformer: emitted kernels bit-exact + admission
#                 clean, bench --fuse loss bit-identity + >=20% audited
#                 byte drop, emit-race injections refused vs
#                 scripts/FUSE_BASELINE.json
#   host_lint     standalone self-lint summary line (rc 1 on any finding)
#
# Exit code: number of failed stages (0 = green).
cd "$(dirname "$0")/.." || exit 1
export JAX_PLATFORMS=cpu
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

FAILED=0
declare -a SUMMARY

stage() {  # stage <name> <cmd...>
    local name="$1"; shift
    echo "=== [ci] $name ===" >&2
    "$@"
    local rc=$?
    SUMMARY+=("$name rc=$rc")
    [ "$rc" -ne 0 ] && FAILED=$((FAILED + 1))
    return 0
}

stage tier-1 timeout -k 10 2400 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
stage bytes_gate    ./scripts/bytes_gate.sh
stage lint_gate     ./scripts/lint_gate.sh
stage mem_gate      ./scripts/mem_gate.sh
stage schedule_gate ./scripts/schedule_gate.sh
stage reshard_gate  ./scripts/reshard_gate.sh
stage overlap_gate  ./scripts/overlap_gate.sh
stage tune_gate     ./scripts/tune_gate.sh
stage obs_gate      ./scripts/obs_gate.sh
stage kernel_gate   ./scripts/kernel_gate.sh
stage fuse_gate     ./scripts/fuse_gate.sh
stage store_chaos   bash -c "\
    timeout -k 10 300 python -m pytest -q -p no:cacheprovider \
        tests/test_store_replicated.py \
    && timeout -k 10 600 python -m pytest -q -p no:cacheprovider \
        tests/test_chaos.py -k 'store_leader or store_quorum \
                                or store_partitioned or launcher_store \
                                or mpmd_stage'"
stage host_lint     python -m paddle_tpu.analysis.host_lint

echo "=== [ci] summary ===" >&2
for s in "${SUMMARY[@]}"; do echo "[ci] $s" >&2; done
echo "[ci] failed stages: $FAILED" >&2
exit "$FAILED"
