#!/bin/bash
# Observability regression gate.  Runs `bench.py --preset obs` on the CPU
# proxy plus tests/test_obs.py and fails when the obs layer's contracts
# break (baseline: scripts/OBS_BASELINE.json):
#
#   Absolute invariants (no baseline needed):
#     - the tracer dump is schema-valid Chrome/Perfetto trace_event JSON
#       (validate_chrome_trace finds zero problems);
#     - the MPMD trace-derived bubble agrees with schedule_lint's
#       DAG-priced analytic bubble within 0.15 relative error — the
#       tracer cross-checking the analyzer and vice versa;
#     - tracing overhead on the tiny pretrain step is within 5% of
#       tracing-off (the "cheap enough to leave wired in" claim);
#     - serving outputs are BIT-identical with tracing on vs off
#       (observe, never perturb);
#     - every request id's lifecycle chain is complete: one begin, one
#       end, no duplicates (exactly-once through the router);
#     - tests/test_obs.py passes (fast-path no-alloc/no-lock pins,
#       histogram quantiles, flight ring bounds, failover chains,
#       chaos postmortem artifacts).
#
#   Baseline-gated (deterministic, any drift is a code change):
#     - metrics_families emitted by the serving run must not shrink
#       (a producer silently unwired shows up as a missing family).
#
# rel_err / overhead are wall-clock-derived: recorded for provenance,
# gated only against the absolute bounds above, never diffed.
#
# Refresh the baseline after an intentional change:
#     scripts/obs_gate.sh --update
# Exit code: number of failed checks (0 = gate passes).
cd "$(dirname "$0")/.." || exit 1
GATE_NAME=obs_gate
GATE_BASELINE="scripts/OBS_BASELINE.json"
. scripts/gate_lib.sh
gate_init "$@"

echo "[obs_gate] obs unit/contract tests" >&2
if ! timeout -k 10 300 python -m pytest tests/test_obs.py -q -m "not slow" \
        -p no:cacheprovider >&2; then
    echo "[obs_gate] conformance: FAILED (tests/test_obs.py)" >&2
    FAIL=$((FAIL + 1))
fi

check_obs() {
    gate_bench obs 1200 || return
    gate_diff obs <<PY
import json, os, sys
exec(os.environ["GATE_PY_COMMON"])
preset, baseline_path, new_path, update = sys.argv[1:5]
line = """$GATE_LINE"""
r = gate_result(line)
entry = {k: r.get(k) for k in (
    "value", "trace_bubble", "analytic_bubble", "n_op_spans",
    "overhead_frac", "outputs_bit_identical", "lifecycle_complete",
    "trace_valid", "metrics_families", "decode_gap_p99_ms")}
gate_record(new_path, preset, entry)
fails = []
if not r.get("trace_valid"):
    fails.append("trace dump fails Chrome/Perfetto schema validation: "
                 + "; ".join(r.get("trace_problems", [])[:3]))
if not r.get("value", 1.0) <= 0.15:
    fails.append(f"trace vs analytic bubble rel_err {r.get('value')} "
                 f"> 0.15 (trace {r.get('trace_bubble')}, analytic "
                 f"{r.get('analytic_bubble')})")
if not r.get("overhead_frac", 1.0) <= 0.05:
    fails.append(f"tracing overhead {r.get('overhead_frac')} > 5%")
if not r.get("outputs_bit_identical"):
    fails.append("serving outputs differ with tracing on vs off")
if not r.get("lifecycle_complete"):
    fails.append("request lifecycle chains incomplete or duplicated")
if fails:
    print(f"[obs_gate] obs: FAILED ({'; '.join(fails)})", file=sys.stderr)
    sys.exit(1)
if int(update):
    print(f"[obs_gate] obs: rel_err {r['value']} overhead "
          f"{r['overhead_frac']} families {r['metrics_families']} "
          f"(recorded)", file=sys.stderr)
    sys.exit(0)
base = gate_base(baseline_path, preset, "obs_gate", "scripts/obs_gate.sh")
if r.get("metrics_families", 0) < base.get("metrics_families", 0):
    print(f"[obs_gate] obs: FAILED (metric families shrank "
          f"{base['metrics_families']} -> {r['metrics_families']} — "
          f"a producer was unwired)", file=sys.stderr)
    sys.exit(1)
print(f"[obs_gate] obs: OK rel_err {r['value']} overhead "
      f"{r['overhead_frac']} families {r['metrics_families']}",
      file=sys.stderr)
PY
}

check_obs

# own only the "obs" section if the baseline file ever grows others
gate_finish_merge
