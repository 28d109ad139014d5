#!/bin/bash
# Sharding & communication lint regression gate.  Re-runs the static
# analyzer (`bench.py --lint` -> paddle_tpu.analysis) over the CPU-proxy
# presets and fails when any preset GAINS a finding in a gated class vs the
# committed baseline (scripts/LINT_BASELINE.json):
#
#   unintended-collective  — a new compiled collective no declared resharding
#                            explains (GSPMD started moving bytes silently)
#   donation-miss          — a large buffer stopped being donated (the update
#                            double-buffers in HBM again)
#
# Other finding codes are reported but do not fail the gate.  The analyzer
# runs on the lowered/compiled step only — nothing is executed beyond what
# the preset itself runs, so counts are deterministic per preset+backend.
#
# Refresh the baseline after an intentional change:
#     scripts/lint_gate.sh --update
# Exit code: number of failed presets (0 = gate passes).
cd "$(dirname "$0")/.." || exit 1
GATE_NAME=lint_gate
GATE_BASELINE="scripts/LINT_BASELINE.json"
. scripts/gate_lib.sh
gate_init "$@"

check() {  # check <preset> <timeout-s> <extra bench args...>
    local preset="$1" budget="$2"; shift 2
    gate_bench "$preset" "$budget" --lint "$@" || return
    gate_diff "$preset" <<PY
import json, os, sys
exec(os.environ["GATE_PY_COMMON"])
preset, baseline_path, new_path, update = sys.argv[1:5]
line = """$GATE_LINE"""
result = gate_result(line)
codes = result.get("lint_codes")
if codes is None:
    err = result.get("lint_error", "no lint_codes in BENCH line")
    print(f"[lint_gate] {preset}: FAILED ({err})", file=sys.stderr)
    sys.exit(1)
gate_record(new_path, preset, {
    "lint_codes": codes, "lint_findings": result.get("lint_findings", 0)})
if int(update):
    print(f"[lint_gate] {preset}: {codes or 'clean'} (recorded)",
          file=sys.stderr)
    sys.exit(0)
base = gate_base(baseline_path, preset, "lint_gate",
                 "scripts/lint_gate.sh")["lint_codes"]
GATED = ("unintended-collective", "donation-miss")
bad = [c for c in GATED if codes.get(c, 0) > base.get(c, 0)]
info = {c: n for c, n in codes.items() if n != base.get(c, 0)}
if bad:
    deltas = ", ".join(f"{c}: {base.get(c, 0)} -> {codes.get(c, 0)}"
                       for c in bad)
    print(f"[lint_gate] {preset}: FAILED ({deltas})", file=sys.stderr)
    sys.exit(1)
note = f" (non-gated drift: {info})" if info else ""
print(f"[lint_gate] {preset}: OK {codes or 'clean'}{note}", file=sys.stderr)
PY
}

# presets cheap enough to execute on the CPU proxy
check tiny   600 --steps 2
check ocr    600
check moe    600
check decode 600
# small/base are compile-only on CPU: lint the lowered step, skip the run
check small  600 --audit-only
check base   900 --audit-only

# the baseline file is shared with schedule_gate's host_lint section:
# merge our preset keys instead of replacing the file
gate_finish_merge
