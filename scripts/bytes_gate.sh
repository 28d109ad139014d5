#!/bin/bash
# HBM-traffic regression gate (tentpole PR 6).  Re-measures bytes_per_step
# for the CPU-proxy presets and fails when any preset regresses more than
# TOLERANCE vs the committed baseline (scripts/BYTES_BASELINE.json).
#
# bytes_per_step comes from XLA's own cost analysis of the compiled step
# (see profiler/fusion_audit.bytes_per_step), so it is deterministic for a
# given preset+backend — the 5% tolerance absorbs compiler-version drift,
# not noise.  Presets too slow to *run* on the CPU proxy are covered via
# `bench.py --audit-only` (compile + cost-analyse, skip the timed loop).
#
# Refresh the baseline after an intentional traffic change:
#     scripts/bytes_gate.sh --update
# Exit code: number of failed presets (0 = gate passes).
cd "$(dirname "$0")/.." || exit 1
GATE_NAME=bytes_gate
GATE_BASELINE="scripts/BYTES_BASELINE.json"
TOLERANCE="${BYTES_GATE_TOLERANCE:-0.05}"
. scripts/gate_lib.sh
gate_init "$@"

check() {  # check <preset> <timeout-s> <extra bench args...>
    local preset="$1" budget="$2"; shift 2
    gate_bench "$preset" "$budget" "$@" || return
    gate_diff "$preset" "$TOLERANCE" <<PY
import json, os, sys
exec(os.environ["GATE_PY_COMMON"])
preset, baseline_path, new_path, update, tol = sys.argv[1:6]
line = """$GATE_LINE"""
result = gate_result(line)
b = result.get("bytes_per_step")
if not b:
    print(f"[bytes_gate] {preset}: FAILED (no bytes_per_step in BENCH line)",
          file=sys.stderr)
    sys.exit(1)
gate_record(new_path, preset,
            {"bytes_per_step": b, "source": result.get("bytes_source", "")})
if int(update):
    print(f"[bytes_gate] {preset}: {b:.0f} B/step (recorded)", file=sys.stderr)
    sys.exit(0)
base = gate_base(baseline_path, preset, "bytes_gate",
                 "scripts/bytes_gate.sh")["bytes_per_step"]
ratio = b / base
if ratio > 1 + float(tol):
    print(f"[bytes_gate] {preset}: FAILED "
          f"{b:.0f} vs baseline {base:.0f} B/step (+{(ratio - 1) * 100:.1f}%"
          f" > {float(tol) * 100:.0f}%)", file=sys.stderr)
    sys.exit(1)
print(f"[bytes_gate] {preset}: OK {b:.0f} B/step "
      f"({(ratio - 1) * 100:+.1f}% vs baseline)", file=sys.stderr)
PY
}

# presets cheap enough to execute on the CPU proxy
check tiny   600 --steps 2
check ocr    600
check moe    600
check decode 600
# small/base are compile-only on CPU: cost-analyse, skip the timed run
check small  600 --audit-only
check base   900 --audit-only

gate_finish
