#!/bin/bash
# Memory-liveness regression gate.  Re-runs the HLO buffer-liveness lint
# (`bench.py --mem` -> paddle_tpu.analysis.memory_lint) over the CPU-proxy
# presets and fails when any preset GAINS a finding in a gated class vs the
# committed baseline (scripts/MEM_BASELINE.json):
#
#   mem-over-budget         — modeled per-device peak exceeds the HBM budget
#   mem-donation-would-help — a large undonated input whose donation would
#                             cut the modeled peak (update double-buffers)
#   mem-replicated-resident — a declared-sharded param resident at global
#                             size in the compiled program
#
# mem-remat-candidate is advisory: reported, never gated.  Two absolute
# invariants fail regardless of baseline: the liveness peak must agree with
# XLA's own memory_analysis() within 10% on every preset program, and
# mem_codes must be present at all (a
# mem_error in the BENCH line means the sweep itself broke).
#
# Defect injection (proves the gate can fail):
#     MEM_GATE_INJECT=strip-donation scripts/mem_gate.sh   # must exit != 0
# Refresh the baseline after an intentional change:
#     scripts/mem_gate.sh --update
# Exit code: number of failed presets (0 = gate passes).
cd "$(dirname "$0")/.." || exit 1
GATE_NAME=mem_gate
GATE_BASELINE="scripts/MEM_BASELINE.json"
. scripts/gate_lib.sh
gate_init "$@"

check() {  # check <preset> <timeout-s> <extra bench args...>
    local preset="$1" budget="$2"; shift 2
    gate_bench "$preset" "$budget" --mem "$@" || return
    gate_diff "$preset" <<PY
import json, os, sys
exec(os.environ["GATE_PY_COMMON"])
preset, baseline_path, new_path, update = sys.argv[1:5]
line = """$GATE_LINE"""
result = gate_result(line)
codes = result.get("mem_codes")
if codes is None:
    err = result.get("mem_error", "no mem_codes in BENCH line")
    print(f"[mem_gate] {preset}: FAILED ({err})", file=sys.stderr)
    sys.exit(1)
entry = {"mem_codes": codes, "mem_findings": result.get("mem_findings", 0)}
for k in ("peak_bytes", "peak_agreement",
          "prefill_peak_bytes", "prefill_peak_agreement"):
    if k in result:
        entry[k] = result[k]
gate_record(new_path, preset, entry)
# absolute invariant: liveness peak within 10% of XLA's memory_analysis()
bad_agree = [f"{k}={result[k]:.4f}"
             for k in ("peak_agreement", "prefill_peak_agreement")
             if k in result and abs(result[k] - 1.0) > 0.10]
if bad_agree:
    print(f"[mem_gate] {preset}: FAILED (liveness vs memory_analysis "
          f"disagree >10%: {', '.join(bad_agree)})", file=sys.stderr)
    sys.exit(1)
if int(update):
    print(f"[mem_gate] {preset}: {codes or 'clean'} (recorded)",
          file=sys.stderr)
    sys.exit(0)
base = gate_base(baseline_path, preset, "mem_gate",
                 "scripts/mem_gate.sh")["mem_codes"]
GATED = ("mem-over-budget", "mem-donation-would-help",
         "mem-replicated-resident")
bad = [c for c in GATED if codes.get(c, 0) > base.get(c, 0)]
info = {c: n for c, n in codes.items() if n != base.get(c, 0)}
if bad:
    deltas = ", ".join(f"{c}: {base.get(c, 0)} -> {codes.get(c, 0)}"
                       for c in bad)
    print(f"[mem_gate] {preset}: FAILED ({deltas})", file=sys.stderr)
    sys.exit(1)
note = f" (non-gated drift: {info})" if info else ""
print(f"[mem_gate] {preset}: OK {codes or 'clean'}{note}", file=sys.stderr)
PY
}

# presets cheap enough to execute on the CPU proxy
check tiny   600 --steps 2
check ocr    600
check moe    600
check decode 600
# small/base are compile-only on CPU: mem-lint the lowered step, skip the run
check small  600 --audit-only
check base   900 --audit-only

# keep only our preset keys fresh in case the baseline file ever grows a
# section owned by another gate
gate_finish_merge
