#!/bin/bash
# Fusion-transformer regression gate.  Proves the emitted-Pallas substitution
# path (kernels/emit.py + analysis/fusion_transform.py) stays correct AND
# keeps its measured byte win, against scripts/FUSE_BASELINE.json:
#
#   Absolute invariants (no baseline needed):
#     - tests/test_fusion_transform.py passes (bit-exact interpret replay of
#       every emitted kernel incl. the e2e grad leg, registry admission,
#       reject-and-report fuse-* codes, emit-race refusal before the first
#       pallas_call, model-seam bit-identity);
#     - `python -m paddle_tpu.kernels.registry` exits 0 — every emitted
#       fuse_* kernel (fwd and bwd) is registered and admission-clean;
#     - `bench.py --fuse` on the tiny preset reports
#       fuse_loss_bitident=true (per-step losses bit-identical across the
#       stock/fused/stock sandwich in one process) with >= 1 accepted site
#       and an audited byte drop >= the 20% acceptance bar.
#
#   Baseline-gated (deterministic, any drift is a code change):
#     - the audited bytes drop fraction must not shrink by more than 0.02
#       absolute (a fused region silently falling back to stock shows up
#       here first);
#     - the audit's candidate worklist must not shrink (the transformer
#       going blind to a pattern class is a regression even if the drop
#       holds);
#     - bytes_per_step of the fused program must not regress > 5%.
#
# Defect injection (proves the gate can fail) — BOTH legs run on every
# normal invocation below, not as an optional mode:
#     FUSE_GATE_INJECT=emit-race    corrupts the GENUINE emitted kernels'
#                                   output index_map at trace time: the
#                                   registry CLI must exit non-zero with a
#                                   krn-write-race finding on fuse_*;
#     KERNEL_GATE_INJECT=emit-race  re-exposes the same defect under the
#                                   injected_* name kernel_gate greps for.
# Refresh the baseline after an intentional change:
#     scripts/fuse_gate.sh --update
# Exit code: number of failed checks (0 = gate passes).
cd "$(dirname "$0")/.." || exit 1
GATE_NAME=fuse_gate
GATE_BASELINE="scripts/FUSE_BASELINE.json"
DROP_SLACK="${FUSE_GATE_DROP_SLACK:-0.02}"
. scripts/gate_lib.sh
gate_init "$@"

echo "[fuse_gate] transformer conformance tests" >&2
if ! timeout -k 10 600 python -m pytest tests/test_fusion_transform.py -q \
        -m "not slow" -p no:cacheprovider >&2; then
    echo "[fuse_gate] conformance: FAILED (tests/test_fusion_transform.py)" >&2
    FAIL=$((FAIL + 1))
fi

echo "[fuse_gate] registry admission (absolute: emitted kernels clean)" >&2
if ! timeout -k 10 600 python -m paddle_tpu.kernels.registry \
        >/dev/null 2>&1; then
    echo "[fuse_gate] admission: FAILED (registry CLI rc != 0):" >&2
    timeout -k 10 600 python -m paddle_tpu.kernels.registry >/dev/null
    FAIL=$((FAIL + 1))
fi

check() {  # check <preset> <timeout-s> <extra bench args...>
    local preset="$1" budget="$2"; shift 2
    # off the chip the emitted kernels run only where the flag asks for the
    # interpreter (kernels/emit.py never picks it by itself)
    FLAGS_pallas_interpret=1 gate_bench "$preset" "$budget" --fuse "$@" || return
    gate_diff "$preset" "$DROP_SLACK" <<PY
import json, os, sys
exec(os.environ["GATE_PY_COMMON"])
preset, baseline_path, new_path, update, slack = sys.argv[1:6]
result = gate_result("""$GATE_LINE""")
drop = float(result.get("value") or 0.0)
entry = {
    "drop_frac": drop,
    "candidates": result.get("fuse_candidates", 0),
    "accepted": result.get("fuse_accepted", 0),
    "sites": result.get("fuse_sites", []),
    "bytes_per_step_fused": result.get("bytes_per_step_fused", 0.0),
    "bytes_per_step_stock": result.get("bytes_per_step_stock", 0.0),
}
gate_record(new_path, preset, entry)
# absolute invariants first: bit-identity, >=1 site, the 20% bar
fails = []
if not result.get("fuse_loss_bitident"):
    fails.append("per-step losses NOT bit-identical across the "
                 "stock/fused/stock sandwich")
if entry["accepted"] < 1:
    fails.append("no accepted substitution site")
if drop < 0.20:
    fails.append(f"audited bytes drop {drop:.1%} below the 20% "
                 "acceptance bar")
if fails:
    print(f"[fuse_gate] {preset}: FAILED ({'; '.join(fails)})",
          file=sys.stderr)
    sys.exit(1)
if int(update):
    print(f"[fuse_gate] {preset}: drop {drop:.1%}, "
          f"{entry['accepted']}/{entry['candidates']} accepted (recorded)",
          file=sys.stderr)
    sys.exit(0)
base = gate_base(baseline_path, preset, "fuse_gate", "scripts/fuse_gate.sh")
if drop < base["drop_frac"] - float(slack):
    fails.append(f"drop fraction shrank {base['drop_frac']:.1%} -> "
                 f"{drop:.1%} (a region fell back to stock?)")
if entry["candidates"] < base["candidates"]:
    fails.append(f"audit worklist shrank {base['candidates']} -> "
                 f"{entry['candidates']} candidates")
if (base.get("bytes_per_step_fused")
        and entry["bytes_per_step_fused"] > base["bytes_per_step_fused"] * 1.05):
    fails.append(f"fused bytes_per_step regressed "
                 f"{base['bytes_per_step_fused']:.0f} -> "
                 f"{entry['bytes_per_step_fused']:.0f} (> 5%)")
if fails:
    print(f"[fuse_gate] {preset}: FAILED ({'; '.join(fails)})",
          file=sys.stderr)
    sys.exit(1)
print(f"[fuse_gate] {preset}: OK drop {drop:.1%} "
      f"({entry['accepted']}/{entry['candidates']} accepted, "
      f"sites {', '.join(entry['sites'])})", file=sys.stderr)
PY
}

check tiny 900 --steps 2

# both seeded-defect legs, every run: the corrupted emission path must be
# refused by admission (rc != 0) BEFORE any kernel could be substituted
for var in FUSE_GATE_INJECT KERNEL_GATE_INJECT; do
    echo "[fuse_gate] injection: $var=emit-race (must be refused)" >&2
    out=$(env "$var=emit-race" timeout -k 10 600 \
          python -m paddle_tpu.kernels.registry 2>&1 >/dev/null)
    rc=$?
    if [ "$rc" -eq 0 ] || ! printf '%s' "$out" | grep -q "krn-write-race"; then
        echo "[fuse_gate] injection $var: FAILED (rc=$rc, expected" \
             "non-zero with a krn-write-race finding)" >&2
        FAIL=$((FAIL + 1))
    fi
done

gate_finish
