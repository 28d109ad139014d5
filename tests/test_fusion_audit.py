"""PR 6: fusion auditor unit tests — byte accounting on a known-wasteful toy
HLO, plus the end-to-end path over a real compiled program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.profiler.fusion_audit import (
    audit_hlo_text, audit_lowered, bytes_per_step, shape_bytes)

MB4 = 1024 * 1024 * 4  # bytes of one f32[1024,1024]

# every avoidable-traffic class the auditor flags, in one module:
# - %dup re-reads %p0 (per-use 3 buffers, unique 2)
# - %cp is a top-level copy (pure data movement XLA failed to sink)
# - %dup -> %consume is a Loop->Loop chain with a single consumer: the
#   intermediate round-trips HBM where one merged fusion would not
# the %fused_body computation must NOT be counted (only ENTRY is audited)
TOY_HLO = """\
HloModule toy, entry_computation_layout={(f32[1024,1024]{1,0})->f32[1024,1024]{1,0}}

%fused_body (param_0: f32[1024,1024]) -> f32[1024,1024] {
  %param_0 = f32[1024,1024]{1,0} parameter(0)
  %ghost = f32[1024,1024]{1,0} multiply(%param_0, %param_0)
  ROOT %out = f32[1024,1024]{1,0} add(%ghost, %param_0)
}

ENTRY %main.7 (p0: f32[1024,1024], p1: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  %dup = f32[1024,1024]{1,0} fusion(%p0, %p0, %p1), kind=kLoop, calls=%fused_body
  %cp = f32[1024,1024]{1,0} copy(%p1)
  ROOT %consume = f32[1024,1024]{1,0} fusion(%dup, %cp), kind=kLoop, calls=%fused_body
}
"""


def test_shape_bytes_parsing():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert shape_bytes("s32[]") == 0 or shape_bytes("s32[]") == 4  # scalar
    assert shape_bytes("(f32[8,128]{1,0}, s32[4])") == 8 * 128 * 4 + 16
    assert shape_bytes("f32[2,<=3]") == 24  # dynamic dim counts at its bound
    assert shape_bytes("token[]") == 0


def test_toy_hlo_duplicate_reads_and_waste():
    audit = audit_hlo_text(TOY_HLO)
    by_name = {r.name: r for r in audit.records}
    # only ENTRY instructions are audited; parameters are free
    assert set(by_name) == {"dup", "cp", "consume"}

    dup = by_name["dup"]
    assert dup.bytes_in == 3 * MB4          # per-use: p0, p0, p1
    assert dup.bytes_in_unique == 2 * MB4   # unique: p0, p1
    assert dup.bytes_out == MB4
    assert dup.waste == MB4
    assert any("re-reads" in n for n in dup.notes)
    assert audit.ranked()[0] is dup         # ranked by waste

    cp = by_name["cp"]
    assert cp.waste == 0
    assert any("data movement" in n for n in cp.notes)


def test_toy_hlo_missed_fusion_chain():
    audit = audit_hlo_text(TOY_HLO)
    assert audit.missed_fusions == [("dup", "consume", MB4)]
    # total avoidable = duplicate read + HBM round-trip of the intermediate
    assert audit.total_waste == 2 * MB4
    report = audit.report()
    assert "missed fusion: dup -> consume" in report
    assert "re-reads" in report


def test_bare_instruction_list_fallback():
    audit = audit_hlo_text(
        "%a = f32[64,64]{1,0} parameter(0)\n"
        "%b = f32[64,64]{1,0} exponential(%a)\n")
    assert len(audit.records) == 1
    assert audit.records[0].bytes_accessed == 2 * 64 * 64 * 4


def test_audit_and_bytes_on_real_compiled_program():
    def step(p, g):
        m = 0.9 * p + 0.1 * g
        return p - 1e-3 * m, m

    x = jnp.zeros((256, 256), jnp.float32)
    lowered = jax.jit(step).lower(x, x)
    audit = audit_lowered(lowered)
    assert audit is not None and audit.records, "no instructions audited"
    assert audit.total_bytes >= 3 * 256 * 256 * 4  # 2 reads + 2 writes min
    b = bytes_per_step(lowered=lowered)
    assert b and b > 0


# a reduction (Input) fusion feeding one elementwise fusion, whose output a
# top-level convert then downcasts: the norm-prologue and cast-epilogue
# pallas-candidate patterns in one module
NORM_HLO = """\
HloModule norm, entry_computation_layout={(f32[1024,1024]{1,0})->bf16[1024,1024]{1,0}}

ENTRY %main.9 (p0: f32[1024,1024]) -> bf16[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %stats = f32[1024]{0} fusion(%p0), kind=kInput, calls=%reduce_body
  %norm = f32[1024,1024]{1,0} fusion(%p0, %stats), kind=kLoop, calls=%scale_body
  ROOT %down = bf16[1024,1024]{1,0} convert(%norm)
}
"""


def test_pallas_candidate_classification():
    audit = audit_hlo_text(NORM_HLO)
    by_name = {r.name: r for r in audit.records}
    assert by_name["stats"].fusible == "norm-prologue"
    assert by_name["down"].fusible == "cast-epilogue"
    # the chain pattern comes from the missed-fusion detector
    toy = audit_hlo_text(TOY_HLO)
    toy_by_name = {r.name: r for r in toy.records}
    assert toy_by_name["dup"].fusible == "elementwise-chain"
    # a copy of a parameter is layout churn but NOT a kernel epilogue
    assert toy_by_name["cp"].fusible == ""


def test_pallas_candidates_worklist():
    cands = audit_hlo_text(NORM_HLO).pallas_candidates()
    assert [c["pattern"] for c in cands] == ["cast-epilogue", "norm-prologue"]
    assert all(c["fusible"] == "pallas-candidate" for c in cands)
    # the folded convert saves its full round-trip (f32 read + bf16 write);
    # the norm prologue saves its stats intermediate
    assert cands[0]["name"] == "down"
    assert cands[0]["bytes_saved"] == MB4 + MB4 // 2
    assert cands[0]["members"] == ["down"]
    assert cands[1]["bytes_saved"] == 1024 * 4
    report = audit_hlo_text(NORM_HLO).report()
    assert "fusible=pallas-candidate (norm-prologue)" in report
    assert "pallas candidates: 2" in report


# PR 19 satellite: worklist hardening.  Two same-source Loop fusions chained
# through a free bitcast, with AD-style metadata: the auditor must group them
# into ONE region (fwd+bwd of a source op), apply the group byte model, and
# drop the per-record entries the region subsumes.
META_HLO = """\
HloModule meta, entry_computation_layout={(f32[1024,1024]{1,0})->f32[1024,1024]{1,0}}

FileNames
1 "/repo/models/mlp.py"
2 "/repo/models/other.py"

FunctionNames
1 "silu"
2 "other"

FileLocations
1 {file_name_id=1 function_name_id=1 line=10 end_line=10 column=4 end_column=20}
2 {file_name_id=1 function_name_id=1 line=11 end_line=11 column=4 end_column=20}
3 {file_name_id=2 function_name_id=2 line=3 end_line=3 column=4 end_column=20}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=3 parent_frame_id=3}

ENTRY %main.9 (p0: f32[1024,1024], p1: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  %a = f32[1024,1024]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%body, metadata={op_name="jit(step)/jit(silu)/mul" stack_frame_id=1}
  %bc = f32[1024,1024]{1,0} bitcast(%a)
  %b = f32[1024,1024]{1,0} fusion(%bc, %p1), kind=kLoop, calls=%body, metadata={op_name="jit(step)/jit(silu)/add" stack_frame_id=2}
  ROOT %c = f32[1024,1024]{1,0} fusion(%b), kind=kLoop, calls=%body, metadata={op_name="jit(step)/other" stack_frame_id=3}
}
"""


def test_source_region_grouping_and_dedupe():
    audit = audit_hlo_text(META_HLO)
    regions = {r["name"]: r for r in audit.regions}
    reg = regions["region:mlp.py:a"]
    assert reg["members"] == ["a", "b"]          # joined through the bitcast
    assert reg["op_hints"] == ["silu"]
    # group model: traffic 2*(2 reads + 1 write) minus externals p0,p1 in and
    # b's output out — the a->b intermediate (write+read) stays in VMEM
    assert reg["bytes_saved"] == 2 * MB4
    cands = audit.pallas_candidates()
    # the region subsumes a's elementwise-chain record entry: "a" appears in
    # exactly one candidate (dedupe), and b appears only as a region member
    flat = [m for c in cands for m in c["members"]]
    assert flat.count("a") == 1 and flat.count("b") == 1
    assert cands[0]["name"] == "region:mlp.py:a"


def test_pallas_candidates_deterministic_ranking():
    # equal bytes_saved entries must tie-break stably by name, and repeated
    # parses must agree exactly (the emitter baselines diff this list)
    runs = [audit_hlo_text(META_HLO).pallas_candidates() for _ in range(3)]
    assert runs[0] == runs[1] == runs[2]
    names = [c["name"] for c in runs[0]]
    assert names == sorted(names, key=lambda n: (
        -[c for c in runs[0] if c["name"] == n][0]["bytes_saved"], n))
    toy = [audit_hlo_text(TOY_HLO).pallas_candidates() for _ in range(2)]
    assert toy[0] == toy[1]


# a counted while loop (trip count 4 from the condition's compare) whose body
# does real per-iteration work plus a loop-carried in-place update: the body
# traffic must scale by the trip count, the dynamic-update-slice must not
WHILE_HLO = """\
HloModule loopy, entry_computation_layout={(f32[256,256]{1,0})->(s32[], f32[256,256]{1,0})}

%wcond (cp: (s32[], f32[256,256])) -> pred[] {
  %cp = (s32[], f32[256,256]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[256,256]{1,0}) %cp), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%wbody (bp: (s32[], f32[256,256])) -> (s32[], f32[256,256]) {
  %bp = (s32[], f32[256,256]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element((s32[], f32[256,256]{1,0}) %bp), index=0
  %x = f32[256,256]{1,0} get-tuple-element((s32[], f32[256,256]{1,0}) %bp), index=1
  %mul = f32[256,256]{1,0} multiply(f32[256,256]{1,0} %x, f32[256,256]{1,0} %x)
  %upd = f32[8,256]{1,0} slice(f32[256,256]{1,0} %mul), slice={[0:8], [0:256]}
  %dus = f32[256,256]{1,0} dynamic-update-slice(f32[256,256]{1,0} %x, f32[8,256]{1,0} %upd, s32[] %i.1, s32[] %i.1)
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i.1, s32[] %one)
  ROOT %tup = (s32[], f32[256,256]{1,0}) tuple(s32[] %next, f32[256,256]{1,0} %dus)
}

ENTRY %main.9 (p0: f32[256,256]) -> (s32[], f32[256,256]) {
  %p0 = f32[256,256]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[256,256]{1,0}) tuple(s32[] %c0, f32[256,256]{1,0} %p0)
  ROOT %w = (s32[], f32[256,256]{1,0}) while((s32[], f32[256,256]{1,0}) %t), condition=%wcond, body=%wbody
}
"""

B256 = 256 * 256 * 4  # bytes of one f32[256,256]


def test_while_body_scaled_by_trip_count():
    audit = audit_hlo_text(WHILE_HLO)
    by_name = {r.name: r for r in audit.records}
    # the loop body's real work is counted once per iteration
    mul = by_name["mul"]
    assert mul.bytes_out == 4 * B256
    assert mul.bytes_in == 2 * 4 * B256  # reads x twice, each iteration
    assert any("in loop body x4" in n for n in mul.notes)
    # ... but the loop-carried in-place update aliases its buffer: once
    dus = by_name["dus"]
    assert dus.bytes_out == B256
    assert any("counted once" in n for n in dus.notes)
    # the opaque while record itself stays a one-time cost at entry
    assert by_name["w"].bytes_out <= 2 * B256


def test_while_trip_count_unknown_scales_nothing():
    # strip the condition's compare: an unknown loop must default to x1
    mangled = WHILE_HLO.replace("direction=LT", "direction=NE")
    audit = audit_hlo_text(mangled)
    by_name = {r.name: r for r in audit.records}
    assert by_name["mul"].bytes_out == B256
    assert not any("in loop body" in n for n in by_name["mul"].notes)
