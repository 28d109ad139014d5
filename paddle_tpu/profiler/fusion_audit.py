"""HLO fusion auditor — bytes-accessed vs. analytic minimum, per fusion.

In the spirit of "Operator Fusion in XLA: Analysis and Evaluation"
(arXiv:2301.13062): XLA's fusion decisions are the single biggest lever on
bandwidth-bound steps, and they are invisible in aggregate timings.  This
pass walks a compiled module's optimized HLO, attributes HBM traffic to each
top-level instruction (fusions, dots, custom calls, copies, collectives),
and compares the traffic each fusion *actually* causes against the analytic
minimum for its operand/output set:

    minimum  = unique operand bytes + output bytes
    actual   = per-use operand bytes + output bytes

so duplicate operand reads show up as waste.  Two further classes of
avoidable traffic are flagged:

- ``copy``/``transpose``/``convert`` instructions surviving at top level
  (layout churn: pure data movement XLA failed to fuse into a consumer);
- **missed producer→consumer fusions**: a loop fusion whose output feeds
  exactly one other loop fusion — the intermediate round-trips HBM where a
  single fusion would have kept it in registers (this is exactly the
  unfused-AdamW pattern ``kernels/adamw.py`` eliminates).

The report ranks by waste so the top entries are the next kernels to write.
Records matching a shape a Pallas kernel provably collapses additionally
carry a ``fusible`` classification (``pallas-candidate``), one of three
patterns:

- ``elementwise-chain`` — the producer of a missed Loop→Loop fusion: one
  kernel keeps the intermediate in VMEM (the fused-AdamW move);
- ``norm-prologue``     — a reduction (Input-kind) fusion feeding a single
  elementwise consumer: the reduce+normalize pair ``kernels/rms_norm.py``
  fuses;
- ``cast-epilogue``     — a top-level ``convert``/``copy``/``transpose``
  consuming a fusion's output: foldable into the producer kernel's store.

:meth:`FusionAudit.pallas_candidates` returns them as a machine-readable
worklist (name, pattern, bytes a kernel saves) — the input queue for
generated kernels, which must then pass ``analysis.pallas_lint`` through
the ``kernels.registry`` admission seam.

Beyond the three per-record shapes, the auditor groups records into **source
regions**: connected components of the dataflow graph whose instructions
trace back to the same Python source file (XLA keeps ``metadata={...
stack_frame_id=}`` through optimization, including through AD — a region
therefore spans a reference op's forward *and* backward instructions).
A region's byte win is the analytic-minimum model applied to the whole
group::

    saved = sum(member bytes_accessed) - unique external inputs - external outputs

i.e. exactly what one fused kernel pair (forward + vjp) keeps in VMEM:
every intermediate crossing between members, including dot operands, never
round-trips HBM.  Region entries dominate the worklist (one MLP region on
the tiny preset carries ~34 MB); per-record entries whose record already
belongs to a region are deduplicated away, and the ranking is fully
deterministic (stable ``(-bytes_saved, name)`` order) so emitter baselines
are reproducible run to run.

Works on the text HLO (``compiled.as_text()``) because jaxlib exposes
cost_analysis only as a module-level aggregate — per-fusion numbers must
come from the instruction stream.  Aggregate ``bytes accessed`` for BENCH
lines still comes from ``utils.xla_cost`` (one authoritative number), with
the audit total as fallback.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# Parser primitives live in analysis/hlo_ir.py (the hoisted single-home
# parser shared with hlo_lint / collective_match / liveness).  The private
# aliases stay as back-compat re-exports for anything that imported them
# from here.  hlo_ir is import-cycle-safe: it pulls in nothing from the
# repo, and nothing under analysis/ imports this module at top level.
from ..analysis.hlo_ir import (
    DTYPE_BYTES as _DTYPE_BYTES,
    INSTR_RE as _INSTR_RE,
    SHAPE_RE as _SHAPE_RE,
    entry_body as _entry_body,
    paren_args as _paren_args,
    shape_bytes,
    split_type_op as _split_type_op,
)

__all__ = [
    "FusionRecord", "FusionAudit", "audit_hlo_text", "audit_compiled",
    "audit_lowered", "bytes_per_step", "shape_bytes",
]

# ops that move no HBM bytes of their own at top level
_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "token", "partition-id", "replica-id", "iota",
    "reshape",  # layout-preserving reshape is a bitcast post-layout
}

_KIND_RE = re.compile(r"kind=k(\w+)")
_FRAME_RE = re.compile(r"metadata=\{[^}]*?stack_frame_id=(\d+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SCOPE_RE = re.compile(r"jit\((\w+)\)")
# jit scopes that name the step itself, not a fusible sub-region
_OUTER_SCOPES = {"main", "step_fn", "train_step", "wrapped", "step"}


@dataclass
class FusionRecord:
    name: str
    opcode: str
    kind: str = ""            # Loop / Input / Output / Custom for fusions
    bytes_out: int = 0
    bytes_in: int = 0         # per-use operand traffic
    bytes_in_unique: int = 0  # unique operand buffers
    operands: List[str] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)
    # pallas-candidate pattern ("elementwise-chain" / "norm-prologue" /
    # "cast-epilogue"); empty when no kernel-shaped rewrite applies
    fusible: str = ""
    # basename of the Python source file XLA's metadata attributes this
    # instruction to ("" when the dump carries no metadata)
    source: str = ""
    source_line: int = 0
    # innermost jit scope from op_name metadata (e.g. "silu"), "" if none
    op_hint: str = ""

    @property
    def bytes_accessed(self) -> int:
        return self.bytes_in + self.bytes_out

    @property
    def bytes_min(self) -> int:
        return self.bytes_in_unique + self.bytes_out

    @property
    def waste(self) -> int:
        return self.bytes_accessed - self.bytes_min


@dataclass
class FusionAudit:
    records: List[FusionRecord]
    missed_fusions: List[Tuple[str, str, int]] = field(default_factory=list)
    # source regions: one dict per connected same-source component with the
    # analytic-minimum byte model applied to the whole group (see module
    # docstring).  Built by audit_hlo_text when metadata is present.
    regions: List[Dict[str, object]] = field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return sum(r.bytes_accessed for r in self.records)

    @property
    def total_min(self) -> int:
        return sum(r.bytes_min for r in self.records)

    @property
    def total_waste(self) -> int:
        # duplicate-read waste + intermediates that a merged fusion would kill
        return (self.total_bytes - self.total_min
                + sum(b for _, _, b in self.missed_fusions))

    def ranked(self) -> List[FusionRecord]:
        return sorted(self.records, key=lambda r: (r.waste, r.bytes_accessed),
                      reverse=True)

    def pallas_candidates(self) -> List[Dict[str, object]]:
        """Machine-readable worklist of fusible regions and records — the
        input queue for ``kernels.emit`` / ``analysis.fusion_transform``,
        ranked by the HBM bytes a kernel saves.  Each entry carries at least
        ``{"name", "fusible": "pallas-candidate", "pattern", "bytes_saved",
        "members", "source", "op_hints"}``.

        The worklist is deduplicated (a record appears in at most one entry:
        source regions win over the per-record classifications they subsume)
        and deterministically ordered — stable ``(-bytes_saved, name)`` —
        so the transformer's baselines reproduce run to run.  Generated
        kernels re-enter through ``kernels.registry`` and must pass the
        pallas_lint admission gate before their first call."""
        out: List[Dict[str, object]] = []
        covered: set = set()
        for reg in self.regions:
            if reg["bytes_saved"] <= 0 or len(reg["members"]) < 2:
                continue
            out.append(dict(reg, fusible="pallas-candidate"))
            covered.update(reg["members"])
        for r in self.records:
            if not r.fusible or r.name in covered:
                continue
            # a folded cast/copy removes its whole round-trip; the chain and
            # norm patterns kill the intermediate output buffer
            saved = (r.bytes_accessed if r.fusible == "cast-epilogue"
                     else r.bytes_out)
            out.append({"name": r.name, "fusible": "pallas-candidate",
                        "pattern": r.fusible, "bytes_saved": saved,
                        "members": [r.name], "source": r.source,
                        "op_hints": [r.op_hint] if r.op_hint else []})
        return sorted(out, key=lambda d: (-d["bytes_saved"], d["name"]))

    def report(self, top: int = 12) -> str:
        lines = [
            f"fusion audit: {len(self.records)} traffic-moving instructions, "
            f"{self.total_bytes / 1e6:.3f} MB accessed, "
            f"{self.total_min / 1e6:.3f} MB analytic minimum, "
            f"{self.total_waste / 1e6:.3f} MB avoidable",
            f"{'instruction':<34}{'op':<14}{'kind':<8}"
            f"{'MB acc':>10}{'MB min':>10}{'waste':>10}  notes",
        ]
        for r in self.ranked()[:top]:
            lines.append(
                f"{r.name[:33]:<34}{r.opcode[:13]:<14}{r.kind[:7]:<8}"
                f"{r.bytes_accessed / 1e6:>10.3f}{r.bytes_min / 1e6:>10.3f}"
                f"{r.waste / 1e6:>10.3f}  {'; '.join(r.notes)}")
        for prod, cons, b in sorted(self.missed_fusions, key=lambda t: -t[2])[:top]:
            lines.append(
                f"missed fusion: {prod} -> {cons} round-trips "
                f"{b / 1e6:.3f} MB intermediate through HBM")
        cands = self.pallas_candidates()
        if cands:
            lines.append(
                f"pallas candidates: {len(cands)} "
                f"({sum(c['bytes_saved'] for c in cands) / 1e6:.3f} MB "
                "saved by kernels; registry admission gates each)")
        return "\n".join(lines)


_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")


def _comp_body(text: str, name: str) -> str:
    """Instruction lines of the named non-entry computation ("" if absent)."""
    m = re.search(rf"^\s*%?{re.escape(name)}\b[^\n]*\{{\s*$", text, re.M)
    if not m:
        return ""
    rest = text[m.end():]
    close = rest.find("\n}")
    return rest[: close if close >= 0 else len(rest)]


def _while_trip_count(text: str, cond_name: str) -> int:
    """Static trip count of a canonical counted loop: the integer constant
    the condition's ``compare`` tests the counter against (1 when the shape
    is anything else — an unknown loop scales nothing rather than guessing).
    """
    body = _comp_body(text, cond_name)
    if not body:
        return 1
    consts: Dict[str, int] = {}
    for raw in body.splitlines():
        mi = _INSTR_RE.match(raw.strip())
        if not mi:
            continue
        mc = re.search(r"constant\((\d+)\)", mi.group("rest"))
        if mc:
            consts[mi.group("name")] = int(mc.group(1))
    for raw in body.splitlines():
        line = raw.strip()
        mcmp = re.search(r"compare\(([^)]*)\)", line)
        if not mcmp or "direction=LT" not in line:
            continue
        for op in re.findall(r"[\w.\-]+", mcmp.group(1)):
            if op in consts:
                return max(1, consts[op])
    return 1


def _stack_frame_sources(text: str) -> Dict[int, Tuple[str, int]]:
    """``stack_frame_id -> (source basename, line)`` from the module header.

    XLA keeps each instruction's Python origin as ``stack_frame_id=N`` and
    the frames in four tables ahead of the first computation (``FileNames``,
    ``FunctionNames``, ``FileLocations``, ``StackFrames``)."""
    tables: Dict[str, Dict[int, str]] = {}
    current = None
    for raw in text.splitlines():
        line = raw.strip()
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            current = tables.setdefault(line, {})
        elif current is not None and line[:1].isdigit():
            idx, _, rest = line.partition(" ")
            current[int(idx)] = rest
        elif line.endswith("{"):
            break                         # first computation: header is over
    def field(s, key):
        m = re.search(key + r"=(\d+)", s)
        return int(m.group(1)) if m else 0
    out: Dict[int, Tuple[str, int]] = {}
    for fid, frame in tables.get("StackFrames", {}).items():
        loc = tables.get("FileLocations", {}).get(
            field(frame, "file_location_id"), "")
        fname = tables.get("FileNames", {}).get(
            field(loc, "file_name_id"), "").strip('"')
        if fname:
            out[fid] = (fname.replace("\\", "/").rsplit("/", 1)[-1],
                        field(loc, "line"))
    return out


def audit_hlo_text(text: str) -> FusionAudit:
    """Audit the ENTRY computation of an optimized HLO text dump.

    ``while`` loops are one opaque call at entry, but their body computation
    carries the real per-iteration traffic — a gradient-accumulation step
    wraps the whole layer stack in one.  Body computations are therefore
    parsed too, with every byte count scaled by the loop's static trip
    count, so fusible regions inside an accumulation loop stay on the
    pallas worklist and audit totals stay comparable across accum settings.
    """
    frames = _stack_frame_sources(text)
    sizes: Dict[str, int] = {}       # scaled: per-use traffic of one step
    base_sizes: Dict[str, int] = {}  # unscaled shape bytes
    records: List[FusionRecord] = []
    consumers: Dict[str, List[str]] = {}
    by_name: Dict[str, FusionRecord] = {}
    free_src: Dict[str, List[str]] = {}  # free op -> operands (origin chase)
    loops: List[Tuple[str, int]] = []    # (body computation, byte scale)

    def scan(comp: str, scale: int) -> None:
        for raw in comp.splitlines():
            line = raw.strip()
            if (not line or line.startswith("//") or line.endswith("{")
                    or line == "}"):
                continue
            mi = _INSTR_RE.match(line)
            if not mi or "=" not in line:
                continue
            name = mi.group("name")
            type_str, opcode, tail = _split_type_op(mi.group("rest"))
            if not opcode:
                continue
            # a dynamic-update-slice updating loop-carried state aliases its
            # buffer across iterations and touches one slice per trip —
            # scaling the full shape by the trip count would invent traffic
            # that never happens, so in-place updates count once
            in_place = scale > 1 and (opcode == "dynamic-update-slice"
                                      or "dynamic-update-slice" in name)
            eff = 1 if in_place else scale
            base = shape_bytes(type_str)
            sizes[name] = base * eff
            base_sizes[name] = base
            operands = [t for t in re.findall(r"%([\w.\-]+)", _paren_args(tail))
                        if t in sizes]
            for op_name in operands:
                consumers.setdefault(op_name, []).append(name)
            if opcode in _FREE_OPS:
                free_src[name] = operands
                continue
            if opcode == "while":
                mb = _WHILE_BODY_RE.search(tail)
                mc = _WHILE_COND_RE.search(tail)
                if mb:
                    trips = _while_trip_count(text, mc.group(1)) if mc else 1
                    loops.append((mb.group(1), scale * trips))
            rec = FusionRecord(name=name, opcode=opcode,
                               bytes_out=base * eff, operands=operands)
            mk = _KIND_RE.search(tail)
            if mk:
                rec.kind = mk.group(1)
            mm = _FRAME_RE.search(tail)
            if mm and int(mm.group(1)) in frames:
                rec.source, rec.source_line = frames[int(mm.group(1))]
            mo = _OPNAME_RE.search(tail)
            if mo:
                scopes = [s for s in _SCOPE_RE.findall(mo.group(1))
                          if s not in _OUTER_SCOPES]
                if scopes:
                    rec.op_hint = scopes[-1]
            opsz = sizes if not in_place else base_sizes
            rec.bytes_in = sum(opsz[o] for o in operands)
            rec.bytes_in_unique = sum(opsz[o] for o in dict.fromkeys(operands))
            dups = [o for o in dict.fromkeys(operands) if operands.count(o) > 1]
            if dups:
                rec.notes.append(f"re-reads {len(dups)} operand(s)")
            if opcode in ("copy", "transpose", "convert"):
                rec.notes.append("pure data movement at top level")
            if in_place:
                rec.notes.append("loop-carried in-place update (counted once)")
            elif scale > 1:
                rec.notes.append(f"in loop body x{scale}")
            records.append(rec)
            by_name[name] = rec

    scan(_entry_body(text), 1)
    descended: set = set()
    while loops:
        body_name, scale = loops.pop(0)
        if body_name in descended:
            continue
        descended.add(body_name)
        body = _comp_body(text, body_name)
        if body:
            scan(body, scale)

    audit = FusionAudit(records=records)
    # missed producer->consumer fusion: a loop fusion feeding exactly one
    # other loop fusion — the intermediate buffer is avoidable traffic
    for rec in records:
        if rec.opcode != "fusion" or rec.kind not in ("Loop", "Output", ""):
            continue
        cons = consumers.get(rec.name, [])
        if len(cons) == 1 and cons[0] in by_name:
            c = by_name[cons[0]]
            if c.opcode == "fusion" and c.kind in ("Loop", "Input", ""):
                audit.missed_fusions.append((rec.name, c.name, rec.bytes_out))

    # fusible classification: shapes a Pallas kernel provably collapses
    for prod, _, _ in audit.missed_fusions:
        by_name[prod].fusible = "elementwise-chain"
    for rec in records:
        if rec.fusible:
            continue
        cons = consumers.get(rec.name, [])
        if (rec.opcode == "fusion" and rec.kind == "Input"
                and len(cons) == 1 and cons[0] in by_name
                and by_name[cons[0]].opcode == "fusion"):
            # reduce feeding one elementwise consumer: rms_norm's shape
            rec.fusible = "norm-prologue"
        elif (rec.opcode in ("convert", "copy", "transpose")
              and any(o in by_name and by_name[o].opcode == "fusion"
                      for o in rec.operands)):
            rec.fusible = "cast-epilogue"
    for rec in records:
        if rec.fusible:
            rec.notes.append(f"fusible=pallas-candidate ({rec.fusible})")
    audit.regions = _build_regions(records, by_name, consumers, free_src, sizes)
    return audit


def _build_regions(records, by_name, consumers, free_src, sizes):
    """Connected components of same-source records with the group byte model.

    Two records join the same region when one consumes the other (possibly
    through free ops: bitcast/reshape/get-tuple-element chains) and both
    carry the same ``source_file`` basename.  Iteration and canonical names
    are sorted, so the result is deterministic regardless of dict order."""

    def origin(name):
        # resolve through free ops to the producing record (or None)
        seen = set()
        while name not in by_name:
            if name in seen or name not in free_src or not free_src[name]:
                return None
            seen.add(name)
            name = free_src[name][0]
        return name

    parent: Dict[str, str] = {}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for rec in records:
        if rec.source:
            parent.setdefault(rec.name, rec.name)
    for rec in records:
        if not rec.source:
            continue
        for op_name in rec.operands:
            o = origin(op_name)
            if o is None or by_name[o].source != rec.source:
                continue
            parent.setdefault(o, o)
            ra, rb = find(rec.name), find(o)
            if ra != rb:
                parent[max(ra, rb)] = min(ra, rb)

    comps: Dict[str, List[str]] = {}
    for name in sorted(parent):
        comps.setdefault(find(name), []).append(name)

    regions: List[Dict[str, object]] = []
    for root in sorted(comps):
        members = comps[root]
        mset = set(members)

        def interior(name):  # does this value stay inside the region?
            o = origin(name)
            return o is not None and o in mset

        traffic = ext_out = 0
        ext_in: Dict[str, int] = {}
        has_reduction = has_interior_dot = feeds_dot = False
        hints: List[str] = []
        for name in members:
            rec = by_name[name]
            traffic += rec.bytes_accessed
            if rec.opcode in ("reduce", "reduce-window") or rec.kind == "Input":
                has_reduction = True
            if rec.opcode == "dot":
                has_interior_dot = True
            if rec.op_hint and rec.op_hint not in hints:
                hints.append(rec.op_hint)
            for op_name in rec.operands:
                if not interior(op_name):
                    ext_in[op_name] = sizes.get(op_name, 0)
            cons = consumers.get(rec.name, [])
            outside = [c for c in cons if c not in mset]
            if outside or not cons:
                ext_out += rec.bytes_out
                if any(c in by_name and by_name[c].opcode == "dot"
                       for c in outside):
                    feeds_dot = True
        saved = traffic - sum(ext_in.values()) - ext_out
        if has_reduction and feeds_dot and not has_interior_dot:
            pattern = "norm-prologue"
        elif has_reduction or has_interior_dot:
            pattern = "elementwise-chain"
        else:
            pattern = "cast-epilogue"
        src = by_name[members[0]].source
        regions.append({
            "name": f"region:{src}:{members[0]}",
            "pattern": pattern,
            "bytes_saved": saved,
            "bytes_traffic": traffic,
            "bytes_ext_in": sum(ext_in.values()),
            "bytes_ext_out": ext_out,
            "members": members,
            "source": src,
            "op_hints": sorted(hints),
        })
    regions.sort(key=lambda r: (-r["bytes_saved"], r["name"]))
    return regions


def audit_compiled(compiled) -> Optional[FusionAudit]:
    """Audit a jax ``Compiled`` object (returns None if the backend does not
    expose optimized HLO text)."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not text:
        return None
    return audit_hlo_text(text)


def audit_lowered(lowered) -> Optional[FusionAudit]:
    try:
        return audit_compiled(lowered.compile())
    except Exception:
        return None


def bytes_per_step(lowered=None, compiled=None) -> Optional[float]:
    """Authoritative bytes-accessed for one execution: XLA's own
    cost_analysis when available, else the audit total from the HLO text."""
    from ..utils.xla_cost import cost_of_lowered

    if lowered is not None:
        cost = cost_of_lowered(lowered)
        if cost and cost.get("bytes accessed"):
            return float(cost["bytes accessed"])
    if compiled is not None:
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost and cost.get("bytes accessed"):
                return float(cost["bytes accessed"])
        except Exception:
            pass
    audit = None
    if compiled is not None:
        audit = audit_compiled(compiled)
    if audit is None and lowered is not None:
        audit = audit_lowered(lowered)
    return float(audit.total_bytes) if audit is not None else None
