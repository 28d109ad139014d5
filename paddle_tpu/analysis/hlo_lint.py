"""Level 2 lint: the optimized HLO module, post-GSPMD.

The jaxpr shows what the user *wrote*; the compiled module shows what the
partitioner *did to it*.  This pass parses ``compiled.as_text()`` (reusing
the instruction-stream machinery from ``profiler.fusion_audit``) and
extracts:

- every **collective** — ``all-gather`` / ``all-reduce`` /
  ``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` (and their
  async ``-start`` forms) — with output byte counts, compared against the
  *expected* set derived from declared shardings via
  :mod:`.spec_algebra`; anything unexplained is an unintended resharding;
- **unpartitioned custom calls**: a ``custom-call`` whose operand chain is
  fed by a GSPMD-inserted ``all-gather`` means the partitioner could not
  shard the op and fell back to gathering the full array onto every
  device (the Mosaic / shard_map gap made visible);
- **replicated buffers**: entry parameters materialized at full global
  size although the caller declared a sharded spec for them.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Optional, Set, Tuple

from .findings import Report
from .hlo_ir import (
    INSTR_RE as _INSTR_RE, entry_body, module_header,
    paren_args as _paren_args, shape_bytes,
    split_type_op as _split_type_op)

__all__ = ["HloInstr", "HloModuleInfo", "parse_hlo_module", "lint_hlo_text"]

COLLECTIVE_OPS = {
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
}

# ops a buffer flows through unchanged (for ancestor tracing)
_PASS_OPS = {
    "copy", "bitcast", "reshape", "transpose", "convert", "tuple",
    "get-tuple-element", "slice", "dynamic-slice",
}

_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class HloInstr:
    name: str
    opcode: str
    type_str: str
    operands: List[str]
    tail: str

    @property
    def bytes_out(self) -> int:
        return shape_bytes(self.type_str)


@dataclass
class HloModuleInfo:
    num_partitions: int = 1
    donated_params: Set[int] = field(default_factory=set)
    instrs: Dict[str, HloInstr] = field(default_factory=dict)
    order: List[str] = field(default_factory=list)
    params: Dict[int, HloInstr] = field(default_factory=dict)

    def collectives(self) -> List[Tuple[str, HloInstr]]:
        """``(normalized kind, instr)`` for every collective, counting async
        pairs once (the ``-done`` half is skipped)."""
        out = []
        for name in self.order:
            ins = self.instrs[name]
            op = ins.opcode
            if op.endswith("-done"):
                continue
            if op.endswith("-start"):
                op = op[: -len("-start")]
            if op in COLLECTIVE_OPS:
                out.append((op, ins))
        return out

    def _is_layout_fusion(self, ins: HloInstr) -> bool:
        """A one-operand fusion whose output has its operand's byte size:
        XLA wraps the layout ``copy`` feeding a custom call in one."""
        if ins.opcode != "fusion" or len(ins.operands) != 1:
            return False
        src = self.instrs.get(ins.operands[0])
        return src is not None and src.bytes_out == ins.bytes_out

    def ancestors(self, name: str, through: Iterable[str] = _PASS_OPS,
                  limit: int = 64) -> List[HloInstr]:
        """Instructions feeding ``name`` through pass-through ops only."""
        through = set(through)
        seen: Set[str] = set()
        frontier = list(self.instrs.get(name, HloInstr("", "", "", [], "")).operands)
        found: List[HloInstr] = []
        while frontier and len(seen) < limit:
            op_name = frontier.pop()
            if op_name in seen or op_name not in self.instrs:
                continue
            seen.add(op_name)
            ins = self.instrs[op_name]
            found.append(ins)
            if ins.opcode in through or self._is_layout_fusion(ins):
                frontier.extend(ins.operands)
        return found


def parse_hlo_module(text: str) -> HloModuleInfo:
    """Parse header metadata + ENTRY instruction stream of an HLO dump."""
    info = HloModuleInfo()
    info.num_partitions, info.donated_params = module_header(text)
    entry = entry_body(text)

    for raw in entry.splitlines():
        line = raw.strip()
        if not line or line.startswith("//") or line.endswith("{") or line == "}":
            continue
        mi = _INSTR_RE.match(line)
        if not mi or "=" not in line:
            continue
        name = mi.group("name")
        type_str, opcode, tail = _split_type_op(mi.group("rest"))
        if not opcode:
            continue
        operands = [t for t in re.findall(r"%?([\w.\-]+)", _paren_args(tail))
                    if t in info.instrs]
        ins = HloInstr(name, opcode, type_str, operands, tail)
        info.instrs[name] = ins
        info.order.append(name)
        if opcode == "parameter":
            pm = re.match(r"\s*(\d+)", _paren_args(tail))
            if pm:
                info.params[int(pm.group(1))] = ins
    return info


def lint_hlo_text(text: str, *, expected_kinds: Iterable[str] = (),
                  declared_params: Optional[
                      Mapping[int, Tuple[str, int, bool]]] = None,
                  min_collective_bytes: int = 0) -> Report:
    """Lint one optimized HLO module.

    ``expected_kinds``: normalized collective kinds that declared
    shardings / reductions justify (from
    :func:`.spec_algebra.expected_collectives`); anything else is flagged.

    ``declared_params``: ``{param index: (label, global_bytes, sharded)}``
    — when ``sharded`` is true but the entry parameter materializes at
    ``global_bytes``, the buffer is replicated against its declaration.
    """
    rep = Report()
    info = parse_hlo_module(text)
    expected = {k[: -len("-start")] if k.endswith("-start") else k
                for k in expected_kinds}
    rep.meta["num_partitions"] = info.num_partitions
    rep.meta["donated_params"] = len(info.donated_params)

    colls = info.collectives()
    rep.meta["collectives"] = len(colls)
    rep.meta["collective_bytes"] = sum(i.bytes_out for _, i in colls)

    for kind, ins in colls:
        if kind in expected or ins.bytes_out < min_collective_bytes:
            continue
        severity = "high" if kind in ("all-gather", "all-to-all") else "medium"
        rep.add(
            "unintended-collective", severity,
            f"`{kind}` not explained by any declared resharding "
            "— GSPMD inserted it to satisfy mismatched shardings",
            where=ins.name, bytes=ins.bytes_out,
            suggestion="align producer/consumer specs, or declare the "
                       "resharding in `expected=` if intended")

    if info.num_partitions > 1:
        for name in info.order:
            ins = info.instrs[name]
            if ins.opcode != "custom-call":
                continue
            gathers = [a for a in info.ancestors(name)
                       if a.opcode.startswith("all-gather")]
            if not gathers:
                continue
            tm = _TARGET_RE.search(ins.tail)
            target = tm.group(1) if tm else "?"
            rep.add(
                "unpartitioned-custom-call", "high",
                f'custom call "{target}" is fed by a partitioner-inserted '
                "all-gather: GSPMD could not shard it, so it runs "
                "replicated on the full array",
                where=ins.name,
                bytes=sum(g.bytes_out for g in gathers),
                suggestion="wrap the op in shard_map with explicit specs "
                           "(framework.shard_map_compat) or register a "
                           "partitionable lowering")

    for idx, (label, global_bytes, sharded) in (declared_params or {}).items():
        ins = info.params.get(idx)
        if ins is None or not sharded or global_bytes <= 0:
            continue
        if ins.bytes_out >= global_bytes and info.num_partitions > 1:
            rep.add(
                "replicated-buffer", "medium",
                f"entry parameter {idx} ({label}) materializes at full "
                f"global size despite a sharded declared spec",
                where=ins.name, bytes=ins.bytes_out,
                suggestion="pass in_shardings=NamedSharding(mesh, spec) to "
                           "jit so the buffer arrives sharded")
    return rep
