"""Cross-rank collective consistency: the deadlock class the sharding
lint cannot see.

One rank's program can be perfectly sharded and still hang the job: SPMD
collectives are rendezvous points, so if rank 3's program issues one
fewer all-reduce — or the same all-reduce over a different participant
set — every other rank waits forever.  Two static detectors:

- :func:`match_collectives` — given each rank's (or each MPMD stage's)
  compiled module text, extract the ordered collective sequence (kind,
  byte count, participant set; async ``-start`` pairs counted once,
  reusing :mod:`.hlo_lint`'s parser idiom over ALL computations so
  collectives inside scan/while bodies are seen) and diff them pairwise
  against the first rank.  Any divergence is a ``collective-mismatch``.

- :func:`lint_rank_divergence` (jaxpr) / :func:`lint_hlo_rank_divergence`
  (compiled HLO) — rank-divergent control flow: a collective under a
  ``lax.cond`` whose predicate derives from ``axis_index`` /
  ``partition-id``.  Different ranks take different branches of the SAME
  program, so a collective present in only one branch is a static
  deadlock even though every rank runs identical code.  The pipeline
  schedules thread shared-param grads through ``pvary`` precisely to keep
  psums OUT of their stage-id conds — this lint is the check that stays
  true.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple, Union

from jax.extend import core as jax_core

from .findings import Report
from .hlo_ir import BRANCHES_RE as _BRANCHES_RE
from .hlo_ir import COMP_REF_RE as _COMP_REF_RE
from .hlo_ir import shape_bytes, split_computations
from .hlo_lint import COLLECTIVE_OPS

__all__ = [
    "CollectiveSig", "collective_sequence", "match_collectives",
    "lint_rank_divergence", "lint_hlo_rank_divergence",
    "JAXPR_COLLECTIVES",
]

# jaxpr-level communication primitives (pvary/pbroadcast are vma type casts,
# not data movement — excluded on purpose)
JAXPR_COLLECTIVES = frozenset({
    "psum", "psum2", "psum_invariant", "ppermute", "pshuffle",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "pmax", "pmin", "pgather", "allreduce", "collective_permute",
})

_RANK_SOURCE_PRIMS = ("axis_index", "axis_size")  # rank-identity producers
_HLO_RANK_OPS = ("partition-id", "replica-id")

_GROUPS_NESTED_RE = re.compile(r"replica_groups=(\{\{.*?\}\})")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=(\[[^\]]*\]<=\[[^\]]*\])")
_GROUPS_FLAT_RE = re.compile(r"replica_groups=(\{[^{}]*\})")


@dataclass(frozen=True)
class CollectiveSig:
    """What must agree across ranks for a collective to rendezvous."""
    kind: str     # normalized opcode (async -start folded)
    bytes: int    # output byte count
    groups: str   # replica_groups text ("" when absent = all devices)
    where: str = ""

    def short(self) -> str:
        g = f" groups={self.groups}" if self.groups else ""
        return f"{self.kind}[{self.bytes}B]{g}"


def _parse_groups(tail: str) -> str:
    for rx in (_GROUPS_NESTED_RE, _GROUPS_IOTA_RE, _GROUPS_FLAT_RE):
        m = rx.search(tail)
        if m:
            return m.group(1)
    return ""


def _parse_computations(text: str) -> List[Tuple[str, List[Tuple[str, str, str, List[str]]]]]:
    """Split a full HLO dump into computations, in file order — EVERY
    computation (branch bodies, scan bodies), not just ENTRY.  Now a thin
    alias of :func:`.hlo_ir.split_computations` (the hoisted parser)."""
    return split_computations(text)


def _norm_opcode(op: str) -> Optional[str]:
    if op.endswith("-done"):
        return None
    if op.endswith("-start"):
        op = op[: -len("-start")]
    return op if op in COLLECTIVE_OPS else None


def collective_sequence(text: str) -> List[CollectiveSig]:
    """Ordered collective signatures of one rank's full module (all
    computations in file order, so scan/while bodies are included)."""
    out: List[CollectiveSig] = []
    for comp, instrs in _parse_computations(text):
        for name, opcode, type_str, tail in instrs:
            kind = _norm_opcode(opcode)
            if kind is None:
                continue
            out.append(CollectiveSig(kind, shape_bytes(type_str),
                                     _parse_groups(tail),
                                     where=f"{comp}/{name}"))
    return out


def match_collectives(per_rank: Union[Sequence, Mapping], *,
                      check_bytes: bool = True) -> Report:
    """Verify collective alignment across ranks / MPMD stage programs.

    ``per_rank``: a sequence or mapping of per-rank items, each either an
    HLO module text or a pre-extracted ``List[CollectiveSig]``.  The first
    rank is the reference; every other rank is diffed positionally.
    """
    if isinstance(per_rank, Mapping):
        items = list(per_rank.items())
    else:
        items = list(enumerate(per_rank))
    seqs: List[Tuple[str, List[CollectiveSig]]] = []
    for label, item in items:
        seq = collective_sequence(item) if isinstance(item, str) else list(item)
        seqs.append((str(label), seq))

    rep = Report()
    rep.meta["ranks"] = len(seqs)
    if seqs:
        rep.meta["collectives_per_rank"] = len(seqs[0][1])
    if len(seqs) < 2:
        return rep

    ref_label, ref = seqs[0]
    for label, seq in seqs[1:]:
        if len(seq) != len(ref):
            rep.add(
                "collective-mismatch", "high",
                f"rank {label} issues {len(seq)} collectives but rank "
                f"{ref_label} issues {len(ref)} — the surplus side blocks "
                "in a rendezvous no one else enters (deadlock)",
                where=f"rank {label}",
                suggestion="make every rank's program issue the same "
                           "collective sequence (guard data-dependent "
                           "collectives identically on all ranks)")
        for i, (a, b) in enumerate(zip(ref, seq)):
            if a.kind != b.kind:
                rep.add(
                    "collective-mismatch", "high",
                    f"position {i}: rank {ref_label} runs {a.short()} but "
                    f"rank {label} runs {b.short()} — mismatched op kinds "
                    "never rendezvous",
                    where=b.where or f"rank {label}#{i}")
                continue
            if a.groups != b.groups:
                rep.add(
                    "collective-mismatch", "high",
                    f"position {i} ({a.kind}): participant sets differ — "
                    f"rank {ref_label} {a.groups or 'ALL'} vs rank {label} "
                    f"{b.groups or 'ALL'}; a device outside the group "
                    "waits forever",
                    where=b.where or f"rank {label}#{i}")
            elif check_bytes and a.bytes != b.bytes:
                rep.add(
                    "collective-mismatch", "medium",
                    f"position {i} ({a.kind}): payload differs — rank "
                    f"{ref_label} moves {a.bytes} B, rank {label} "
                    f"{b.bytes} B; shape mismatch corrupts or aborts",
                    where=b.where or f"rank {label}#{i}",
                    bytes=abs(a.bytes - b.bytes))
    return rep


# ---------------------------------------------------------------------------
# rank-divergent control flow: jaxpr level


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, jax_core.ClosedJaxpr) else j


def _collective_seq_of(jaxpr) -> Tuple[str, ...]:
    """Ordered collective primitive names in a jaxpr, nested included."""
    out: List[str] = []
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in JAXPR_COLLECTIVES:
            out.append(eqn.primitive.name)
        for pval in eqn.params.values():
            for sub in (pval if isinstance(pval, (list, tuple)) else (pval,)):
                if isinstance(sub, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                    out.extend(_collective_seq_of(sub))
    return tuple(out)


def _sub_tainted(sub, eqn_invars, tainted) -> set:
    """Map taint of the call-site invars onto a sub-jaxpr's invars.
    Alignment is from the END (leading sub invars are usually consts)."""
    sub = _as_jaxpr(sub)
    out = set()
    for sv, ev in zip(reversed(sub.invars), reversed(eqn_invars)):
        if isinstance(ev, jax_core.Var) and ev in tainted:
            out.add(sv)
    return out


def _walk_taint(jaxpr, tainted_in: set, path: str, rep: Report) -> None:
    jaxpr = _as_jaxpr(jaxpr)
    tainted = set(tainted_in)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_tainted = any(isinstance(v, jax_core.Var) and v in tainted
                         for v in eqn.invars)
        if name in _RANK_SOURCE_PRIMS:
            tainted.update(eqn.outvars)
            continue
        here = f"{path}/{name}" if path else name
        if name == "cond":
            pred = eqn.invars[0]
            pred_tainted = isinstance(pred, jax_core.Var) and pred in tainted
            branches = eqn.params.get("branches", ())
            seqs = [_collective_seq_of(b) for b in branches]
            if pred_tainted and len(set(seqs)) > 1:
                desc = " vs ".join(
                    "{" + ", ".join(s) + "}" if s else "{}" for s in seqs)
                rep.add(
                    "rank-divergent-collective", "high",
                    "collective under a `lax.cond` whose predicate derives "
                    f"from axis_index: branches run {desc} — ranks taking "
                    "the collective-free branch never enter the rendezvous "
                    "(static deadlock)",
                    where=here,
                    suggestion="hoist the collective out of the cond (mask "
                               "its operand instead), or make every branch "
                               "issue the identical collective sequence")
            for b in branches:
                _walk_taint(b, _sub_tainted(b, eqn.invars[1:], tainted),
                            here, rep)
        else:
            for pval in eqn.params.values():
                for sub in (pval if isinstance(pval, (list, tuple))
                            else (pval,)):
                    if isinstance(sub, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                        _walk_taint(sub,
                                    _sub_tainted(sub, eqn.invars, tainted),
                                    here, rep)
        if in_tainted:
            tainted.update(eqn.outvars)


def lint_rank_divergence(closed_jaxpr) -> Report:
    """Flag collectives under ``axis_index``-derived ``lax.cond`` branches
    in a (closed) jaxpr — the trace-time form of the deadlock, caught
    before GSPMD ever sees the program."""
    rep = Report()
    _walk_taint(closed_jaxpr, set(), "", rep)
    return rep


# ---------------------------------------------------------------------------
# rank-divergent control flow: compiled HLO level


def lint_hlo_rank_divergence(text: str) -> Report:
    """The post-compile form: an HLO ``conditional`` whose predicate is fed
    (transitively) by ``partition-id``/``replica-id`` and whose branch
    computations contain differing collective sequences."""
    rep = Report()
    comps = _parse_computations(text)
    by_name: Dict[str, List[Tuple[str, str, str, List[str]]]] = {}
    for comp, instrs in comps:
        by_name[comp] = instrs

    seq_cache: Dict[str, Tuple[str, ...]] = {}

    def comp_collectives(name: str, seen=None) -> Tuple[str, ...]:
        if name in seq_cache:
            return seq_cache[name]
        seen = set() if seen is None else seen
        if name in seen or name not in by_name:
            return ()
        seen.add(name)
        out: List[str] = []
        for _, opcode, _, tail in by_name[name]:
            kind = _norm_opcode(opcode)
            if kind is not None:
                out.append(kind)
            for ref in _COMP_REF_RE.findall(tail):
                out.extend(comp_collectives(ref, seen))
            m = _BRANCHES_RE.search(tail)
            if m:
                for ref in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    out.extend(comp_collectives(ref, seen))
        seq_cache[name] = tuple(out)
        return seq_cache[name]

    for comp, instrs in comps:
        # local taint: instruction names derived from partition-id/replica-id
        tainted: set = set()
        names_here = set()
        for iname, opcode, _, tail in instrs:
            names_here.add(iname)
            if opcode in _HLO_RANK_OPS:
                tainted.add(iname)
                continue
            operands = [t for t in re.findall(r"%([\w.\-]+)", tail)
                        if t in names_here]
            if any(o in tainted for o in operands):
                tainted.add(iname)
        for iname, opcode, _, tail in instrs:
            if opcode != "conditional":
                continue
            operands = [t for t in re.findall(r"%([\w.\-]+)", tail)
                        if t in names_here]
            pred_tainted = bool(operands) and operands[0] in tainted
            branch_names: List[str] = []
            m = _BRANCHES_RE.search(tail)
            if m:
                branch_names = re.findall(r"%?([\w.\-]+)", m.group(1))
            else:
                branch_names = [r for r in _COMP_REF_RE.findall(tail)]
            seqs = [comp_collectives(b) for b in branch_names]
            if pred_tainted and len(set(seqs)) > 1:
                desc = " vs ".join(
                    "{" + ", ".join(s) + "}" if s else "{}" for s in seqs)
                rep.add(
                    "rank-divergent-collective", "high",
                    "compiled `conditional` predicated on partition-id with "
                    f"divergent branch collectives: {desc} — ranks taking "
                    "the collective-free branch deadlock the rest",
                    where=f"{comp}/{iname}")
    return rep
