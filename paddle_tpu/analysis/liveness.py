"""Buffer-liveness sweep over compiled HLO: static per-device peak-resident
bytes plus a ranked lifetime profile.

The model (calibrated against ``compiled.memory_analysis()`` on CPU dumps,
which carry ``is_scheduled=true`` so ENTRY instruction order IS the
schedule):

* a linear sweep over each computation in scheduled order tracks the set of
  live buffers; an instruction's buffer goes live at its definition and is
  released after its last use;
* alias-forwarding ops (``bitcast``, ``get-tuple-element``, ``reshape``)
  define no storage — they forward to operand 0's buffer; ``tuple`` /
  ``constant`` likewise contribute 0 bytes;
* entry parameters AND entry output buffers are live for the whole
  execution — XLA's buffer assignment reserves both up front (its own
  accounting is ``argument + output + temp - alias``); a ROOT output
  element aliased to a donated parameter (``input_output_alias`` header)
  contributes 0 bytes — it is written INTO the parameter's buffer.  That
  is the whole point of donation, and modeling it wrong overestimates a
  donated elementwise update by ~33%;
* the ROOT buffer and, for a tuple ROOT, its element buffers live to the
  end;
* a call site (``while``/``conditional``/``call``/``reduce`` bodies via
  ``to_apply``/``condition``/``body``/``branch_computations``) adds the
  max internal peak of its referenced computations at that point —
  while/scan bodies reuse one set of loop-carried buffers, which the
  caller already accounts for as the call's operands/results; ``fusion``
  internals are register/scratch-resident and add nothing;
* per-device: SPMD modules (``num_partitions>1``) print per-device shapes
  in ``as_text()``, so the sweep is per-device for free.

Cross-validation: ``xla_peak_bytes`` reconstructs XLA's own number as
``argument + output + temp - alias`` from ``memory_analysis()``.  Measured
agreement on the bench presets is within a few % (exactly equal modulo
XLA's tuple index tables on programs without backend-internal scratch).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .hlo_ir import (
    BRANCHES_RE, COMP_REF_RE, entry_name, module_header, output_aliases,
    paren_args, shape_bytes, split_computations,
)

__all__ = ["Lifetime", "LivenessResult", "PreparedModule", "analyze_text",
           "analyze_lowered", "xla_peak_bytes", "ALIAS_OPS", "FREE_OPS"]

# ops that forward their operand's buffer (no new storage) — ``while``
# because XLA threads ONE set of loop-carried buffers through init, body
# params, body root, and the while result (all aliased in place); counting
# the carry tuple as fresh storage double-charges every loop program
ALIAS_OPS = {"bitcast", "get-tuple-element", "reshape", "while"}
# ops that define no HBM storage of their own
FREE_OPS = {"parameter", "constant", "tuple"}
# elementwise ops whose output can reuse a same-size dying operand buffer
# (XLA buffer assignment shares those allocations; loop fusions get the
# same treatment via their kind=kLoop tail)
REUSE_OPS = {
    "tanh", "exp", "log", "negate", "abs", "sign", "sqrt", "rsqrt",
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "power", "and", "or", "xor", "not", "select", "clamp",
}

_OPERAND_RE = re.compile(r"%([\w.\-]+)")


@dataclass
class Lifetime:
    """One entry-computation buffer's residency interval."""
    name: str            # defining instruction (post alias-resolution)
    bytes: int
    def_idx: int         # index in scheduled ENTRY order (-1: param, pre-start)
    last_idx: int        # index of last use (len(instrs): lives to end)
    opcode: str = ""
    is_param: bool = False
    param_index: int = -1
    donated: bool = False
    live_at_peak: bool = False

    @property
    def span(self) -> int:
        return max(0, self.last_idx - max(self.def_idx, 0))


@dataclass
class LivenessResult:
    peak_bytes: int
    peak_at: str                       # instruction name where peak occurs
    peak_idx: int
    lifetimes: List[Lifetime]
    entry: str
    num_partitions: int = 1
    donated_params: Set[int] = field(default_factory=set)
    entry_instrs: List[Tuple[str, str, str, str]] = field(default_factory=list)

    def ranked(self) -> List[Lifetime]:
        """Lifetime profile, largest × longest-lived first."""
        return sorted(self.lifetimes,
                      key=lambda l: (-l.bytes, -l.span, l.name))

    def params(self) -> List[Lifetime]:
        return [l for l in self.lifetimes if l.is_param]


def _parse_ops(instrs, idx):
    """Per-instruction operand lists (names defined in this computation)."""
    out = []
    for _name, _opcode, _type, tail in instrs:
        out.append([t for t in _OPERAND_RE.findall(paren_args(tail))
                    if t in idx])
    return out


def _comp_peak(comps: Dict[str, list], name: str, cache: Dict[str, int]) -> int:
    """Internal peak of a sub-computation: max live bytes of buffers DEFINED
    inside it.  Its parameters alias caller buffers (counted at the call
    site), so they are free here."""
    if name in cache:
        return cache[name]
    cache[name] = 0          # cycle guard (malformed dumps)
    instrs = comps.get(name, [])
    idx = {inst[0]: i for i, inst in enumerate(instrs)}
    operands = _parse_ops(instrs, idx)
    peak = _sweep(comps, instrs, idx, operands, cache,
                  param_bytes=None, zero_bufs=set(), out_resident={})[0]
    cache[name] = peak
    return peak


def _call_extra(comps, cache, opcode, tail) -> int:
    """Peak contributed by computations referenced from a call site."""
    if opcode == "fusion":
        return 0             # fusion internals are register/scratch resident
    refs = COMP_REF_RE.findall(tail)
    m = BRANCHES_RE.search(tail)
    if m:
        refs += re.findall(r"%?([\w.\-]+)", m.group(1))
    refs = [r for r in refs if r in comps]
    if not refs:
        return 0
    return max(_comp_peak(comps, r, cache) for r in refs)


def _sweep(comps, instrs, idx, operands, cache, *, param_bytes, zero_bufs,
           out_resident):
    """Linear liveness sweep.  ``param_bytes``: ``{name: (bytes, pindex)}``
    for the ENTRY computation (params resident from start), or ``None`` for
    sub-computations (params free).  ``zero_bufs``: buffers that occupy no
    storage of their own (outputs aliased into donated params).
    ``out_resident``: ``{buffer: bytes}`` entry output buffers — reserved
    up front by XLA's buffer assignment, so resident from the start.
    Returns ``(peak, peak_at, peak_idx, lifetimes_by_buffer)``."""
    names = [inst[0] for inst in instrs]

    def resolve(n):
        seen = set()
        while n in idx and n not in seen:
            seen.add(n)
            i = idx[n]
            if instrs[i][1] in ALIAS_OPS and operands[i]:
                n = operands[i][0]
                continue
            break
        return n

    nbytes = {}
    for iname, opcode, type_str, _tail in instrs:
        if opcode in FREE_OPS or opcode in ALIAS_OPS or iname in zero_bufs:
            nbytes[iname] = 0
        else:
            nbytes[iname] = shape_bytes(type_str)

    tup_elems = {}
    for i, (iname, opcode, _t, _tl) in enumerate(instrs):
        if opcode == "tuple":
            tup_elems[iname] = [resolve(o) for o in operands[i]]

    # last use per resolved buffer
    last = {n: idx[n] for n in names}
    for i, ops in enumerate(operands):
        for o in ops:
            b = resolve(o)
            last[b] = max(last.get(b, 0), i)

    # a tuple's element buffers back every use of the tuple itself — a
    # while result resolves to its init tuple, so the loop-carried buffers
    # must outlive the last use of the loop result
    changed = True
    while changed:
        changed = False
        for tname, elems in tup_elems.items():
            tl = last.get(tname, -1)
            for e in elems:
                if last.get(e, -1) < tl:
                    last[e] = tl
                    changed = True
    live_to_end: Set[str] = set()
    if names:
        root = names[-1]
        r = resolve(root)
        live_to_end.add(r)
        for e in tup_elems.get(r, []) + tup_elems.get(root, []):
            live_to_end.add(e)

    live: Dict[str, int] = {}
    born: Dict[str, int] = {}
    if param_bytes:
        # entry params are resident from start to end — XLA charges
        # arguments for the whole execution; donation savings come from
        # the aliased OUTPUT being zero_bufs, not from releasing the param
        for pname, (pb, _pi) in param_bytes.items():
            if pb:
                live[pname] = pb
                born[pname] = -1
            live_to_end.add(pname)
    for oname, ob in out_resident.items():
        if ob and oname not in live:
            live[oname] = ob
            born[oname] = -1
        live_to_end.add(oname)
    for b in live_to_end:
        last[b] = len(instrs)

    # precomputed expiry: buffers released after instruction i
    expire_at: Dict[int, List[str]] = {}
    for b, l in last.items():
        if b not in live_to_end and (nbytes.get(b, 0) or b in live):
            expire_at.setdefault(l, []).append(b)

    total = sum(live.values())
    peak, peak_at, peak_idx = total, "", -1
    peak_live: Set[str] = set(live)
    ended: Dict[str, Tuple[int, int, int]] = {}   # buf -> (bytes, def, last)
    for i, (iname, opcode, _t, tail) in enumerate(instrs):
        nb = nbytes.get(iname, 0)
        if nb and iname not in live:
            # in-place reuse: an elementwise op (or loop fusion) writes
            # over a same-size operand buffer that dies at this very use
            if opcode in REUSE_OPS or (opcode == "fusion" and "kind=kLoop" in tail):
                for o in operands[i]:
                    ob = resolve(o)
                    if (ob in live and ob not in live_to_end
                            and last.get(ob) == i and live[ob] == nb
                            and born.get(ob, -1) >= 0):
                        ended[ob] = (live[ob], born[ob], i)
                        total -= live.pop(ob)
                        break
            live[iname] = nb
            born[iname] = i
            total += nb
        cur = total + _call_extra(comps, cache, opcode, tail)
        if cur > peak:
            peak, peak_at, peak_idx = cur, iname, i
            peak_live = set(live)
        for o in expire_at.get(i, ()):
            if o in live:
                ended[o] = (live[o], born.get(o, i), last.get(o, i))
                total -= live[o]
                del live[o]
    for o, b in live.items():
        ended[o] = (b, born.get(o, 0), last.get(o, len(instrs)))

    lifetimes = {o: Lifetime(name=o, bytes=b, def_idx=d, last_idx=l,
                             live_at_peak=(o in peak_live))
                 for o, (b, d, l) in ended.items()}
    return peak, peak_at, peak_idx, lifetimes


class PreparedModule:
    """One parsed HLO dump, reusable across what-if liveness sweeps.

    The regex parse over the full text dominates ``analyze_text`` on large
    modules; the donation and remat advisors re-sweep once per candidate,
    so they parse once here and re-run only the linear sweep.  The
    sub-computation peak cache is shared across sweeps too — internal peaks
    do not depend on entry-level what-ifs."""

    def __init__(self, text: str, *, ignore_donation: bool = False):
        self.num_partitions, self._donated = module_header(text)
        self._alias_out = output_aliases(text)   # {output elem idx: param idx}
        if ignore_donation:
            self._donated, self._alias_out = set(), {}

        self._comps = dict(split_computations(text))
        entry = entry_name(text)
        if entry not in self._comps:
            entry = next(reversed(self._comps)) if self._comps else None
        self.entry = entry
        self._instrs = self._comps.get(entry, [])
        self._idx = {inst[0]: i for i, inst in enumerate(self._instrs)}
        self._operands = _parse_ops(self._instrs, self._idx)
        self._cache: Dict[str, int] = {}

        self._param_bytes: Dict[str, Tuple[int, int]] = {}
        self._pidx_of: Dict[str, int] = {}
        for iname, opcode, type_str, tail in self._instrs:
            if opcode == "parameter":
                m = re.match(r"\s*(\d+)", paren_args(tail))
                pi = int(m.group(1)) if m else len(self._param_bytes)
                self._param_bytes[iname] = (shape_bytes(type_str), pi)
                self._pidx_of[iname] = pi

        # ROOT output element buffers, in output order (alias resolution as
        # in the sweep: chase bitcast/gte/reshape to the defining buffer)
        instrs, idx, operands = self._instrs, self._idx, self._operands

        def _resolve(n):
            seen = set()
            while n in idx and n not in seen:
                seen.add(n)
                i = idx[n]
                if instrs[i][1] in ALIAS_OPS and operands[i]:
                    n = operands[i][0]
                    continue
                break
            return n

        self._out_elems: List[Tuple[str, int]] = []    # (buffer name, bytes)
        if instrs:
            rname, ropcode, rtype, _rtail = instrs[-1]
            rres = _resolve(rname)
            if ropcode == "tuple" or (rres in idx and instrs[idx[rres]][1] == "tuple"):
                ti = idx[rres] if rres in idx else idx[rname]
                self._out_elems = [(_resolve(o), shape_bytes(instrs[idx[o]][2])
                                    if o in idx else 0) for o in operands[ti]]
            else:
                self._out_elems = [(rres, shape_bytes(rtype))]

    def analyze(self, *, extra_donated: Optional[Set[int]] = None,
                drop_buffers: Optional[Set[str]] = None) -> LivenessResult:
        donated = set(self._donated)
        param_bytes, out_elems = self._param_bytes, self._out_elems

        # outputs aliased into donated params occupy no storage of their own
        zero_bufs = {out_elems[oi][0] for oi in self._alias_out
                     if oi < len(out_elems)}
        if extra_donated:
            bytes_of_pi = {pi: b for _n, (b, pi) in param_bytes.items()}
            claimed = set(self._alias_out)
            for pi in sorted(extra_donated):
                want = bytes_of_pi.get(pi, 0)
                for oi, (buf, b) in enumerate(out_elems):
                    if oi in claimed or b != want or buf in zero_bufs:
                        continue
                    claimed.add(oi)
                    zero_bufs.add(buf)
                    donated.add(pi)
                    break
        if drop_buffers:
            # the remat what-if: treat these entry buffers as rematerialized
            # (no resident storage of their own); params keep their storage
            zero_bufs |= {b for b in drop_buffers if b not in param_bytes}

        # non-aliased entry outputs: reserved up front by buffer assignment
        out_resident = {buf: b for buf, b in out_elems
                        if b and buf not in zero_bufs and buf not in param_bytes}

        peak, peak_at, peak_idx, lifetimes = _sweep(
            self._comps, self._instrs, self._idx, self._operands, self._cache,
            param_bytes=param_bytes, zero_bufs=zero_bufs,
            out_resident=out_resident)
        donated_names = {n for n, pi in self._pidx_of.items() if pi in donated}

        for n, lt in lifetimes.items():
            if n in param_bytes:
                lt.is_param = True
                lt.param_index = self._pidx_of[n]
                lt.donated = n in donated_names
            if n in self._idx:
                lt.opcode = self._instrs[self._idx[n]][1]
            elif n in param_bytes:
                lt.opcode = "parameter"

        return LivenessResult(
            peak_bytes=peak, peak_at=peak_at, peak_idx=peak_idx,
            lifetimes=sorted(lifetimes.values(), key=lambda l: l.def_idx),
            entry=self.entry or "", num_partitions=self.num_partitions,
            donated_params=donated, entry_instrs=self._instrs)


def analyze_text(text: str, *, extra_donated: Optional[Set[int]] = None,
                 ignore_donation: bool = False,
                 drop_buffers: Optional[Set[str]] = None) -> LivenessResult:
    """Liveness-model peak for an optimized HLO text dump.

    ``extra_donated`` marks additional entry-parameter indices as donated
    (the what-if the donation advisor asks) — each claims the first
    un-aliased same-size ROOT output slot; ``drop_buffers`` names entry
    buffers to treat as rematerialized (the what-if the remat advisor
    asks — the peak drop is the buffer's PROVEN resident contribution);
    ``ignore_donation`` drops the module's own alias header (defect
    injection)."""
    return PreparedModule(text, ignore_donation=ignore_donation).analyze(
        extra_donated=extra_donated, drop_buffers=drop_buffers)


def xla_peak_bytes(compiled) -> Optional[Tuple[int, object]]:
    """XLA's own peak, reconstructed from ``memory_analysis()`` as
    ``argument + output + temp - alias`` (per device on SPMD modules).
    ``None`` when jaxlib does not expose the stats."""
    try:
        ma = compiled.memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        return None
    return int(peak), ma


def compile_for_memory(lowered):
    """``lowered.compile()`` under a memory-minimizing schedule.

    XLA's CPU backend schedules for concurrency by default and hoists a
    rematerialized forward next to the original, so remat frees nothing
    there; the memory analyses state what a scheduler that minimizes
    memory, like the TPU's, does."""
    import jax

    if jax.default_backend() != "cpu":
        return lowered.compile()
    return lowered.compile(compiler_options={
        "xla_cpu_enable_concurrency_optimized_scheduler": False})


def analyze_lowered(lowered) -> Tuple[LivenessResult, Optional[int]]:
    """Compile, sweep the optimized text, and return
    ``(LivenessResult, xla_peak_or_None)``."""
    compiled = compile_for_memory(lowered)
    res = analyze_text(compiled.as_text())
    xp = xla_peak_bytes(compiled)
    return res, (xp[0] if xp else None)
