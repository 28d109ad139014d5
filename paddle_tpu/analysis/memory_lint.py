"""Static HBM lint on top of the liveness sweep (``analysis/liveness.py``).

Finding codes (see ``findings.py`` for the full catalogue):

* ``mem-over-budget`` — modeled peak-resident bytes exceed the declared
  per-device HBM budget.  The check the serving tier and auto-parallel
  need BEFORE an OOM, not after.
* ``mem-donation-would-help`` — a non-donated input ≥ the big-buffer
  threshold has a matching un-aliased output slot, and re-running the
  sweep with that parameter donated PROVABLY lowers the peak (the finding
  carries the delta, not a guess).
* ``mem-remat-candidate`` — a large long-lived activation stays resident
  across ≥ K compute instructions while the peak is hit; low severity (not
  gated) but ACTIONABLE: ``bytes`` carries the proven peak drop from
  re-sweeping with the buffer rematerialized, which is what
  ``analysis.autotune.remat_policy`` ranks by.
* ``mem-replicated-resident`` — an entry parameter is resident at global
  size on every device although its declared spec shards it (the
  residency twin of hlo_lint's ``replicated-buffer``).

Defect injection for the gate: ``MEM_GATE_INJECT=strip-donation`` makes
the sweep ignore the module's ``input_output_alias`` header, so every
donated train-state param shows up as a donation candidate and the
donation advisor must fire — ``scripts/mem_gate.sh`` verifies rc 1.

``MEM_LINT_BIG_BUFFER`` overrides the big-buffer threshold (bytes).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from .findings import Report
from .hlo_ir import shape_bytes
from .liveness import (
    ALIAS_OPS, FREE_OPS, LivenessResult, PreparedModule, xla_peak_bytes,
)

__all__ = ["DEFAULT_BIG_BUFFER", "DEFAULT_REMAT_SPAN", "GATED_MEM_CODES",
           "lint_memory_text", "lint_memory"]

DEFAULT_BIG_BUFFER = 1 << 20   # 1 MiB, matches jaxpr_lint.DEFAULT_BIG_BUFFER
DEFAULT_REMAT_SPAN = 16        # compute instructions a resident buffer spans

# codes the mem gate fails on (mem-remat-candidate is advisory only)
GATED_MEM_CODES = ("mem-over-budget", "mem-donation-would-help",
                   "mem-replicated-resident")


def _big_buffer_default() -> int:
    try:
        return int(os.environ.get("MEM_LINT_BIG_BUFFER", DEFAULT_BIG_BUFFER))
    except ValueError:
        return DEFAULT_BIG_BUFFER


def _tuple_elem_bytes(type_str: str):
    """Byte size of each element of a (possibly tuple) HLO type."""
    t = type_str.strip()
    if not t.startswith("("):
        return [shape_bytes(t)]
    inner, depth, start, out = t[1:-1] if t.endswith(")") else t[1:], 0, 0, []
    for i, c in enumerate(inner):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:   # dims/layouts nest commas in []/{}
            out.append(inner[start:i])
            start = i + 1
    out.append(inner[start:])
    return [shape_bytes(e) for e in out if e.strip()]


def _output_slots(res: LivenessResult):
    """Multiset of ROOT output element sizes (the slots donation can claim)."""
    if not res.entry_instrs:
        return {}
    root_type = res.entry_instrs[-1][2]
    slots: Dict[int, int] = {}
    for b in _tuple_elem_bytes(root_type):
        if b:
            slots[b] = slots.get(b, 0) + 1
    return slots


def _span_compute(res: LivenessResult, lt) -> int:
    """Compute instructions (non-free, non-alias) a lifetime spans."""
    lo, hi = max(lt.def_idx, 0) + 1, min(lt.last_idx, len(res.entry_instrs))
    return sum(1 for j in range(lo, hi)
               if res.entry_instrs[j][1] not in FREE_OPS
               and res.entry_instrs[j][1] not in ALIAS_OPS)


def lint_memory_text(
    text: str,
    *,
    hbm_budget: Optional[int] = None,
    declared_params: Optional[Dict[int, Tuple[str, int, bool]]] = None,
    big_buffer_bytes: Optional[int] = None,
    remat_span: int = DEFAULT_REMAT_SPAN,
    xla_peak: Optional[int] = None,
) -> Report:
    """Memory-lint an optimized HLO text dump.

    ``declared_params`` maps entry-parameter position to
    ``(label, global_bytes, sharded)`` — the same structure
    ``analysis._declared_params`` builds for hlo_lint."""
    big = _big_buffer_default() if big_buffer_bytes is None else big_buffer_bytes
    inject = os.environ.get("MEM_GATE_INJECT", "")
    mod = PreparedModule(text, ignore_donation=(inject == "strip-donation"))
    res = mod.analyze()

    rep = Report()
    rep.meta["peak_bytes"] = res.peak_bytes
    rep.meta["peak_at"] = res.peak_at
    rep.meta["num_partitions"] = res.num_partitions
    if xla_peak:
        rep.meta["xla_peak_bytes"] = int(xla_peak)
        rep.meta["peak_agreement"] = round(res.peak_bytes / max(xla_peak, 1), 4)

    # --- mem-over-budget -------------------------------------------------
    if hbm_budget is not None and res.peak_bytes > hbm_budget:
        rep.add("mem-over-budget", "high",
                f"modeled peak {res.peak_bytes / 1e6:.1f} MB exceeds the "
                f"declared per-device budget {hbm_budget / 1e6:.1f} MB",
                where=res.peak_at, bytes=res.peak_bytes - hbm_budget,
                suggestion="shrink batch/pools, shard further, or raise the budget")

    # --- mem-donation-would-help -----------------------------------------
    # Donated params claim matching output slots first (mirrors the slot
    # logic of jaxpr_lint.lint_donation); a remaining non-donated big param
    # with a free same-size slot is a candidate, confirmed by re-sweeping
    # with it donated and demanding a strictly lower peak.
    slots = _output_slots(res)
    params = sorted(res.params(), key=lambda l: l.param_index)
    for lt in params:
        if lt.donated and slots.get(lt.bytes, 0) > 0:
            slots[lt.bytes] -= 1
    for lt in params:
        if lt.donated or lt.bytes < big or slots.get(lt.bytes, 0) <= 0:
            continue
        what_if = mod.analyze(extra_donated={lt.param_index})
        delta = res.peak_bytes - what_if.peak_bytes
        if delta > 0:
            slots[lt.bytes] -= 1
            rep.add("mem-donation-would-help", "medium",
                    f"donating param {lt.param_index} "
                    f"({lt.bytes / 1e6:.3f} MB) lowers modeled peak by "
                    f"{delta / 1e6:.3f} MB",
                    where=lt.name, bytes=delta,
                    suggestion=f"add argnum {lt.param_index} to donate_argnums")

    # --- mem-remat-candidate (actionable: proven delta) -------------------
    # Each candidate is re-swept with its buffer rematerialized
    # (``drop_buffers``); the finding's ``bytes`` is the PROVEN peak drop,
    # not the buffer's size — the peak can move to another instruction when
    # a buffer is dropped, so the two differ.  The selective-remat policy
    # (``analysis.autotune.remat_policy``) ranks by this exact saving.
    for lt in res.lifetimes:
        if lt.is_param or lt.bytes < big or not lt.live_at_peak:
            continue
        span = _span_compute(res, lt)
        if span >= remat_span:
            what_if = mod.analyze(drop_buffers={lt.name})
            delta = max(0, res.peak_bytes - what_if.peak_bytes)
            rep.add("mem-remat-candidate", "low",
                    f"{lt.bytes / 1e6:.3f} MB activation resident across "
                    f"{span} compute instructions while peak is hit; "
                    f"rematerializing it provably drops the peak by "
                    f"{delta / 1e6:.3f} MB",
                    where=lt.name, bytes=delta,
                    suggestion="consider jax.checkpoint/remat around its producer")

    # --- mem-replicated-resident -----------------------------------------
    if declared_params and res.num_partitions > 1:
        for lt in params:
            decl = declared_params.get(lt.param_index)
            if decl is None:
                continue
            label, global_bytes, sharded = decl
            if sharded and global_bytes and lt.bytes >= global_bytes:
                rep.add("mem-replicated-resident", "high",
                        f"param {lt.param_index} ({label}) resident at global "
                        f"size {lt.bytes / 1e6:.3f} MB on each of "
                        f"{res.num_partitions} devices despite a sharded spec",
                        where=lt.name, bytes=lt.bytes,
                        suggestion="check in_shardings / shard_map in_specs "
                                   "reach this argument")
    return rep


def lint_memory(compiled, **kwargs) -> Report:
    """Memory-lint a compiled executable, cross-validating the liveness
    peak against ``compiled.memory_analysis()`` when available."""
    xp = xla_peak_bytes(compiled)
    if xp is not None and "xla_peak" not in kwargs:
        kwargs["xla_peak"] = xp[0]
    return lint_memory_text(compiled.as_text(), **kwargs)
