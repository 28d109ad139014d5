"""Liveness-driven selective-remat / host-offload policy.

Given one compiled program and an HBM budget the program exceeds, pick the
CHEAPEST set of activations to stop keeping resident — analytically, from
the liveness model's per-buffer peak contributions, not by compiling a
sweep of remat configs:

1. candidates are the ``mem-remat-candidate`` buffers (big, live at the
   peak, long compute span) with their PROVEN peak deltas — each delta is
   a ``drop_buffers`` what-if re-sweep, so overlapping contributions are
   exact, not additive guesses;
2. greedy by delta per recompute-cost (output bytes proxy): add a buffer,
   re-sweep the cumulative drop set, stop when the modeled peak fits;
3. each chosen buffer is tagged ``remat`` or ``offload`` by comparing the
   modeled recompute cost against the round-trip host-transfer cost on the
   reference chip — short-span buffers recompute cheaply, whole-program
   residents are cheaper to park in host memory;
4. the plan maps to the model-level knob ``LlamaConfig.recompute_layers``
   (recompute the first k decoder layers): decoder layers are homogeneous,
   so the all-candidates delta divides evenly and
   ``k = ceil(needed / per_layer_saving)``.

Validation (tests + PERF.md): the re-swept predicted peak must agree with
``compiled.memory_analysis()`` of the APPLIED config within the existing
10% liveness bound, and the policy must buy at least one batch-size step
at fixed budget on the CPU proxy — the same trade PERF.md measured as the
base-preset b4 -> b6 boundary (0.56 GB over at b6 with remat off).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

from ..liveness import PreparedModule
from ..memory_lint import DEFAULT_REMAT_SPAN, _big_buffer_default, _span_compute
from .scorer import REF_CHIP

__all__ = ["RematAction", "RematPlan", "plan_remat", "plan_remat_lowered"]

# a buffer spanning more than this fraction of the program's compute is
# cheaper to round-trip to host memory than to recompute (its producer
# chain is most of the program)
OFFLOAD_SPAN_FRACTION = 0.75


@dataclass
class RematAction:
    buffer: str            # entry-instruction (buffer) name
    resident_bytes: int    # the buffer's own size
    proven_delta: int      # peak drop when this buffer alone is dropped
    span: int              # compute instructions it stays resident across
    action: str = "remat"  # "remat" | "offload"


@dataclass
class RematPlan:
    hbm_budget: int
    base_peak: int
    predicted_peak: int          # re-swept peak with the chosen set dropped
    fits: bool
    actions: List[RematAction] = field(default_factory=list)
    candidates: int = 0          # how many the policy could choose from
    n_layers: int = 0
    layers_to_remat: int = 0     # LlamaConfig.recompute_layers application
    per_layer_saving: int = 0

    @property
    def dropped_bytes(self) -> int:
        return self.base_peak - self.predicted_peak

    def summary(self) -> str:
        acts = sum(1 for a in self.actions if a.action == "remat")
        offs = len(self.actions) - acts
        return (f"peak {self.base_peak / 1e6:.1f} -> "
                f"{self.predicted_peak / 1e6:.1f} MB vs budget "
                f"{self.hbm_budget / 1e6:.1f} MB "
                f"({'fits' if self.fits else 'STILL OVER'}; "
                f"{acts} remat + {offs} offload of {self.candidates} "
                f"candidates; apply recompute_layers="
                f"{self.layers_to_remat}/{self.n_layers})")


def plan_remat(text: str, *, hbm_budget: int, n_layers: int = 0,
               big_buffer_bytes: Optional[int] = None,
               remat_span: int = DEFAULT_REMAT_SPAN) -> RematPlan:
    """Pick the cheapest activation set to drop until ``text``'s modeled
    peak fits ``hbm_budget``.  Analytic: one parse, one sweep per candidate
    plus one per greedy step — no candidate config is ever compiled."""
    big = _big_buffer_default() if big_buffer_bytes is None else big_buffer_bytes
    mod = PreparedModule(text)
    res = mod.analyze()
    base_peak = res.peak_bytes
    plan = RematPlan(hbm_budget=int(hbm_budget), base_peak=int(base_peak),
                     predicted_peak=int(base_peak),
                     fits=base_peak <= hbm_budget, n_layers=n_layers)
    # total compute length for the offload heuristic — computed once
    total_compute = _total_compute(res)

    # candidate set = the mem-remat-candidate filter, with proven deltas
    cands: List[RematAction] = []
    for lt in res.lifetimes:
        if lt.is_param or lt.bytes < big or not lt.live_at_peak:
            continue
        span = _span_compute(res, lt)
        if span < remat_span:
            continue
        delta = base_peak - mod.analyze(drop_buffers={lt.name}).peak_bytes
        action = ("offload" if total_compute
                  and span >= OFFLOAD_SPAN_FRACTION * total_compute
                  else "remat")
        cands.append(RematAction(buffer=lt.name, resident_bytes=lt.bytes,
                                 proven_delta=max(0, delta), span=span,
                                 action=action))
    plan.candidates = len(cands)
    if plan.fits or not cands:
        return plan

    # greedy: best proven saving per byte of recompute/transfer work first
    def cost(a: RematAction) -> float:
        if a.action == "offload":
            return 2.0 * a.resident_bytes / REF_CHIP["pcie_bytes_per_s"]
        return a.resident_bytes / REF_CHIP["hbm_bytes_per_s"]

    cands.sort(key=lambda a: (-(a.proven_delta / max(cost(a), 1e-12)),
                              a.buffer))
    chosen: List[RematAction] = []
    drop = set()
    for a in cands:
        chosen.append(a)
        drop.add(a.buffer)
        peak = mod.analyze(drop_buffers=drop).peak_bytes
        plan.predicted_peak = int(peak)
        if peak <= hbm_budget:
            plan.fits = True
            break
    plan.actions = chosen

    # model-level application: homogeneous decoder layers split the
    # all-candidates saving evenly, so the needed fraction maps to a count
    if n_layers > 0:
        all_drop = mod.analyze(
            drop_buffers={a.buffer for a in cands}).peak_bytes
        delta_all = max(0, base_peak - all_drop)
        plan.per_layer_saving = delta_all // n_layers if delta_all else 0
        need = base_peak - hbm_budget
        if plan.per_layer_saving > 0:
            plan.layers_to_remat = min(
                n_layers, math.ceil(need / plan.per_layer_saving))
        else:
            plan.layers_to_remat = n_layers
    return plan


def _total_compute(res) -> int:
    from ..liveness import ALIAS_OPS, FREE_OPS
    return sum(1 for _n, op, _t, _tl in res.entry_instrs
               if op not in FREE_OPS and op not in ALIAS_OPS)


def plan_remat_lowered(lowered, **kw) -> RematPlan:
    """Compile and plan against the optimized module text."""
    from ..liveness import compile_for_memory

    return plan_remat(compile_for_memory(lowered).as_text(), **kw)
