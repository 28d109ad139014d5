"""Fusion transformer: act on the fusion audit's pallas-candidate worklist.

``profiler.fusion_audit`` *finds* avoidable HBM traffic — duplicate reads,
missed Loop->Loop fusion chains, source regions whose members round-trip
intermediates the analytic-minimum byte model says could stay in VMEM.  This
module *acts* on that worklist, closing ROADMAP item 4's analyzer->transformer
loop the way ``schedule_engine`` closed it for pipeline schedules:

1. every flagged candidate is matched against the emitted-kernel sites in
   ``kernels.emit`` (pattern + source/op-hint match),
2. a matched site is accepted only if the audit byte model shows a real win
   (``bytes_saved > 0``), the emitted forward AND backward kernels replay
   bit-exact against the jnp reference in interpret mode — including an
   end-to-end ``jax.grad``-through-``custom_vjp`` leg — and the admission
   registry (``pallas_lint``) passes both kernels,
3. everything else is *rejected and reported* through the ``fuse-*`` findings
   codes; a rejected site is never activated, so the model seam falls back to
   the stock jnp path and training loss stays bit-identical by construction.

The resulting :class:`TransformPlan` carries the accepted substitutions and
their audited byte credit; ``plan.apply()`` is a context manager that flips
the ``kernels.emit`` activation table for the duration of a fused run
(what ``bench.py --fuse`` and the autotuner's ``fuse=auto`` axis use).

Finding codes (the ``fuse-*`` rows of the catalogue):

========================== ======================================================
``fuse-unmatched-site``    a flagged candidate has no emitter site — the
                           pattern is real but nothing can act on it yet
                           (advisory; flash-attention regions land here until
                           the attention seam is emitted)
``fuse-no-byte-win``       the analytic-minimum model shows no traffic saved;
                           substitution would be churn, not a win
``fuse-verify-mismatch``   an emitted kernel (fwd, bwd, or the end-to-end grad
                           through the installed ``custom_vjp``) diverges
                           bit-wise from the jnp reference in interpret mode
``fuse-admission-rejected`` ``kernels.registry`` admission (``pallas_lint``)
                           refused the emitted kernel — write race, coverage
                           hole, VMEM over budget, ...
========================== ======================================================
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .findings import Report

__all__ = ["TransformPlan", "plan_transform"]


@dataclass
class TransformPlan:
    """Outcome of one transformer pass over an audit worklist."""
    accepted: List[Dict] = field(default_factory=list)
    rejected: List[Dict] = field(default_factory=list)
    report: Report = field(default_factory=Report)
    candidates: int = 0

    @property
    def bytes_saved(self) -> int:
        return sum(int(a["bytes_saved"]) for a in self.accepted)

    def fused_bytes(self, stock_total: int) -> int:
        """Audit-model bytes_per_step of the substituted program: the stock
        audit total minus the verified, admitted savings.  (The fused HLO
        cannot be re-audited textually — pallas_call is a custom-call opaque
        to the parser — so the credit comes from the same analytic-minimum
        model that flagged the regions.)"""
        return max(0, int(stock_total) - self.bytes_saved)

    def sites(self) -> List[str]:
        """Accepted site names, deduped, in acceptance order."""
        seen: List[str] = []
        for a in self.accepted:
            if a["site"] not in seen:
                seen.append(a["site"])
        return seen

    def activation(self) -> Dict[str, object]:
        """Site name -> fused callable, the ``emit.activate`` table."""
        from ..kernels import emit
        return {s: emit.make_fused(s) for s in self.sites()}

    def apply(self):
        """Context manager: substitute the accepted sites into the model
        seams for the duration of the ``with`` block."""
        from ..kernels import emit
        return emit.activate(self.activation())

    def summary(self) -> Dict[str, object]:
        return {
            "candidates": self.candidates,
            "accepted": len(self.accepted),
            "rejected": len(self.rejected),
            "sites": self.sites(),
            "bytes_saved": self.bytes_saved,
            "finding_counts": self.report.counts(),
        }

    def describe(self) -> str:
        lines = [f"fusion transform: {len(self.accepted)}/{self.candidates} "
                 f"candidate(s) accepted, {self.bytes_saved / 1e6:.2f} MB "
                 f"audited traffic removed"]
        for a in self.accepted:
            lines.append(f"  + {a['candidate']} -> {a['site']} "
                         f"[{a['pattern']}] {a['bytes_saved'] / 1e6:.2f} MB")
        for r in self.rejected:
            tgt = f" -> {r['site']}" if r.get("site") else ""
            lines.append(f"  - {r['candidate']}{tgt} [{r['code']}]")
        if self.report:
            lines.append(self.report.report())
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps(self.summary(), sort_keys=True)


def _match_site(cand: Dict, sites: Dict[str, object]) -> Optional[str]:
    """First site (fixed declaration order -> deterministic) whose pattern and
    source/op-hint evidence match the candidate."""
    for name, site in sites.items():
        if site.matches(cand):
            return name
    return None


def plan_transform(audit_or_candidates, *, sites=None, verify: bool = True,
                   interpret: Optional[bool] = None,
                   admission: bool = True, shapes=None,
                   device=None) -> TransformPlan:
    """Run the transformer pass over an audit (or its ``pallas_candidates()``
    list) and return the :class:`TransformPlan`.

    Several candidates may map to one site (e.g. every decoder layer's silu
    MLP region matches ``fuse_swiglu_mlp`` — activating the seam substitutes
    all of them), so verification and admission run once per *site* while the
    byte credit accrues per *candidate*.

    ``shapes`` (``emit.llama_site_shapes``) gives the widths the program
    calls each site with; a site named there is also compiled at those
    widths for ``device`` (``emit.compile_refusal``), and what the TPU's
    compiler refuses is a ``fuse-admission-rejected`` in its own words.
    """
    from ..kernels import emit, registry

    sites = emit.SITES if sites is None else sites
    cands = (audit_or_candidates if isinstance(audit_or_candidates, list)
             else audit_or_candidates.pallas_candidates())
    plan = TransformPlan(candidates=len(cands))
    plan.report.meta["transform"] = "fusion"

    site_ok: Dict[str, Optional[str]] = {}   # site -> None (ok) | reject code

    def _site_status(name: str) -> Optional[str]:
        if name in site_ok:
            return site_ok[name]
        code: Optional[str] = None
        # admission (static safety lint) gates before the bit-exact replay:
        # an inadmissible kernel must never even be traced for verification
        if admission:
            try:
                registry.admit(name)
                registry.admit(name + "_bwd")
            except registry.KernelRejected as e:
                plan.report.add(
                    "fuse-admission-rejected", "high",
                    f"registry admission refused emitted kernel(s) for "
                    f"site {name}: {str(e).splitlines()[0]}",
                    where=name,
                    suggestion="site stays on the stock path; fix the "
                               "emission or raise the VMEM budget")
                code = "fuse-admission-rejected"
        if code is None and admission and name in (shapes or {}):
            words = emit.compile_refusal(name, *shapes[name], device=device)
            if words:
                plan.report.add(
                    "fuse-admission-rejected", "high",
                    f"the TPU compiler refused emitted kernel(s) for site "
                    f"{name} at the program's widths: {words}",
                    where=name,
                    suggestion="site stays on the stock path; the emitted "
                               "backward keeps every operand in one VMEM "
                               "block")
                code = "fuse-admission-rejected"
        if code is None and verify:
            vrep = emit.verify_site(name, interpret=(
                True if interpret is None else interpret))
            if vrep:
                plan.report.extend(vrep)
                code = "fuse-verify-mismatch"
        site_ok[name] = code
        return code

    for cand in cands:
        cname = cand.get("name", "?")
        pattern = cand.get("pattern", "")
        saved = int(cand.get("bytes_saved", 0))
        site = _match_site(cand, sites)
        if site is None:
            plan.report.add(
                "fuse-unmatched-site", "low",
                f"candidate {cname} [{pattern}] has no emitter site",
                where=cname, bytes=saved,
                suggestion="add a FusionSite in kernels.emit covering this "
                           "source region")
            plan.rejected.append({"candidate": cname, "site": None,
                                  "pattern": pattern,
                                  "code": "fuse-unmatched-site"})
            continue
        if saved <= 0:
            plan.report.add(
                "fuse-no-byte-win", "medium",
                f"candidate {cname} -> {site}: analytic-minimum model shows "
                f"no traffic saved",
                where=cname,
                suggestion="substitution would be churn; leave the seam on "
                           "the stock path")
            plan.rejected.append({"candidate": cname, "site": site,
                                  "pattern": pattern,
                                  "code": "fuse-no-byte-win"})
            continue
        code = _site_status(site)
        if code is not None:
            plan.rejected.append({"candidate": cname, "site": site,
                                  "pattern": pattern, "code": code})
            continue
        plan.accepted.append({"candidate": cname, "site": site,
                              "pattern": pattern, "bytes_saved": saved})

    plan.report.meta["fuse_candidates"] = plan.candidates
    plan.report.meta["fuse_accepted"] = len(plan.accepted)
    plan.report.meta["fuse_rejected"] = len(plan.rejected)
    plan.report.meta["fuse_bytes_saved"] = plan.bytes_saved
    return plan
