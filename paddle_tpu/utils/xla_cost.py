"""XLA cost-analysis helper shared by ``paddle.flops`` and ``bench.py``.

The JAX cost-analysis API has two entry points whose availability varies by
backend (HLO-level ``lowered.cost_analysis()``; executable-level
``lowered.compile().cost_analysis()``); this is the one place that chain
lives.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["flops_of_lowered", "cost_of_lowered", "cost_of_executable",
           "memory_of_executable"]


def _as_cost_dict(cost) -> Optional[dict]:
    """Normalize a cost-analysis result: executable-level ``cost_analysis``
    returns a one-dict-per-program LIST on some jaxlib versions, HLO-level
    returns the dict directly."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if cost and cost.get("flops") else None


def cost_of_lowered(lowered) -> Optional[dict]:
    """The full cost dict (``flops``, ``bytes accessed``, ...) of a lowered
    computation, or None."""
    for get in (lambda: lowered.cost_analysis(),
                lambda: lowered.compile().cost_analysis()):
        try:
            cost = _as_cost_dict(get())
        except Exception:
            continue
        if cost:
            return cost
    return None


def cost_of_executable(compiled) -> Optional[dict]:
    """Executable-level cost analysis from an already-compiled object (avoids
    the extra compile ``cost_of_lowered``'s fallback would trigger)."""
    try:
        return _as_cost_dict(compiled.cost_analysis())
    except Exception:
        return None


def memory_of_executable(compiled) -> Optional[dict]:
    """Scalar fields of the executable's memory analysis (argument/output/
    temp/generated-code sizes), or None where the backend omits it."""
    try:
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        # attribute reads can themselves raise (e.g. UNIMPLEMENTED), not
        # just AttributeError — keep them in the try
        out = {}
        for k in dir(mem):
            if k.startswith("_"):
                continue
            v = getattr(mem, k, None)
            if isinstance(v, (int, float)):
                out[k] = v
        return out or None
    except Exception:
        return None


def flops_of_lowered(lowered) -> Optional[float]:
    """FLOPs of a lowered jax computation, or None when neither analysis
    path yields a count (callers decide whether that is an error)."""
    cost = cost_of_lowered(lowered)
    return float(cost["flops"]) if cost else None
