"""The one place where the benchmark reads what ``serving.Engine`` does not
yet offer in public: how many tokens each live request holds after a
``step()``, and how many decode steps the engine has dispatched.

``Engine.step()`` returns finished requests only, so a client cannot see
when its first token arrived.  If the engine has a public
``token_counts()`` (PERF.md asks the ``tracing`` issue for it, under that
name) it is used; until then the counts come from the slots' requests.
"""
from __future__ import annotations


def token_counts(engine) -> dict:
    """``{request_id: tokens that reached the host}`` of the requests that
    hold a slot."""
    public = getattr(engine, "token_counts", None)
    if public is not None:
        return public()
    out = {}
    for slot in engine._slots:
        req = slot.req
        if req is not None:
            out[req.request_id] = len(req.prior_output) + len(req._out_vals)
    return out


def decode_steps(engine) -> int:
    """Decode steps dispatched so far (a chunk of k counts k)."""
    return int(engine.stats["decode_steps"])
