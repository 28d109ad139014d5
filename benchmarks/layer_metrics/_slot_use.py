"""What the engine's decode chunks and prefill calls spent their rows on,
from the args of its spans that lie wholly inside the traced window:
``serve.dispatch`` (``k``, ``width`` = the batch's slots, ``live`` = slots
that decoded, ``kept`` = their slot-steps inside their requests' budgets),
``serve.prefill`` (``bucket``, ``n``, ``tokens``) and
``serve.prefill-chunk`` (``bucket``, ``tokens``).  A program whose spans
carry no such args gives None, as does a window that holds no such span:
a missing metric, never a wrong one."""
from __future__ import annotations

from benchmarks import harness
from benchmarks.layer_metrics import _program_spans

_last = {}          # kind -> (red, sums or None): every reader shares one sum


def _args(tied, name, keys):
    lo_w, hi_w = tied.window
    out = [a for n, lo, hi, a in tied.spans
           if n == name and lo >= lo_w and hi <= hi_w]
    if any(not all(k in a for k in keys) for a in out):
        return None
    return out


def _once(kind, red, sums, **beside):
    """``sums(tied)`` of this run, said once on an earlier line with
    ``beside``."""
    got = _last.get(kind)
    if got is not None and got[0] is red:
        return got[1]
    tied = _program_spans.tie(red)
    out = sums(tied) if tied is not None else None
    if out is not None:
        harness.say(**{kind: out}, **beside)
    _last[kind] = (red, out)
    return out


def _decode(tied):
    spans = _args(tied, "serve.dispatch", ("k", "width", "live", "kept"))
    if not spans:
        return None
    return {"chunks": len(spans),
            "steps": sum(a["k"] for a in spans),
            "slot_steps": sum(a["k"] * a["width"] for a in spans),
            "kept": sum(a["kept"] for a in spans),
            "empty": sum(a["k"] * (a["width"] - a["live"]) for a in spans)}


def _prefill(tied):
    calls = _args(tied, "serve.prefill", ("bucket", "n", "tokens"))
    chunks = _args(tied, "serve.prefill-chunk", ("bucket", "tokens"))
    if calls is None or chunks is None or not calls + chunks:
        return None
    return {"calls": len(calls), "chunk_calls": len(chunks),
            "rows": (sum(a["bucket"] * a["n"] for a in calls)
                     + sum(a["bucket"] for a in chunks)),
            "tokens": sum(a["tokens"] for a in calls + chunks)}


def decode(red, run):
    """Sums over the window's decode chunks: ``steps`` (Σ k),
    ``slot_steps`` (Σ k x width), ``kept``, ``empty`` (Σ k x (width -
    live): empty and mid-prefill slots), or None.  The line that says them
    carries the runner's count of the decode steps dispatched between the
    window's marks, which ``steps`` should match to a chunk at each end."""
    return _once("decode_slot_steps", red, _decode,
                 decode_steps_marked=run.get("decode_steps_marked"))


def prefill(red):
    """Sums over the window's prefill calls: ``rows`` they ran and the
    prompt ``tokens`` among them, or None."""
    return _once("prefill_rows", red, _prefill)
