"""Share of the decode chunks' slot-steps in the traced window that fall
inside a request's budget: Σ ``kept`` / Σ (``k`` x ``width``) over the
engine's ``serve.dispatch`` spans.  The rest is empty or mid-prefill slots
(``decode_slot_empty_share``) and the tail a finished request runs to the
chunk's end."""
from benchmarks.layer_metrics import _slot_use


def read(red, run):
    d = _slot_use.decode(red, run)
    return d["kept"] / d["slot_steps"] if d and d["slot_steps"] else None
