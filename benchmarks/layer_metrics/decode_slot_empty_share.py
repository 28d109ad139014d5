"""Share of the decode chunks' slot-steps in the traced window spent in a
slot that holds no request or one mid-chunked-prefill:
Σ (``k`` x (``width`` - ``live``)) / Σ (``k`` x ``width``) over the engine's
``serve.dispatch`` spans."""
from benchmarks.layer_metrics import _slot_use


def read(red, run):
    d = _slot_use.decode(red, run)
    return d["empty"] / d["slot_steps"] if d and d["slot_steps"] else None
