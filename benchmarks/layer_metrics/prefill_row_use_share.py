"""Share of the rows the prefill programs ran in the traced window that
hold a prompt token, the rest being the bucket's padding: Σ ``tokens`` /
Σ rows over the engine's ``serve.prefill`` (``bucket`` x ``n`` rows) and
``serve.prefill-chunk`` (``bucket`` rows) spans."""
from benchmarks.layer_metrics import _slot_use


def read(red, run):
    p = _slot_use.prefill(red)
    return p["tokens"] / p["rows"] if p and p["rows"] else None
