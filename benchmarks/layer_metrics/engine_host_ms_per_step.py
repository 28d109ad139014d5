"""The host's own work per ``Engine.step()``: the ``serve.step`` spans wholly
inside the traced window, less the time they spend blocked in
``serve.readback``, over their count.  To be held against
``sched_gap_p50_ms``, the same round trip seen from the device."""
from benchmarks import harness, stats, tracered
from benchmarks.layer_metrics import _program_spans


def read(red, run):
    tied = _program_spans.tie(red)
    if tied is None:
        return None
    steps = tied.intervals("serve.step")
    if not steps:
        return None
    reads = tracered.merge(tied.intervals("serve.readback", whole=False))
    own = [1e3 * (b - a - tracered.length(tracered.clip(reads, a, b)))
           for a, b in steps]
    harness.say(**stats.describe("engine_step_host_ms", own, "ms"))
    return sum(own) / len(own)
