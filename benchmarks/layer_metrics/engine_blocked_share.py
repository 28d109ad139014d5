"""Share of the traced window the engine's thread spends inside
``serve.readback``, blocked on the device: it can neither admit an arrival
nor hand out a token meanwhile."""
from benchmarks import harness, stats, tracered
from benchmarks.layer_metrics import _program_spans


def read(red, run):
    tied = _program_spans.tie(red)
    if tied is None:
        return None
    reads = tied.intervals("serve.readback", whole=False)
    window_s = red.window[1] - red.window[0]
    if not reads or window_s <= 0:
        return None
    harness.say(**stats.describe("engine_readback_ms",
                                 [1e3 * (b - a) for a, b in reads], "ms"))
    return tracered.length(tracered.merge(reads)) / window_s
