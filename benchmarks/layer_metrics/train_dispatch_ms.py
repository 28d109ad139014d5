"""Median duration of ``train.step``, all of ``TrainStep.__call__`` on the
host: what it costs to dispatch one step, inside the traced window."""
from benchmarks import harness, stats
from benchmarks.layer_metrics import _program_spans


def read(red, run):
    tied = _program_spans.tie(red)
    if tied is None:
        return None
    took = [1e3 * (b - a) for a, b in tied.intervals("train.step")]
    harness.say(**stats.describe("train_dispatch_ms", took, "ms"))
    return stats.median(took)[0]
