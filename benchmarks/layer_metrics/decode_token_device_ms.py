"""Device time of the decode-chunk programs inside the traced window over
the decode steps the engine dispatched in it (a chunk of k steps counts k):
the device time of one decode step of the whole batch."""
from benchmarks import tracered
from benchmarks.layer_metrics._engine_programs import DECODE


def read(red, run):
    steps = run.get("decode_steps_marked")
    if not red.devices or not steps:
        return None
    d = tracered.module_durations(red.devices[0], DECODE, red.window)
    return 1e3 * sum(d) / steps if d else None
