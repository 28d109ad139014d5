"""Median device time of a prefill program (any bucket, any batch of the
4/2/1 ladder) inside the traced window."""
import statistics

from benchmarks import tracered
from benchmarks.layer_metrics._engine_programs import PREFILL


def read(red, run):
    if not red.devices:
        return None
    d = tracered.module_durations(red.devices[0], PREFILL, red.window)
    return 1e3 * statistics.median(d) if d else None
