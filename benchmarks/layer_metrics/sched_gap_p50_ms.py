"""Median idle time of the device after a decode-chunk program, until the
next engine program starts, inside the traced window: the round trip in which
the host reads the chunk's tokens back, finishes and admits requests, and
dispatches the next round.  (Programs dispatched back to back inside one
round follow each other within microseconds and are not counted.)"""
import statistics

from benchmarks import tracered
from benchmarks.layer_metrics._engine_programs import ALL, DECODE


def read(red, run):
    if not red.devices:
        return None
    dev = red.devices[0]
    progs = tracered.module_intervals(dev, ALL, red.window)
    chunks = set(tracered.module_intervals(dev, DECODE, red.window))
    gaps = [b[0] - a[1] for a, b in zip(progs, progs[1:])
            if a in chunks and b[0] > a[1]]
    return 1e3 * statistics.median(gaps) if gaps else None
