"""Median device time of the training step's program on the ``XLA
Modules`` line, over the steps that lie wholly inside the traced window; on
several chips, of the slowest device."""
import statistics

from benchmarks import tracered

PROGRAM = "jit_step_fn"     # paddle.jit.TrainStep's jitted function


def per_device(red):
    out = []
    for dev in red.devices:
        d = tracered.module_durations(dev, PROGRAM, red.window)
        if d:
            out.append(statistics.median(d))
    return out


def read(red, run):
    meds = per_device(red)
    return 1e3 * max(meds) if meds else None
