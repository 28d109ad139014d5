"""Share of the step program's device time in which a collective operation
ran (``XLA Ops`` or ``Async XLA Ops``) and no other operation did, over the
steps that lie wholly inside the traced window, on the worst device."""
from benchmarks import tracered
from benchmarks.layer_metrics.train_step_device_ms import PROGRAM


def read(red, run):
    shares = []
    for dev in red.devices:
        steps = tracered.module_intervals(dev, PROGRAM, red.window)
        if steps:
            shares.append(tracered.collective_exposed(dev, steps)
                          / sum(b - a for a, b in steps))
    return max(shares) if shares else None
