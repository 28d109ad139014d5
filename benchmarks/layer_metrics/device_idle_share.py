"""1 - (union of the ``XLA Ops`` intervals) / traced window, averaged over
the devices used."""
from benchmarks import tracered


def read(red, run):
    busy, window = tracered.busy_seconds(red)
    if not red.devices or window <= 0:
        return None
    return 1.0 - busy / window
