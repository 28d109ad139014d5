"""Model FLOPs of one step (``peaks.train_flops_per_token`` x tokens, no
recomputation counted) over chips x the chip's peak bf16 FLOP/s, over the
step program's device time: the share of the compute roofline the whole
step reaches, optimizer and loss included.  Compute-bound."""
from benchmarks import peaks
from benchmarks.layer_metrics import train_step_device_ms


def read(red, run):
    ms = train_step_device_ms.read(red, run)
    if ms is None or run["peaks"] is None:
        return None
    flops = (peaks.train_flops_per_token(run["config"], run["seq"])
             * run["tokens_per_step"])
    least_s = flops / (run["chips"] * run["peaks"]["flops"])
    return least_s / (ms * 1e-3)
