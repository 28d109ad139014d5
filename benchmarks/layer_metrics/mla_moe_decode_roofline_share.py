"""Least time of one decode step of the latent-attention, sparse-expert
decoder (``peaks_mla_moe.decode_step_bytes``: every non-expert weight once,
the weights of the experts that got a row by the program's own count
``moe.experts_touched``, the latent rows of the live tokens, over the chip's
HBM bytes/s) over the device time of one decode step.  Memory-bound: 192
routed rows a layer are 0.003 TFLOP a product against 1.1 GB of weights."""
from benchmarks import peaks_mla_moe
from benchmarks.layer_metrics import _mla_moe, decode_token_device_ms


def read(red, run):
    ms = decode_token_device_ms.read(red, run)
    means = _mla_moe.decode_means(run["config"], red)
    if ms is None or means is None or run["peaks"] is None:
        return None
    least_s = peaks_mla_moe.decode_step_bytes(
        run["config"], means[1], run.get("live_kv_tokens", 0.0)
    ) / run["peaks"]["hbm"]
    return least_s / (ms * 1e-3)
