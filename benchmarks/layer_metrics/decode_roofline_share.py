"""Least time of one decode step (``peaks.decode_step_bytes``: every weight
once plus the keys and values of the tokens live in the batch, over the
chip's HBM bytes/s) over the device time of one decode step.  Memory-bound:
at 32 sequences the matmuls need 0.24 TFLOP a step, 1.2 ms at the peak,
against 9+ ms of weight traffic."""
from benchmarks import peaks
from benchmarks.layer_metrics import decode_token_device_ms


def read(red, run):
    ms = decode_token_device_ms.read(red, run)
    if ms is None or run["peaks"] is None:
        return None
    least_s = peaks.decode_step_bytes(
        run["config"], run.get("live_kv_tokens", 0.0)) / run["peaks"]["hbm"]
    return least_s / (ms * 1e-3)
