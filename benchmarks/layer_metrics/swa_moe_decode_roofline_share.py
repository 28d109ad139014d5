"""Least time of one decode step of the window/full attention, sparse-expert
decoder (``peaks_swa_moe.decode_step_bytes``: every non-expert weight once,
the weights of the HELD experts that got a row by the program's own count
``moe.experts_touched``, the full layers' keys and values of the live
tokens, the window layers' rings of the live slots, over the chip's HBM
bytes/s) over the device time of one decode step.  Memory-bound: 32 rows a
step are nothing against 6 GB of weights."""
from benchmarks import peaks_swa_moe
from benchmarks.layer_metrics import (_mla_moe, _swa_moe,
                                      decode_token_device_ms)


def read(red, run):
    cfg = run["config"]
    if "hybrid_layer_pattern" not in cfg or run["peaks"] is None:
        return None
    ms = decode_token_device_ms.read(red, run)
    means = _mla_moe.decode_means(cfg, red)
    g = _swa_moe.gauges(red)
    if ms is None or means is None or g is None:
        return None
    least_s = peaks_swa_moe.decode_step_bytes(
        cfg, means[1], run.get("live_kv_tokens", 0.0),
        g["cache.window_slots_live"]) / run["peaks"]["hbm"]
    return least_s / (ms * 1e-3)
