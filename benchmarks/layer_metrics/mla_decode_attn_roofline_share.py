"""Least time of the latent decode attention (kernel ``mla_paged_decode``:
the live tokens' latent rows of one layer, the queries in and the weighted
latent out, over the chip's HBM bytes/s) over its device time in the decode
programs of the traced window."""
from benchmarks import peaks_mla_moe
from benchmarks.layer_metrics import _mla_moe
from benchmarks.layer_metrics._engine_programs import DECODE


def read(red, run):
    events = _mla_moe.kernel_events(red, "mla_paged_decode", DECODE)
    if not events or run["peaks"] is None:
        return None
    cfg = run["config"]
    least_s = len(events) * peaks_mla_moe.latent_decode_attn_bytes(
        cfg, run.get("live_kv_tokens", 0.0), cfg["engine"]["max_batch"]
    ) / run["peaks"]["hbm"]
    return least_s / sum(d for _, d in events)
