"""Least time of the full layers' paged decode attention (kernel
``gqa_paged_decode``: the live tokens' keys, 192 wide, and values, 128 wide,
of one layer's 4 KV heads, the queries in and the outputs out, over the
chip's HBM bytes/s) over its device time in the decode programs of the
traced window.  The published widths are counted: rows the pool pads would
show as lost share."""
from benchmarks import peaks_swa_moe
from benchmarks.layer_metrics import _mla_moe
from benchmarks.layer_metrics._engine_programs import DECODE


def read(red, run):
    cfg = run["config"]
    if "hybrid_layer_pattern" not in cfg or run["peaks"] is None:
        return None
    events = _mla_moe.kernel_events(red, "gqa_paged_decode", DECODE)
    if not events:
        return None
    least_s = len(events) * peaks_swa_moe.paged_decode_attn_bytes(
        cfg, run.get("live_kv_tokens", 0.0), cfg["engine"]["max_batch"]
    ) / run["peaks"]["hbm"]
    return least_s / sum(d for _, d in events)
