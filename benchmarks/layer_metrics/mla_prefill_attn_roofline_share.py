"""Least time of the prefill attention with unequal widths (kernel
``mla_prefill_attn``: the causal half of the prompts' (query, key) pairs,
scores over 192 and values over 128 for every head, over the chip's bf16
FLOP/s) over its device time in the prefill programs of the traced window.
The pairs are the program's own count of the prompts' true lengths
(``mla.prefill_kilo_pairs``), the mean of a prefill call in the window; one kernel call a
layer."""
from benchmarks import peaks_mla_moe
from benchmarks.layer_metrics import _mla_moe
from benchmarks.layer_metrics._engine_programs import PREFILL


def read(red, run):
    events = _mla_moe.kernel_events(red, "mla_prefill_attn", PREFILL)
    pre = _mla_moe.prefill_means(run["config"], red)
    if not events or pre is None or run["peaks"] is None:
        return None
    least_s = len(events) * peaks_mla_moe.prefill_attn_flops(
        run["config"], pre[2]) / run["peaks"]["flops"]
    return least_s / sum(d for _, d in events)
