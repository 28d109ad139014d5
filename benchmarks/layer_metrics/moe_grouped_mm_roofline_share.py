"""Least time of the expert layers' grouped products (kernel
``moe_grouped_mm``) over their device time, in the decode and the prefill
programs of the traced window together.  A decode call's least time is that
of the touched experts' weight bytes, a prefill call's that of its
operations (``peaks_mla_moe.grouped_mm_least_s``), from the program's own
counts of rows and of experts touched (means over the traced window); two kernel
calls make one expert layer."""
from benchmarks import harness, peaks_mla_moe
from benchmarks.layer_metrics import _mla_moe
from benchmarks.layer_metrics._engine_programs import (CHUNK_PREFILL, DECODE,
                                                       PREFILL)

KERNEL = "moe_grouped_mm"


def read(red, run):
    cfg, peaks = run["config"], run["peaks"]
    dec = _mla_moe.decode_means(cfg, red)
    if dec is None or peaks is None:
        return None
    d_ev = _mla_moe.kernel_events(red, KERNEL, DECODE)
    p_ev = _mla_moe.kernel_events(red, KERNEL, (PREFILL, CHUNK_PREFILL))
    took = sum(d for _, d in d_ev) + sum(d for _, d in p_ev)
    if not took:
        return None
    least_d = len(d_ev) / 2 * peaks_mla_moe.grouped_mm_least_s(
        cfg, dec[0], dec[1], peaks)
    pre = _mla_moe.prefill_means(cfg, red)
    least_p = 0.0 if pre is None else len(p_ev) / 2 * \
        peaks_mla_moe.grouped_mm_least_s(cfg, pre[0], pre[1], peaks)
    harness.say(grouped_mm={
        "decode_calls": len(d_ev), "decode_s": sum(d for _, d in d_ev),
        "decode_least_s": least_d, "prefill_calls": len(p_ev),
        "prefill_s": sum(d for _, d in p_ev), "prefill_least_s": least_p,
        "decode_rows_experts_max": dec, "prefill_rows_experts_pairs": pre})
    return (least_d + least_p) / took
