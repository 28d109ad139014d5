"""Least time of the prefill attention of both layer kinds (kernel
``gqa_prefill_attn``: the (query, key) pairs INSIDE the mask, causal for a
full layer and the 128-wide band for a window layer, scores over 192 and
values over 128 for each of the 64 query heads, over the chip's bf16 FLOP/s;
or the bytes of q, k, v and o once where that is longer) over its device
time in the prefill programs of the traced window.  The pairs are the
program's own counts of the prompts' true lengths
(``attn.prefill_kilo_pairs_full`` / ``_window``: one layer of each kind, the
mean of a prefill call in the window); a call of the kernel a layer, so the
events divide among the kinds as the layers do."""
from benchmarks import peaks_swa_moe
from benchmarks.layer_metrics import _mla_moe, _swa_moe
from benchmarks.layer_metrics._engine_programs import PREFILL


def read(red, run):
    cfg = run["config"]
    if "hybrid_layer_pattern" not in cfg or run["peaks"] is None:
        return None
    events = _mla_moe.kernel_events(red, "gqa_prefill_attn", PREFILL)
    pre = _swa_moe.prefill_call(cfg, red)
    if not events or pre is None:
        return None
    n_full, n_window = peaks_swa_moe.layer_kinds(cfg)
    # a prompt's tokens from its causal pairs: n (n + 1) / 2
    tokens = (2.0 * pre[0]) ** 0.5
    per_call = (n_full * peaks_swa_moe.prefill_attn_least_s(
        cfg, pre[0], tokens, False, run["peaks"])
        + n_window * peaks_swa_moe.prefill_attn_least_s(
            cfg, pre[1], tokens, True, run["peaks"]))
    calls = len(events) / (n_full + n_window)
    return calls * per_call / sum(d for _, d in events)
