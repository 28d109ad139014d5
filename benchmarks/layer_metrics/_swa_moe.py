"""What the window/full attention, sparse-expert cells' readers share: the
program's counters and the cache's gauges inside the traced window.

The backend's ``cache.counters`` spans (``paddle_tpu.obs``; one a readback)
carry the running totals of the counters and the gauges as they stood.
Counters are read as ``_mla_moe.counters`` reads them (the difference of the
window's last and first span); gauges as the mean over the window's spans.
A program without such spans or such names gives None, and the metric is
left out."""
from benchmarks.layer_metrics import _mla_moe, _program_spans

GAUGES = ("cache.kv_blocks_live", "cache.kv_bytes_per_token",
          "cache.window_slots_live", "cache.window_bytes_per_slot")


def counters(red):
    """``{name: value}`` of the ``moe.*`` / ``attn.*`` counters over the
    traced window, or None."""
    got = _mla_moe.counters(red)
    if got is None or "attn.prefill_kilo_pairs_full" in got:
        return got
    # the run's totals: ``_mla_moe`` keeps the names it knows of
    from paddle_tpu import obs

    snap = obs.registry().snapshot()
    got.update({k: v["value"] for k, v in snap.items()
                if k.startswith("attn.") and v.get("type") == "counter"})
    return got if "attn.prefill_kilo_pairs_full" in got else None


def gauges(red):
    """``{name: mean}`` of the cache's gauges over the readbacks inside the
    traced window, or None where the spans carry none."""
    tied = _program_spans.tie(red) if red is not None else None
    if tied is None:
        return None
    marks = [args for name, lo, hi, args in tied.spans
             if name == "cache.counters" and args
             and tied.window[0] <= lo and hi <= tied.window[1]
             and all(g in args for g in GAUGES)]
    if not marks:
        return None
    return {g: sum(float(m[g]) for m in marks) / len(marks) for g in GAUGES}


def prefill_call(config, red):
    """``(pairs inside a full layer's mask, pairs inside a window layer's,
    calls)`` of the prefill calls the window's counters cover (pairs are
    means of one call), or None before any prefill."""
    c = counters(red)
    if c is None or not c.get("moe.prefill_calls"):
        return None
    calls = c["moe.prefill_calls"]
    return (1024.0 * c.get("attn.prefill_kilo_pairs_full", 0.0) / calls,
            1024.0 * c.get("attn.prefill_kilo_pairs_window", 0.0) / calls,
            calls)
