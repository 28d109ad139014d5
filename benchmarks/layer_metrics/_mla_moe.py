"""What the latent-attention, sparse-expert cells' readers share: the
program's expert-layer counters (``paddle_tpu.obs``, published by the cache
backend inside the engine's readback) as means, and the device time of a
named kernel inside the engine's programs.

A program that keeps no such counters (the parent of the PR that brought
them, a dense model) gives None, and the metric is left out."""
from benchmarks import peaks_mla_moe, tracered


def counters(red=None):
    """``{name: value}`` of the ``moe.*`` / ``mla.*`` counters, or None.
    With a reduced trace: what was counted between the first and the last
    readback inside the traced window (the backend's ``cache.counters``
    spans carry the running totals, 32 bits that wrap), so that the means
    are of the stretch whose device time the readers divide by; the run's
    totals where the window holds fewer than two readbacks."""
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    if red is not None:
        from benchmarks.layer_metrics import _program_spans

        tied = _program_spans.tie(red)
        marks = [] if tied is None else [
            args for name, lo, hi, args in tied.spans
            if name == "cache.counters" and args
            and tied.window[0] <= lo and hi <= tied.window[1]]
        if len(marks) >= 2:
            got = {k: float((int(marks[-1][k]) - int(marks[0][k])) % 2 ** 32)
                   for k in marks[0] if k in marks[-1]}
            if got.get("moe.steps"):
                return got
    snap = obs.registry().snapshot()
    got = {k: v["value"] for k, v in snap.items()
           if k.startswith(("moe.", "mla.")) and v.get("type") == "counter"}
    return got if got.get("moe.steps") else None


def decode_means(config, red=None):
    """``(rows, experts touched, largest expert's rows)`` of one expert layer
    in one decode step, the mean over the steps and layers ``counters``
    covers."""
    c = counters(red)
    if c is None:
        return None
    n = c["moe.steps"] * peaks_mla_moe.expert_layers(config)
    return (c.get("moe.rows", 0.0) / n, c.get("moe.experts_touched", 0.0) / n,
            c.get("moe.max_expert_rows", 0.0) / n)


def prefill_means(config, red=None):
    """``(rows, experts touched)`` of one expert layer in one prefill call
    and the (query, key) pairs of one call, or None before any prefill."""
    c = counters(red)
    if c is None or not c.get("moe.prefill_calls"):
        return None
    calls = c["moe.prefill_calls"]
    n = calls * peaks_mla_moe.expert_layers(config)
    return (c.get("moe.prefill_rows", 0.0) / n,
            c.get("moe.prefill_experts_touched", 0.0) / n,
            1024.0 * c.get("mla.prefill_kilo_pairs", 0.0) / calls)


def kernel_events(red, kernel, programs):
    """``[(start, seconds)]`` of the device operations named after
    ``kernel`` that ran inside a program whose name starts with one of
    ``programs``, wholly inside the traced window."""
    if not red.devices:
        return []
    dev = red.devices[0]
    inside = tracered.merge(tracered.module_intervals(dev, programs))
    out = []
    for raw, s, d in dev.ops:
        if kernel not in tracered.op_name(raw):
            continue
        if s < red.window[0] or s + d > red.window[1]:
            continue
        if any(lo <= s < hi for lo, hi in inside):
            out.append((s, d))
    return out
