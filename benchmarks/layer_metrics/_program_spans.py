"""The spans and request marks the program recorded itself (``paddle_tpu.obs``
records while a profiler session is on), placed on the trace's clock.

The program stamps its events on its own monotonic clock; the reduced trace
holds only the benchmark's spans.  The tie between the two clocks is the pair
of spans around each call into the program: the harness's ``engine.step``
encloses exactly one ``serve.step`` (``train_step`` one ``train.step``), and
the profiler starts between two calls, so both sequences begin with the same
call.  For every pair the offset (trace clock - program clock) lies in
``[h_start - p_start, h_end - p_end]``; the intersection over all pairs, with
``SLACK_S`` of slack, must not be empty.  The offset taken is its upper end,
the tight side: nothing runs between the program's return and the harness
span's exit, whereas ``train_step`` also holds the batch's upload before the
call.  If the counts differ or the intersection is empty, a line says so and
every reader returns None: a missing metric, never a wrong one.  So does a
program that has no such recorder (``obs.profiled_events`` is missing).
"""
from __future__ import annotations

from dataclasses import dataclass

from benchmarks import harness, tracered

SLACK_S = 50e-6
# (the harness's span, the program's span it encloses)
PAIRS = (("engine.step", "serve.step"), ("train_step", "train.step"))
# chain marks: a request's first prefill is dispatched at the first of these
DISPATCHED = ("prefill", "prefill-chunk")


@dataclass
class Tied:
    offset_s: float        # trace clock - program clock
    interval_s: tuple      # (lo, hi) the offset must lie in, without slack
    paired: int
    spans: list            # (name, lo_s, hi_s, args) on the trace's clock
    marks: list            # (request_id, phase, t_s) on the trace's clock
    window: tuple

    def intervals(self, name, whole=True):
        """``(lo, hi)`` of the spans of that name: those wholly inside the
        window, or every one clipped to it."""
        ivs = [(lo, hi) for n, lo, hi, _ in self.spans if n == name]
        lo_w, hi_w = self.window
        if whole:
            return [(a, b) for a, b in ivs if a >= lo_w and b <= hi_w]
        return tracered.clip(ivs, lo_w, hi_w)

    def elapsed_ms(self, start, end):
        """Per request, milliseconds from its first mark among the phases
        ``start`` to its first among ``end``, where both lie in the window."""
        first = {}
        for rid, phase, t in self.marks:
            if self.window[0] <= t <= self.window[1]:
                first.setdefault(rid, {}).setdefault(phase, t)
        out = []
        for phases in first.values():
            a = [phases[p] for p in start if p in phases]
            b = [phases[p] for p in end if p in phases]
            if a and b:
                out.append(1e3 * (min(b) - min(a)))
        return out


def offset_interval(harness_spans, program_spans):
    """``(lo, hi)``: where the offset must lie for every harness span
    ``(start, end)`` to enclose its program span, pair by pair in order."""
    lo = max(h[0] - p[0] for h, p in zip(harness_spans, program_spans))
    hi = min(h[1] - p[1] for h, p in zip(harness_spans, program_spans))
    return lo, hi


def tie_events(red, events):
    """``Tied`` from the reduced trace and the program's events (Chrome
    trace_event dicts, ``ts``/``dur`` in microseconds), or None with a line
    that says why."""
    for h_name, p_name in PAIRS:
        outer = [(s, s + d) for n, s, d in red.host_spans if n == h_name]
        if outer:
            break
    else:
        harness.say(program_spans="no harness span encloses a program span")
        return None
    calls = sorted((e for e in events
                    if e.get("ph") == "X" and e["name"] == p_name),
                   key=lambda e: e["ts"])
    inner = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6) for e in calls]
    if len(inner) != len(outer):
        harness.say(program_spans="counts differ", harness_span=h_name,
                    harness_count=len(outer), program_span=p_name,
                    program_count=len(inner))
        return None
    lo, hi = offset_interval(outer, inner)
    say = dict(harness_span=h_name, program_span=p_name, paired=len(outer),
               offset_lo_s=lo, offset_hi_s=hi, width_us=1e6 * (hi - lo))
    if hi + SLACK_S < lo:
        harness.say(program_spans="no offset fits every pair", **say)
        return None
    harness.say(program_spans="tied", **say)
    tid = calls[0]["tid"]          # the thread that drives the program
    spans = sorted(((e["name"], e["ts"] * 1e-6 + hi,
                     (e["ts"] + e["dur"]) * 1e-6 + hi, e.get("args") or {})
                    for e in events
                    if e.get("ph") == "X" and e["tid"] == tid),
                   key=lambda s: (s[1], -s[2]))
    marks = [(e["id"], e["name"], e["ts"] * 1e-6 + hi)
             for e in events if e.get("ph") == "n"]
    return Tied(offset_s=hi, interval_s=(lo, hi), paired=len(outer),
                spans=spans, marks=marks, window=red.window)


def innermost(spans):
    """``[(lo, hi, name)]``: for nested spans of one thread, sorted by start,
    the stretches of time by the innermost span the thread was in."""
    segs, stack, cur = [], [], 0.0

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][2] <= t:
            name, _, hi = stack.pop()
            if hi > cur:
                segs.append((cur, hi, name))
                cur = hi

    for name, lo, hi, *_ in spans:
        close_until(lo)
        if stack and lo > cur:
            segs.append((cur, lo, stack[-1][0]))
        cur = max(cur, lo)
        stack.append((name, lo, hi))
    close_until(float("inf"))
    return segs


def by_program_span(tied, within):
    """``[[name, seconds], ...]``: the time of the merged intervals
    ``within`` by the innermost program span the host was in (``(none)``
    outside all), largest first."""
    total, named = {}, 0.0
    for lo, hi, name in innermost(tied.spans):
        secs = tracered.length(tracered.clip(within, lo, hi))
        if secs > 0:
            total[name] = total.get(name, 0.0) + secs
            named += secs
    rest = tracered.length(within) - named
    if rest > 1e-9:
        total["(none)"] = rest
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def idle_by_program_span(red, tied):
    """Device 0's idle time inside the window, by program span."""
    return by_program_span(tied, tracered.gaps(
        tracered.busy(red.devices[0], red.window), *red.window))


_last = None       # (red, Tied or None): every reader of a run shares one tie


def tie(red):
    """The program's events of this run's profiler session, tied to ``red``'s
    clock; None where there is nothing to tie (said once, on an earlier
    line)."""
    global _last
    if _last is not None and _last[0] is red:
        return _last[1]
    try:
        from paddle_tpu import obs

        events = obs.profiled_events()
    except AttributeError:
        events = None              # a program without the recorder
    tied = tie_events(red, events) if events else None
    if tied is not None and red.devices:
        # where the host's time went in the window, and which of it the
        # device sat idle through
        harness.say(host_by_program_span=by_program_span(
            tied, [list(red.window)]))
        harness.say(idle_by_program_span=idle_by_program_span(red, tied))
    _last = (red, tied)
    return tied
