"""The reduction from a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: per device the programs (line ``XLA Modules``) and operations
(``XLA Ops``, ``Async XLA Ops``) with start and duration in seconds on the
trace's own clock, and the benchmark's host spans on the same clock.

Only ``jax.profiler.ProfileData`` is needed to read a trace.  Checked on the
two recorded traces under ``evidence/xplane/`` by ``benchmarks.selfcheck``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
WINDOW_SPAN = "bench.window"
# operations that only enclose others on the same line: counting them would
# count their bodies twice
CONTAINER = re.compile(r"^(while|conditional|call|closed_call)(\.\d+)?$")
NUMBER = re.compile(r"\.\d+$")
OPERAND = re.compile(r"%(?:params|opt_state|buffers)__+([A-Za-z0-9_]+?)__")


@dataclass
class Device:
    index: int
    modules: list = field(default_factory=list)    # (name, start_s, dur_s)
    ops: list = field(default_factory=list)
    async_ops: list = field(default_factory=list)


@dataclass
class Reduced:
    devices: list          # [Device], by index
    host_spans: list       # (name, start_s, dur_s) of the benchmark's own spans
    window: tuple          # (lo_s, hi_s): the steady window that was traced


# ------------------------------------------------------------- intervals --

def merge(intervals):
    """Sorted, disjoint ``[lo, hi]`` covering the same points."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(merged):
    return sum(hi - lo for lo, hi in merged)


def subtract(a, b):
    """Merged ``a`` without the points of merged ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append([cur, hi])
    return out


def gaps(merged, lo, hi):
    """The idle intervals of ``[lo, hi]`` that ``merged`` leaves."""
    return subtract([[lo, hi]], merged)


def spans_of(events):
    return [(s, s + d) for _, s, d in events]


# ---------------------------------------------------------------- reading --

def find_xplane(log_dir):
    """The newest ``*.xplane.pb`` below ``log_dir``, or None."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def op_name(raw):
    """``%fusion.16 = (u32[1]...) fusion(...)`` -> ``fusion.16``."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


def reduce(path, span_names=()):
    """Read one trace file.  ``span_names`` are the host spans to keep (the
    benchmark's own ``TraceAnnotation`` names); the window is the span
    ``bench.window`` if the trace has one, else the extent of the device
    operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = set(span_names) | {WINDOW_SPAN}
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(index=int(m.group(1)))
            for line in plane.lines:
                target = {"XLA Modules": dev.modules, "XLA Ops": dev.ops,
                          "Async XLA Ops": dev.async_ops}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    target.append((e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9))
                target.sort(key=lambda t: t[1])
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    devices.sort(key=lambda d: d.index)
    host.sort(key=lambda t: t[1])
    marks = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if marks:
        window = max(marks, key=lambda w: w[1] - w[0])
    else:
        every = [iv for d in devices for iv in spans_of(d.ops)]
        window = ((min(a for a, _ in every), max(b for _, b in every))
                  if every else (0.0, 0.0))
    host = [h for h in host if h[0] != WINDOW_SPAN]
    return Reduced(devices=devices, host_spans=host, window=window)


# ------------------------------------------------------------- reductions --

def busy(dev, window):
    """Merged intervals inside the window in which an operation ran."""
    return merge(clip(spans_of(dev.ops), *window))


def busy_seconds(red):
    """Seconds in which an operation ran, averaged over the devices that ran
    any, and the window's length."""
    used = [d for d in red.devices if d.ops]
    if not used:
        return 0.0, red.window[1] - red.window[0]
    total = sum(length(busy(d, red.window)) for d in used)
    return total / len(used), red.window[1] - red.window[0]


def module_intervals(dev, prefix, window=None):
    """``(start, end)`` of the programs whose name starts with ``prefix`` (a
    string or a tuple of them; a jitted function ``f`` runs as
    ``jit_f(<hash>)``), those that lie wholly inside the window."""
    out = []
    for name, s, d in dev.modules:
        if not name.startswith(prefix):
            continue
        if window and (s < window[0] or s + d > window[1]):
            continue
        out.append((s, s + d))
    return out


def module_durations(dev, prefix, window=None):
    return [b - a for a, b in module_intervals(dev, prefix, window)]


def collective_exposed(dev, within):
    """Seconds inside the merged intervals ``within`` in which a collective
    ran (either line) and no other operation did."""
    coll = merge((s, s + d) for n, s, d in dev.ops + dev.async_ops
                 if COLLECTIVE.search(op_name(n)))
    compute = merge((s, s + d) for n, s, d in dev.ops
                    if not COLLECTIVE.search(op_name(n)))
    exposed = subtract(coll, compute)
    return length(exposed) - length(subtract(exposed, merge(within)))


def top_ops(red, n=10):
    """``[[name, seconds], ...]``: the operations of device 0 that took most
    time in the window, summed under ``<program>/<op>``: the trace's name
    without its number (``fusion.76`` -> ``fusion``; a step has thousands of
    numbered copies and fusions), with the first parameter the operation
    reads in brackets where its text names one.  Operations that only
    enclose others are left out."""
    if not red.devices:
        return []
    dev = red.devices[0]
    starts = [s for _, s, _ in dev.modules]
    total = {}
    for raw, s, d in dev.ops:
        if s < red.window[0] or s + d > red.window[1]:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = "?"
        if i >= 0 and s < dev.modules[i][1] + dev.modules[i][2]:
            prog = dev.modules[i][0].split("(", 1)[0]
        op = op_name(raw)
        if CONTAINER.match(op):
            continue
        hint = OPERAND.search(raw)
        key = (f"{prog}/{NUMBER.sub('', op)}"
               + (f"[{hint.group(1)[:48]}]" if hint else ""))
        total[key] = total.get(key, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def top_idle_gaps(red, n=10):
    """``[[host span, seconds], ...]``: the idle time of device 0 inside the
    window, summed by the benchmark span the host was in for most of each
    gap (``(none)`` where it was in none)."""
    if not red.devices:
        return []
    idle = gaps(busy(red.devices[0], red.window), *red.window)
    spans = sorted(red.host_spans, key=lambda t: t[1])
    starts = [s for _, s, _ in spans]
    total = {}
    for lo, hi in idle:
        best, best_len = "(none)", 0.0
        i = bisect.bisect_right(starts, hi)
        for name, s, d in spans[max(0, i - 64):i]:
            ov = min(hi, s + d) - max(lo, s)
            # the innermost span wins a tie: it started later
            if ov > 0 and ov >= best_len:
                best, best_len = name, ov
        total[best] = total.get(best, 0.0) + (hi - lo)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]
