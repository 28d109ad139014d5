"""The benchmark's readers of the program's own spans
(``benchmarks/layer_metrics/_program_spans.py``): the tie between the
program's clock and the trace's, on synthetic traces; and the new
``per_layer`` entries against the benchmark's own rules."""
import json
import os

import pytest

from benchmarks import harness, selfcheck
from benchmarks.layer_metrics import _program_spans as ps
from benchmarks.tracered import Device, Reduced

OFFSET = 12.345678            # trace clock - program clock, seconds
NEW = ("engine_queue_wait_p50_ms", "engine_first_token_p50_ms",
       "engine_blocked_share", "engine_host_ms_per_step",
       "train_dispatch_ms", "engine_warmup_s", "decode_slot_use_share",
       "decode_slot_empty_share", "prefill_row_use_share")
SLOT_SHARES = NEW[-3:]


def _x(name, t0, t1, tid=7, **args):
    return {"name": name, "ph": "X", "cat": "serve", "pid": 1, "tid": tid,
            "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6, "args": args}


def _mark(rid, phase, t):
    return {"name": phase, "ph": "n", "cat": "serve.request", "id": rid,
            "pid": 1, "tid": 7, "ts": t * 1e6}


def _run(n=5, step=0.75, gap=0.004, h_name="engine.step",
         p_name="serve.step"):
    """``n`` calls: the harness span opens 5 us before the program's and
    closes 3 us after it; the device is busy except while the host absorbs
    and admits (1 ms each) and dispatches (2 ms) between chunks."""
    host, events, busy = [], [], []
    t = 1.0
    for i in range(n):
        p0, p1 = t, t + step
        host.append((h_name, p0 + OFFSET - 5e-6, step + 8e-6))
        events.append(_x(p_name, p0, p1))
        events.append(_x("serve.admit", p0, p0 + 0.001, admitted=1))
        events.append(_x("serve.dispatch", p0 + 0.001, p0 + 0.003, k=32))
        events.append(_x("serve.decode-chunk", p0 + 0.0015, p0 + 0.0025))
        events.append(_x("serve.readback", p0 + 0.003, p1 - 0.001))
        events.append(_x("serve.absorb", p1 - 0.001, p1, tokens=32))
        # the device starts when the chunk is dispatched and ends as the
        # read-back returns
        busy.append(("jit_decode(1)", p0 + 0.0025 + OFFSET,
                     step - 0.0035))
        rid = f"u{i}"
        events.append(_mark(rid, "queued", p0 - 0.0005))
        events.append(_mark(rid, "prefill", p0 + 0.0005))
        events.append(_mark(rid, "first-token", p1 - 0.001))
        t = p1 + gap
    window = (1.0 + OFFSET - 0.01, t + OFFSET)
    dev = Device(index=0, modules=list(busy), ops=list(busy))
    return Reduced(devices=[dev], host_spans=host, window=window), events


def test_offset_found_within_the_slack():
    red, events = _run()
    tied = ps.tie_events(red, events)
    assert tied is not None and tied.paired == 5
    lo, hi = tied.interval_s
    assert lo <= OFFSET <= hi and hi - lo == pytest.approx(8e-6, abs=1e-9)
    assert abs(tied.offset_s - OFFSET) <= ps.SLACK_S
    steps = tied.intervals("serve.step")
    assert len(steps) == 5
    assert steps[0][0] == pytest.approx(1.0 + OFFSET, abs=ps.SLACK_S)


def test_a_dropped_program_span_gives_nothing(capsys):
    red, events = _run()
    steps = [e for e in events if e["name"] == "serve.step"]
    events.remove(steps[2])              # an instrumentation point rotted
    assert ps.tie_events(red, events) is None
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["program_spans"] == "counts differ"
    assert (said["harness_count"], said["program_count"]) == (5, 4)


def test_sequences_shifted_by_one_give_nothing(capsys):
    """Equal counts, but the program's first span is missing and a later
    one is extra: no single offset lets every harness span enclose its
    partner."""
    red, events = _run()
    steps = [e for e in events if e["name"] == "serve.step"]
    events.remove(steps[0])
    last = steps[-1]
    events.append(_x("serve.step", (last["ts"] + last["dur"]) * 1e-6 + 0.5,
                     (last["ts"] + last["dur"]) * 1e-6 + 1.0))
    assert ps.tie_events(red, events) is None
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["program_spans"] == "no offset fits every pair"


def test_no_harness_span_or_no_recorder_gives_nothing():
    red, events = _run()
    red.host_spans = []
    assert ps.tie_events(red, events) is None
    red2, _ = _run()
    assert ps.tie(red2) is None          # this process recorded no session


def test_train_pair_is_loose_below_and_tight_above():
    """``train_step`` also holds the batch's upload before the call: the
    lower side of the interval is loose by it, the upper side is not."""
    red, events = _run(n=4, step=0.29, h_name="train_step",
                       p_name="train.step")
    red.host_spans = [(n, s - 0.0012, d + 0.0012)
                      for n, s, d in red.host_spans]
    tied = ps.tie_events(red, events)
    lo, hi = tied.interval_s
    assert hi - lo == pytest.approx(0.0012 + 8e-6, abs=1e-9)
    assert tied.offset_s - OFFSET == pytest.approx(3e-6, abs=1e-9)


def test_innermost_splits_time_by_the_deepest_span():
    spans = [("a", 0.0, 10.0), ("b", 1.0, 4.0), ("c", 2.0, 3.0),
             ("d", 6.0, 7.0), ("e", 12.0, 13.0)]
    assert ps.innermost(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"),
        (4.0, 6.0, "a"), (6.0, 7.0, "d"), (7.0, 10.0, "a"),
        (12.0, 13.0, "e")]


def test_idle_is_named_by_the_innermost_program_span():
    red, events = _run()
    tied = ps.tie_events(red, events)
    idle = dict(ps.idle_by_program_span(red, tied))
    # per call: absorb 1 ms after the chunk, admit 1 ms, dispatch 1.5 ms of
    # which 1 ms inside the jitted call; between calls 4 ms in no span
    assert idle["serve.absorb"] == pytest.approx(5 * 0.001, abs=1e-4)
    assert idle["serve.admit"] == pytest.approx(5 * 0.001, abs=1e-4)
    assert idle["serve.dispatch"] == pytest.approx(5 * 0.0005, abs=1e-4)
    assert idle["serve.decode-chunk"] == pytest.approx(5 * 0.001, abs=1e-4)
    assert "serve.step" not in idle and "(none)" in idle
    # the host's whole window by span: the read-back holds nearly all of it
    host = dict(ps.by_program_span(tied, [list(red.window)]))
    assert sum(host.values()) == pytest.approx(red.window[1] - red.window[0])
    assert host["serve.readback"] == pytest.approx(5 * 0.746)
    assert host["serve.dispatch"] == pytest.approx(5 * 0.001)


def test_readers_on_a_tied_run(monkeypatch):
    red, events = _run()
    monkeypatch.setattr(ps, "_last", (red, ps.tie_events(red, events)))
    read = harness.layer_reader
    assert read("engine_queue_wait_p50_ms")(red, {}) == pytest.approx(1.0)
    assert read("engine_first_token_p50_ms")(red, {}) == pytest.approx(
        748.5)
    window_s = red.window[1] - red.window[0]
    assert read("engine_blocked_share")(red, {}) == pytest.approx(
        5 * 0.746 / window_s)
    assert read("engine_host_ms_per_step")(red, {}) == pytest.approx(4.0)
    # a training reader finds no train.step here: missing, not wrong
    assert read("train_dispatch_ms")(red, {}) is None


def _with_slot_args(events, parent=False):
    """Each call of ``_run()`` gains a prefill of two prompts (100 and 60
    tokens) in a bucket of 128 and a final chunk of 200 tokens in one of
    256; each chunk of k = 32 runs over 4 slots, 3 of them live, 80 of
    their 96 slot-steps kept.  ``parent``: the spans as PR 38 recorded
    them, without ``kept``, ``width`` and ``tokens``."""
    out = []
    for e in events:
        if e["name"] == "serve.dispatch" and not parent:
            e = {**e, "args": {**e["args"], "width": 4, "live": 3,
                               "kept": 80}}
        out.append(e)
        if e["name"] == "serve.admit":
            t0 = e["ts"] * 1e-6
            tok = ({}, {}) if parent else ({"tokens": 160}, {"tokens": 200})
            out.append(_x("serve.prefill", t0 + 2e-4, t0 + 5e-4,
                          bucket=128, n=2, **tok[0]))
            out.append(_x("serve.prefill-chunk", t0 + 5e-4, t0 + 8e-4,
                          bucket=256, final=True, **tok[1]))
    return out


@pytest.mark.parametrize("parent", [False, True])
def test_slot_and_row_shares_on_a_tied_run(monkeypatch, capsys, parent):
    red, events = _run()
    events = _with_slot_args(events, parent)
    monkeypatch.setattr(ps, "_last", (red, ps.tie_events(red, events)))
    got = {n: harness.layer_reader(n)(red, {"decode_steps_marked": 160})
           for n in SLOT_SHARES}
    if parent:          # the parent's spans carry no such args: nothing
        assert got == dict.fromkeys(SLOT_SHARES)
        return
    # 5 chunks of 32 x 4 slot-steps: 5 x 80 kept, 5 x 32 in the empty slot
    assert got["decode_slot_use_share"] == pytest.approx(400 / 640)
    assert got["decode_slot_empty_share"] == pytest.approx(160 / 640)
    # 5 x (160 + 200) prompt tokens in 5 x (2 x 128 + 256) rows
    assert got["prefill_row_use_share"] == pytest.approx(1800 / 2560)
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    decode = next(s for s in said if "decode_slot_steps" in s)
    assert decode["decode_slot_steps"]["steps"] == 160
    assert decode["decode_steps_marked"] == 160


@pytest.mark.parametrize("name", NEW)
def test_new_metric_has_its_reader_and_entry(name):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert name in entries and entries[name]["workloads"]
    assert os.path.exists(os.path.join(
        harness.HERE, "layer_metrics", f"{name}.py"))
    assert callable(harness.layer_reader(name))
    # nothing recorded and nothing traced: a reader returns None, never
    # raises (what the parent commit gives the driver)
    empty = Reduced(devices=[], host_spans=[], window=(0.0, 0.0))
    if name != "engine_warmup_s":
        assert harness.layer_reader(name)(empty, {}) is None


def test_benchmark_json_passes_its_own_rules():
    assert "names, units" in selfcheck.contract_limits()
    assert "cells" in selfcheck.files_agree()
