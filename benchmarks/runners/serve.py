"""Serving runner: ``serving.Engine`` with the configuration's ``engine``
arguments, ``warmup()``, then an open loop of requests from one thread.

Each iteration sends what is due (``add_request``), calls ``step()`` once
and then asks which requests hold how many tokens; the time after the
``step()`` is when those tokens reached the host.  Time to first token runs
from the time a request was DUE.  With ``"drain": true`` the run goes on
after the window, under the same load, until the requests due inside it
have finished or ``drain_cap_s`` has passed (what is unfinished then has
failed).  Without it the window closes at the end of the first ``step()``
that returns after ``seconds``, and ``serve_tok_s`` is every output token
that reached the host by then over that time.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from .. import harness, stats
from ..adapters import engine_tokens


class Drive:
    """One open-loop drive of a warm engine over a list of arrivals."""

    def __init__(self, engine, arrivals, temperature=0.0, top_k=0, top_p=1.0):
        from paddle_tpu.serving import GenRequest

        self.engine, self.arrivals = engine, arrivals
        self._make = lambda a: GenRequest(
            prompt_ids=a.prompt_ids, max_new_tokens=a.max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            request_id=a.rid)
        self.by_id = {a.rid: a for a in arrivals}
        self.sent, self.refused = {}, {}
        self.count, self.first_t, self.last_t = {}, {}, {}
        self.finished, self.outputs = {}, {}
        self.delivered = []            # (time, tokens) per step
        self.live_kv = []              # (time, tokens of KV held by live requests)
        self._next = 0

    def send_due(self, now):
        with harness.span("add_request"):
            while (self._next < len(self.arrivals)
                   and self.arrivals[self._next].due_s <= now):
                a = self.arrivals[self._next]
                self._next += 1
                try:
                    self.engine.add_request(self._make(a))
                    self.sent[a.rid] = now
                except ValueError as e:        # refused: counts as failed
                    self.refused[a.rid] = str(e)

    def next_due(self):
        return (self.arrivals[self._next].due_s
                if self._next < len(self.arrivals) else None)

    def step(self, clock):
        """One ``Engine.step()``; returns the time after it."""
        with harness.span("engine.step"):
            outs = self.engine.step()
        t = clock()
        fresh = 0
        counts = engine_tokens.token_counts(self.engine)
        for o in outs:
            counts[o.request_id] = len(o.output_ids)
            self.finished[o.request_id] = t
            self.outputs[o.request_id] = list(o.output_ids)
        kv = 0
        for rid, c in counts.items():
            had = self.count.get(rid, 0)
            if c > had:
                fresh += c - had
                self.count[rid] = c
                self.first_t.setdefault(rid, t)
                self.last_t[rid] = t
            if rid not in self.finished and c > 0:
                kv += len(self.by_id[rid].prompt_ids) + c
        self.delivered.append((t, fresh))
        self.live_kv.append((t, kv))
        return t

    def judged_done(self):
        return all(a.rid in self.finished or a.rid in self.refused
                   for a in self.arrivals if a.judged)

    def waiting(self):
        """Requests sent that hold no token yet."""
        return sum(1 for rid in self.sent if rid not in self.first_t)


def build_engine(config, seed):
    import paddle_tpu as paddle
    from paddle_tpu.serving import Engine

    builder = harness.load_module("models", config["builder"])
    paddle.seed(seed)
    model = builder.build(config)
    e = dict(config["engine"])
    e["prefill_buckets"] = tuple(e["prefill_buckets"])
    engine = Engine(model, **e)
    return builder, model, engine


def warm(engine, config, traffic, seed):
    """``warmup()`` as a user calls it, then a few real requests through the
    loop, so that every eager helper the scheduler calls has run once."""
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    gen = harness.load_module("traffic", traffic["generator"])
    few = gen.make({**traffic, "rate_rps": 1000.0, "drain": False},
                   config["vocab_size"], seed + 1,
                   traffic.get("warm_requests", 8) / 1000.0)
    for a in few:
        a.max_new_tokens = min(a.max_new_tokens, 40)
    d = Drive(engine, few)
    d.send_due(1e9)
    while engine.has_work():
        d.step(time.perf_counter)
    return warm_s, len(few)


def run(*, cell, config, traffic, seed, window, devices, t_start, rehearse):
    builder, model, engine = build_engine(config, seed)
    plan = engine.memory_plan()
    warm_s, n_warm = warm(engine, config, traffic, seed)
    gen = harness.load_module("traffic", traffic["generator"])
    arrivals = gen.make(traffic, config["vocab_size"], seed, window.seconds)
    drive = Drive(engine, arrivals, traffic.get("temperature", 0.0),
                  traffic.get("top_k", 0), traffic.get("top_p", 1.0))
    harness.say(phase="setup", warmup_s=warm_s, warm_requests=n_warm,
                memory_plan_total=plan["total_bytes"],
                kv_pool_bytes=plan["kv_pool_bytes"],
                params_bytes=plan["params_bytes"], arrivals=len(arrivals),
                judged=sum(a.judged for a in arrivals))

    # a traced run stops at the end of its mark and waits for nothing
    drain = bool(traffic.get("drain")) and not window.trace
    cap = window.seconds + float(traffic.get("drain_cap_s", 0.0))
    marks = {}
    window.on_mark.append(lambda which: marks.__setitem__(
        which, (clock(), engine_tokens.decode_steps(engine))))

    setup_s = time.perf_counter() - t_start
    t_begin = window.begin()

    def clock():
        return time.perf_counter() - t_begin

    open_, t_close = True, None
    while True:
        now = clock()
        drive.send_due(now if open_ or drain else -1.0)
        if not engine.has_work():
            nxt = drive.next_due()
            if not open_ and (not drain or drive.judged_done()):
                break
            if nxt is None:
                break
            with harness.span("loadgen.sleep"):
                time.sleep(max(0.0, min(nxt - clock(), 0.002)))
            if open_ and not window.trace and clock() >= window.seconds:
                open_, t_close = False, clock()
            continue
        t = drive.step(clock)
        if open_:
            if not window.tick():
                open_, t_close = False, t
                if not drain:
                    break
        elif drive.judged_done() or t > cap:
            break
    if t_close is None:               # the arrivals ran out first
        t_close = clock()
    compiled = window.end()
    memory_peak = harness.peak_memory(devices[:cell["chips"]])

    # ---- metrics over the requests due inside the window ----------------
    judged = [a for a in arrivals if a.judged and a.due_s <= t_close]
    ttft, tpot, missing = [], [], 0
    for a in judged:
        done = a.rid in drive.finished and \
            len(drive.outputs[a.rid]) == a.max_new_tokens
        if drain and not done:
            missing += 1
            ttft.append(math.inf)
            continue
        if a.rid in drive.first_t:
            ttft.append(1e3 * (drive.first_t[a.rid] - a.due_s))
        if done and a.max_new_tokens >= 2:
            tpot.append(1e3 * (drive.last_t[a.rid] - drive.first_t[a.rid])
                        / (a.max_new_tokens - 1))
    late = [1e3 * (drive.sent[a.rid] - a.due_s) for a in arrivals
            if a.rid in drive.sent]
    tokens = sum(n for t, n in drive.delivered if t <= t_close)
    failed = len(drive.refused) + missing
    harness.say(**stats.describe("ttft_ms", ttft, "ms"))
    harness.say(**stats.describe("tpot_ms", tpot, "ms"))
    harness.say(**stats.describe("loadgen_late_ms", late, "ms"))
    harness.say(phase="window", closed_s=t_close, judged=len(judged),
                finished=len(drive.finished), refused=len(drive.refused),
                unfinished=missing, tokens_in_window=tokens,
                steps=len(drive.delivered), waiting_at_close=drive.waiting(),
                evictions=engine.stats.get("evictions"),
                least_samples=traffic.get("least_samples"))

    end_to_end = {"setup_s": setup_s,
                  "serve_tok_s": tokens / t_close if t_close else None}
    if ttft:       # a tail that holds a missing request reads as the cap
        end_to_end["ttft_p95_ms"] = min(stats.percentile(ttft, 95)[0],
                                        1e3 * cap)
    if tpot:
        end_to_end["tpot_p95_ms"] = stats.percentile(tpot, 95)[0]

    layer_inputs = {"late_ms": late}
    if "start" in marks and "end" in marks:
        (ta, sa), (tb, sb) = marks["start"], marks["end"]
        kv = [n for t, n in drive.live_kv if ta < t <= tb]
        layer_inputs.update(decode_steps_marked=sb - sa,
                            live_kv_tokens=float(np.mean(kv)) if kv else 0.0)

    # ---- correctness, outside the window, after the pools are freed -----
    n_layers = config["num_hidden_layers"]
    del engine, drive.engine
    gc.collect()
    correct, worst = check_tokens(builder, model, config, traffic, drive,
                                  seed, n_layers)
    harness.say(phase="correct", **worst)
    return {"correct": correct and failed == 0, "attempted": len(judged),
            "failed": failed, "end_to_end": end_to_end, "compiled": compiled,
            "memory_peak": memory_peak, "layer_inputs": layer_inputs}


def check_tokens(builder, model, config, traffic, drive, seed, n_layers):
    """A seeded sample of finished requests against the plain reference."""
    reference = harness.load_module("reference", config["reference"])
    done = sorted(rid for rid in drive.finished if rid in drive.by_id)
    if not done:
        return False, {"checked": 0}
    rng = np.random.default_rng(seed)
    sample = rng.choice(done, size=min(int(traffic.get("check_requests", 4)),
                                       len(done)), replace=False)
    pad = -(-(traffic["prompt"]["max"] + traffic["output"]["max"]) // 128) * 128
    checker = reference.TokenChecker(config, pad, traffic["output"]["max"])
    top = builder.top_weights(model)
    gaps = {}
    for rid in sample:
        a = drive.by_id[rid]
        gaps[rid] = checker.worst_gap_ulps(
            top, lambda i: builder.layer_weights(model, i), n_layers,
            a.prompt_ids, drive.outputs[rid])
    worst = max(gaps.values())
    return worst <= checker.ULPS, {"checked": len(gaps), "gap_ulps": gaps,
                                   "tol_ulps": checker.ULPS,
                                   "tokens_checked": sum(
                                       len(drive.outputs[r]) for r in sample)}
