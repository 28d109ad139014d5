"""Find the knee of a serving cell once: the highest offered rate the engine
sustains.  One process, one warm engine, a handful of rates of some seconds
each, the engine drained between them.

    python3 -m benchmarks.sweep --workload serve_chat --rates 4,6,8,10,12,14 --seconds 16

At each rate arrivals run for ``--seconds`` and the second half is judged:
offered = output tokens of the requests due in that half, served = output
tokens that reached the host in it.  A rate is sustained when served stays
within 5% of offered and no more requests wait for a first token at the end
than at the middle.  The table goes to standard output, one JSON object per
rate, and the knee on the last line; the cells' traffic files and PERF.md
take their rates from it by hand.
"""
from __future__ import annotations

import argparse
import json
import time

from benchmarks import harness
from benchmarks.runners import serve


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    harness.REHEARSAL = args.rehearse
    cell, config, traffic = harness.load_cell(args.workload, args.rehearse)
    harness.require_device(cell["chips"], args.rehearse)
    harness.enable_cache()
    _, _, engine = serve.build_engine(config, args.seed)
    warm_s, _ = serve.warm(engine, config, traffic, args.seed)
    print(json.dumps({"phase": "setup", "warmup_s": None if args.rehearse
                      else warm_s}), flush=True)
    gen = harness.load_module("traffic", traffic["generator"])
    table = []
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = gen.make({**traffic, "rate_rps": rate, "drain": False},
                            config["vocab_size"], args.seed, args.seconds)
        drive = serve.Drive(engine, arrivals)
        half = args.seconds / 2
        t0 = time.perf_counter()

        def clock():
            return time.perf_counter() - t0

        waiting_mid = None
        while clock() < args.seconds:
            drive.send_due(clock())
            if waiting_mid is None and clock() >= half:
                waiting_mid = drive.waiting()
            if engine.has_work():
                drive.step(clock)
            else:
                time.sleep(0.002)
        waiting_end = drive.waiting()
        t_end = clock()
        offered = sum(a.max_new_tokens for a in arrivals
                      if half <= a.due_s < args.seconds)
        served = sum(n for t, n in drive.delivered if half <= t <= t_end)
        ttft = sorted(1e3 * (drive.first_t[a.rid] - a.due_s)
                      for a in arrivals if a.rid in drive.first_t)
        longest = 0.0
        t_drain = time.perf_counter()
        while engine.has_work():                 # drain before the next rate
            drive.step(clock)
        for a in arrivals:
            if a.rid in drive.finished:
                longest = max(longest, drive.finished[a.rid] - a.due_s)
        row = {"rate_rps": rate, "requests": len(arrivals),
               "offered_tok_s": offered / (args.seconds - half),
               "served_tok_s": served / (t_end - half),
               "waiting_mid": waiting_mid, "waiting_end": waiting_end,
               "ttft_median_ms": ttft[len(ttft) // 2] if ttft else None,
               "longest_request_s": longest,
               "drain_s": time.perf_counter() - t_drain}
        row["sustained"] = bool(
            row["served_tok_s"] >= 0.95 * row["offered_tok_s"]
            and waiting_end <= max(waiting_mid or 0, 1))
        table.append(row)
        if not args.rehearse:
            print(json.dumps(row), flush=True)
    ok = [r["rate_rps"] for r in table if r["sustained"]]
    print(json.dumps({"knee_rps": (max(ok) if ok else None)
                      if not args.rehearse else "rehearsal",
                      "rates": [r["rate_rps"] for r in table]}), flush=True)


if __name__ == "__main__":
    main()
