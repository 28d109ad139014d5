"""Checks of the benchmark itself, on the CPU, with no chip:

    JAX_PLATFORMS=cpu python3 -m benchmarks.selfcheck [--no-rehearse]

- ``BENCHMARK.json`` and the files it names agree (every cell, configuration,
  traffic mix and per-layer reader exists; ``moves`` is reported wherever the
  metric is; no width is in ``reduced``);
- the trace reduction on the two recorded traces under ``evidence/xplane/``
  gives what a plain read of them gives;
- the interval arithmetic, on small cases;
- the traffic generators are deterministic in the seed, give every seed the
  same work in another order, and take a seed over 2**31;
- the percentile helper returns its sample count, and every cell that reports
  a tail states the least count its window was sized for;
- ``--rehearse`` runs every cell's control flow at tiny widths on CPU devices
  (one process per cell, four virtual devices for a four-chip cell).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

import numpy as np

from benchmarks import harness, stats, tracered

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


def expect(cond, what):
    if not cond:
        raise AssertionError(what)


def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@check
def files_agree():
    b = bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_experts_per_tok")
    for name, w in cells.items():
        cell, config, traffic = harness.load_cell(name)
        expect(cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
               and cell["chips"] == w["chips"], f"{name}: file and entry differ")
        entry = configs[w["config"]]
        expect(entry["file"] == f"benchmarks/configs/{w['config']}.json",
               f"{name}: configuration file")
        expect(sorted(entry["reduced"]) == sorted(config["reduced"]),
               f"{w['config']}: reduced differs from its file")
        for k in entry["reduced"]:
            expect(k not in widths and not k.endswith(("_dim", "_rank")),
                   f"{w['config']}: a width is in reduced")
            expect(config[k] != config["published"][k], f"{k} is not reduced")
        harness.load_module("runners", cell["runner"])
        harness.load_module("traffic", traffic["generator"])
        harness.load_module("models", config["builder"])
        harness.load_module("reference", config["reference"])
        mine_e2e, mine_layer = harness.cell_metrics(name)
        expect(any(m["name"] == "setup_s" for m in mine_e2e)
               and len(mine_e2e) >= 2 and mine_layer, f"{name}: too few metrics")
        for m in mine_layer:
            expect(m["moves"] in {x["name"] for x in mine_e2e},
                   f"{name}: {m['name']} moves {m['moves']}, not reported here")
            harness.layer_reader(m["name"])
        for m in mine_e2e:
            if "_p9" in m["name"]:
                expect(traffic.get("least_samples", {}).get(m["name"]),
                       f"{name}: no least sample count for {m['name']}")
    for c in b["configs"]:
        expect(any(w["config"] == c["name"] for w in cells.values()),
               f"{c['name']}: no cell uses it")
    four = sum(w["chips"] == 4 for w in cells.values())
    expect(four <= max(1, len(cells) // 4), "too many four-chip cells")
    expect(all(0 < m["bound"] <= 0.1 for m in e2e.values()), "a bound")
    return f"{len(cells)} cells, {len(configs)} configurations"


@check
def contract_limits():
    import re

    b = bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda t: 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    expect(sorted(b) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"]),
           "keys of BENCHMARK.json")
    expect(1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int),
           "run_seconds")
    expect(all(line(w) for w in b["command"]) and len(b["command"]) <= 32,
           "command")
    for c in b["configs"]:
        expect(sorted(c) == ["file", "name", "reduced", "source", "why"], c)
        expect(name.match(c["name"]) and line(c["why"]) and line(c["source"])
               and all(name.match(k) for k in c["reduced"]), c["name"])
    for w in b["workloads"]:
        expect(sorted(w) == ["chips", "config", "name", "traffic", "why"], w)
        expect(name.match(w["name"]) and name.match(w["traffic"])
               and line(w["why"]) and w["chips"] in (1, 4), w["name"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    expect(len(set(pairs)) == len(pairs), "a configuration-traffic pair twice")
    seen = set()
    for kind, keys in (("end_to_end", ["better", "bound", "name", "source",
                                       "unit"]),
                       ("per_layer", ["better", "layer", "moves", "name",
                                      "source", "unit"])):
        for m in b[kind]:
            expect(sorted(k for k in m if k != "workloads") == keys, m)
            expect(name.match(m["name"]) and unit.match(m["unit"])
                   and m["better"] in ("lower", "higher")
                   and m["name"] not in seen, m["name"])
            seen.add(m["name"])
            expect(m["source"] in (("host_clock", "device_trace")
                                   if kind == "end_to_end" else
                                   ("device_trace", "program_span",
                                    "program_counter", "host_clock")), m)
            if kind == "per_layer":
                expect(line(m["layer"]), m)
    size = os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json"))
    expect(size <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    return f"names, units, lines, keys; {size} bytes"


@check
def intervals():
    m = tracered.merge([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    expect(m == [[0, 2], [3, 4]], m)
    expect(tracered.length(m) == 3, "length")
    expect(tracered.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]])
           == [[0, 1], [2, 4], [6, 9]], "subtract")
    expect(tracered.gaps(m, 0, 5) == [[2, 3], [4, 5]], "gaps")
    expect(tracered.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)], "clip")
    dev = tracered.Device(index=0, ops=[
        ("%all-reduce.1 = f32[] all-reduce()", 0.0, 2.0),
        ("%fusion.2 = f32[] fusion()", 1.0, 2.0)],
        async_ops=[("%all-gather-start.3 = f32[]", 2.5, 2.0)])
    expect(abs(tracered.collective_exposed(dev, [(0.0, 10.0)]) - 2.5) < 1e-12,
           "exposed: 0-1 and 3-4.5")
    expect(abs(tracered.collective_exposed(dev, [(0.5, 3.5)]) - 1.0) < 1e-12,
           "exposed, clipped")
    return "merge, subtract, gaps, clip, exposed collectives"


@check
def recorded_traces():
    paths = sorted(glob.glob(os.path.join(
        harness.ROOT, "evidence", "xplane", "*", "**", "*.xplane.pb"),
        recursive=True))
    expect(len(paths) == 2, f"expected two recorded traces, found {paths}")
    want_window = [726.5, 735.4]
    for path, window_ms in zip(paths, want_window):
        red = tracered.reduce(path)
        dev = red.devices[0]
        steps = tracered.module_durations(dev, "jit_step_fn")
        expect(len(dev.modules) == 15 and len(steps) == 3
               and all(abs(1e3 * s - 192.4) < 0.05 for s in steps),
               f"XLA Modules: {len(dev.modules)} events, steps {steps}")
        busy, window = tracered.busy_seconds(red)
        expect(len(dev.ops) == 6666 and abs(1e3 * busy - 577.2) < 0.15
               and abs(1e3 * window - window_ms) < 0.05,
               f"XLA Ops: {len(dev.ops)} events, busy {busy}, window {window}")
        ops = tracered.top_ops(red, 3)
        expect(ops[0][0].startswith("jit_step_fn/fusion"), ops)
        idle = tracered.top_idle_gaps(red)
        expect(abs(sum(s for _, s in idle) - (window - busy)) < 1e-6, idle)
    return "15 programs, 3 steps of 192.4 ms, 6,666 ops, 577.2 ms busy"


@check
def traffic_is_seeded():
    for name in sorted({w["traffic"] for w in bench()["workloads"]}):
        t = harness.load_json("traffic", f"{name}.json")
        gen = harness.load_module("traffic", t["generator"])
        args = (t, 32768, 2**31 + 11) + (
            (30.0,) if t["generator"] == "open_loop" else ())
        other = (t, 32768, 2**31 + 12) + args[3:]
        a, b, c = gen.make(*args), gen.make(*args), gen.make(*other)
        if t["generator"] == "open_loop":
            key = lambda xs: [(x.due_s, x.max_new_tokens,
                               x.prompt_ids.tobytes()) for x in xs]
            expect(key(a) == key(b) and key(a) != key(c), f"{name}: seed")
            work = lambda xs: (sorted(len(x.prompt_ids) for x in xs),
                               sorted(x.max_new_tokens for x in xs))
            expect(work(a) == work(c), f"{name}: seeds differ in work")
            expect(all(x.due_s <= y.due_s for x, y in zip(a, a[1:])), "order")
            judged = [x for x in a if x.judged]
            expect(len(judged) == round(t["rate_rps"] * 30.0)
                   and judged[-1].due_s < 30.0, f"{name}: judged arrivals")
        else:
            x, y, z = a.next(), b.next(), c.next()
            expect((x == y).all() and (x != z).any()
                   and x.shape == (t["batch"], t["seq"])
                   and x.dtype == np.int32, f"{name}: seed")
    return "same seed same inputs, other seed other order, seeds over 2**31"


@check
def percentile_counts():
    v, n = stats.percentile(range(1, 241), 95)
    expect((v, n) == (228, 240), (v, n))
    d = stats.describe("x", list(range(1, 241)), "ms")
    expect(d["count"] == 240 and d["beyond_tail"] == 12, d)
    expect(stats.percentile([], 95) == (None, 0), "empty")
    print(json.dumps(d))
    return "p95 of 240 samples is the 228th, 12 beyond it"


def rehearse_cells():
    for w in bench()["workloads"]:
        for trace in (0, 1):
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            if w["chips"] > 1:
                env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                                    f"{w['chips']}")
            p = subprocess.run(
                [sys.executable, "-m", "benchmarks.run", "--workload",
                 w["name"], "--seed", str(2**31 + 11), "--seconds", "3",
                 "--trace", str(trace), "--rehearse"],
                capture_output=True, text=True, env=env, cwd=harness.ROOT)
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            expect(p.returncode == 0 and '"rehearsal": "passed"' in last,
                   f"{w['name']} --trace {trace}: rc {p.returncode}\n"
                   f"{p.stdout[-800:]}\n{p.stderr[-1500:]}")
            expect('"device"' not in last and "tokens/s" not in p.stdout,
                   f"{w['name']}: the rehearsal printed a device number")
            print(f"ok   rehearse {w['name']} --trace {trace}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--no-rehearse", action="store_true")
    args = ap.parse_args(argv)
    for fn in CHECKS:
        print(f"ok   {fn.__name__}: {fn()}", flush=True)
    if not args.no_rehearse:
        rehearse_cells()
    print("selfcheck passed")


if __name__ == "__main__":
    main()
