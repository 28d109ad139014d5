"""Run one cell of the benchmark.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's file (``workloads/<cell>.json``) names its configuration, its
traffic mix and its runner; ``BENCHMARK.json`` says which metrics the cell
reports.  Earlier lines of output are one JSON object each (medians, sample
counts, what was compiled); the last line is the contract's object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics from a profiler trace of a few seconds of the steady window.

Without a TPU, or with fewer chips than the cell asks for, the run ends with
code 2 and prints no result.  ``--rehearse`` walks the same control flow on
the CPU at the tiny sizes the files give under ``rehearse`` and prints
``{"rehearsal": ...}`` in place of the result: never a device number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import gc                          # noqa: E402
import sys                         # noqa: E402

from benchmarks import harness     # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to leave the profiler's files in")
    args = ap.parse_args(argv)

    harness.REHEARSAL = args.rehearse
    cell, config, traffic = harness.load_cell(args.workload, args.rehearse)
    end_to_end, per_layer = harness.cell_metrics(cell["name"])
    seconds = args.seconds if args.seconds is not None else (
        3.0 if args.rehearse else 10.0)

    devices = harness.require_device(cell["chips"], args.rehearse)
    cache_dir = harness.enable_cache()
    counter = harness.CompileCounter()
    harness.say(phase="start", cell=cell["name"], config=cell["config"],
                traffic=cell["traffic"], seed=args.seed, seconds=seconds,
                trace=args.trace, device_kind=devices[0].device_kind,
                devices=len(devices), compile_cache=cache_dir)

    window = harness.Window(seconds, bool(args.trace), counter,
                            keep_dir=args.keep_trace,
                            **cell.get("trace_window", {}))
    runner = harness.load_module("runners", cell["runner"])
    res = runner.run(cell=cell, config=config, traffic=traffic,
                     seed=args.seed, window=window, devices=devices,
                     t_start=T_START, rehearse=args.rehearse)
    gc.collect()

    harness.say(phase="end_to_end", values=res["end_to_end"],
                compiled=res["compiled"], setup_compiles=counter.snapshot())
    if args.trace:
        red = window.reduce()
        if red is None:
            sys.exit("the profiler left no trace")
        from benchmarks import peaks, tracered

        run_info = dict(res["layer_inputs"], config=config,
                        chips=cell["chips"], compiled=res["compiled"],
                        peaks=None if args.rehearse else peaks.chip_peaks(
                            devices[0].device_kind))
        values = {}
        for m in per_layer:
            v = harness.layer_reader(m["name"])(red, run_info)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tracered.busy_seconds(red)
        breakdown = {"device_ops": tracered.top_ops(red),
                     "idle_gaps": tracered.top_idle_gaps(red)}
    else:
        values = {m["name"]: {"value": res["end_to_end"][m["name"]],
                              "unit": m["unit"]}
                  for m in end_to_end if m["name"] in res["end_to_end"]}
        busy = breakdown = None

    if args.rehearse:
        harness.say(rehearsal="passed" if res["correct"] else "FAILED",
                    cell=cell["name"], metrics_named=sorted(values),
                    attempted=res["attempted"], failed=res["failed"])
        sys.exit(0 if res["correct"] else 1)
    if args.trace and not busy[0] > 0:
        sys.exit("no operation ran on the device inside the traced window")
    harness.last_line(res["correct"], res["attempted"], res["failed"], values,
                      devices, res["memory_peak"], busy, breakdown)


if __name__ == "__main__":
    main()
