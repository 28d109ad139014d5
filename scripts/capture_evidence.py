"""Capture a TPU evidence bundle next to PERF.md.

This script runs on the chip and writes into ``evidence/``:

- ``device.json`` — device_kind / platform / client versions, straight from
  the PJRT client (no self-reporting).
- ``cost_<preset>.json`` — the compiled executable's OWN cost analysis
  (flops, bytes accessed) for the train step, plus the memory analysis
  (argument/output/temp sizes). These are the
  numbers PERF.md's MFU and roofline rows are derived from. The step is
  built by ``bench.build_pretrain_step`` — the EXACT program the benchmark
  measures — and compiled exactly once here.
- ``xplane/<run-stamp>/`` — a ``jax.profiler`` trace of a few real steps
  (``*.xplane.pb``). Each run
  traces into a fresh per-run directory so stale files from an earlier
  capture can never be counted as this run's evidence.

Usage: ``python scripts/capture_evidence.py [--presets base,longctx]``
(pretrain presets only: tiny/small/base/longctx — the decode/serve/ocr/moe
presets build their steps inside bench functions and record their cost
analyses in their own JSON lines).
It needs a TPU and exits with code 2 without one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
EVIDENCE = os.path.join(REPO, "evidence")

import bench  # noqa: E402  (stdlib-only at import time)

PRETRAIN_PRESETS = tuple(bench.DEFAULTS)


def _device_record(jax) -> dict:
    dev = jax.devices()[0]
    return {
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "num_devices": len(jax.devices()),
        "jax_version": jax.__version__,
        "default_backend": jax.default_backend(),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": bench.git_short_sha() or "unknown",
    }


def _cost_record(compiled) -> dict:
    from paddle_tpu.utils.xla_cost import (cost_of_executable,
                                           memory_of_executable)

    rec: dict = {}
    cost = cost_of_executable(compiled)
    if cost:
        rec["cost_analysis"] = {
            k: v for k, v in cost.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")
        }
    mem = memory_of_executable(compiled)
    if mem:
        rec["memory_analysis"] = mem
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--presets", default="base,longctx")
    ap.add_argument("--profile-steps", type=int, default=3)
    args = ap.parse_args()

    presets = [p.strip() for p in args.presets.split(",") if p.strip()]
    bad = [p for p in presets if p not in PRETRAIN_PRESETS]
    if bad:
        print(f"unsupported presets {bad}; choose from {PRETRAIN_PRESETS}",
              file=sys.stderr)
        sys.exit(2)

    import jax

    if jax.default_backend() != "tpu":
        print(f"capture_evidence.py needs a TPU; jax.default_backend() is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        sys.exit(2)

    import numpy as np

    os.makedirs(EVIDENCE, exist_ok=True)
    device = _device_record(jax)
    with open(os.path.join(EVIDENCE, "device.json"), "w") as f:
        json.dump(device, f, indent=2)
    print(f"[evidence] device: {device['device_kind']} "
          f"({device['default_backend']})")

    import jax.numpy as jnp

    from paddle_tpu.framework import random as rnd

    on_tpu = True
    profiled = False
    for preset in presets:
        step_fn, ids, model, _cfg, _ = bench.build_pretrain_step(
            preset, on_tpu)
        lowered = bench.lower_pretrain_step(step_fn, ids)
        compiled = lowered.compile()  # the ONE compile per preset
        rec = {"preset": preset, **device, **_cost_record(compiled)}
        path = os.path.join(EVIDENCE, f"cost_{preset}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        flops = rec.get("cost_analysis", {}).get("flops")
        print(f"[evidence] {path}: flops={flops}")

        if not profiled:
            # one xplane trace of real steps on the first preset, executing
            # the AOT executable directly (the jax.jit path would compile
            # a second time). donate_argnums=(0,2) invalidates
            # the inputs, so thread params/opt_state through the loop; a
            # fresh per-run directory so only THIS run's files count.
            stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            xdir = os.path.join(EVIDENCE, "xplane", stamp)
            params, buffers = step_fn._params, step_fn._buffers
            opt_state = step_fn._opt_state

            def run_step(params, opt_state):
                loss, params, opt_state = compiled(
                    params, buffers, opt_state,
                    jnp.asarray(3e-4, jnp.float32), jnp.asarray(1, jnp.int32),
                    rnd.next_key(), (ids._data,))
                float(np.asarray(loss))  # host read = sync
                return params, opt_state

            try:
                params, opt_state = run_step(params, opt_state)  # warmup
                with jax.profiler.trace(xdir):
                    for _ in range(args.profile_steps):
                        params, opt_state = run_step(params, opt_state)
                names = [os.path.join(dp, fn)
                         for dp, _, fns in os.walk(xdir) for fn in fns]
                print(f"[evidence] xplane trace ({stamp}): {len(names)} files")
                profiled = bool(names)
            except Exception as exc:
                print(f"[evidence] profiler unavailable: {exc!r}",
                      file=sys.stderr)
            del params, buffers, opt_state
        # the next preset allocates its own full model + AdamW state; two
        # resident 0.7B-class train states exceed the 16GB chip — release
        # EVERYTHING holding this preset's buffers (model Parameters and
        # TrainStep state included) before building the next
        del step_fn, lowered, compiled, model, ids


if __name__ == "__main__":
    main()
