"""The two readings a serving cell's token limit is set between, in one chip
call, for a configuration whose reference takes ``lowp``:

    chiprun --timeout 1500 -- python3 -m benchmarks.chip.precision_readings \\
        --workload serve_moe_mla_sat --seeds 2147483659,2147483693

Per seed: the engine at the published widths serves a few requests of one
prefill bucket to completion (two programs compile, not the whole ladder);
its tokens are then held against the plain reference twice, once as the
cell's ``correct`` does and once with the operands of the reference's
attention and expert products rounded to float8 (e4m3), the nearest
precision below the bfloat16 the configuration states.  The first reading
has to pass the limit and the second to fail it.  One JSON line a request
and reading.
"""
from __future__ import annotations

import argparse
import gc
import json

import numpy as np

from benchmarks import harness
from benchmarks.runners import serve


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2147483659")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    from paddle_tpu.serving import GenRequest

    harness.REHEARSAL = args.rehearse
    cell, config, traffic = harness.load_cell(args.workload, args.rehearse)
    harness.require_device(cell["chips"], args.rehearse)
    harness.enable_cache()
    bucket = sorted(config["engine"]["prefill_buckets"])[-3 if not
                                                         args.rehearse else 0]
    reference = harness.load_module("reference", config["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        builder, model, engine = serve.build_engine(config, seed)
        rng = np.random.default_rng(seed)
        prompts = {f"p{j}": rng.integers(
            1, config["vocab_size"], size=int(rng.integers(
                bucket // 2 + 1, bucket)), dtype=np.int32)
            for j in range(args.requests)}
        n_new = 65 if not args.rehearse else 9     # 1 + two chunks of 32
        for rid, ids in prompts.items():
            engine.add_request(GenRequest(prompt_ids=ids, max_new_tokens=n_new,
                                          request_id=rid))
        outs = {o.request_id: list(o.output_ids)
                for o in engine.run_to_completion()}
        del engine
        gc.collect()
        pad = -(-(bucket + n_new) // 128) * 128
        for name, lowp in (("as_stated", None),
                           ("float8_e4m3", jnp.float8_e4m3fn)):
            checker = reference.TokenChecker(config, pad, n_new, lowp=lowp)
            for rid, ids in prompts.items():
                gap = checker.worst_gap_ulps(
                    builder.top_weights(model),
                    lambda i: builder.layer_weights(model, i),
                    config["num_hidden_layers"], ids, outs[rid])
                print(json.dumps({"seed": seed, "request": rid,
                                  "reference": name, "gap_ulps": gap,
                                  "limit_ulps": checker.ULPS,
                                  "passes": bool(gap <= checker.ULPS)}),
                      flush=True)
        del model
        gc.collect()


if __name__ == "__main__":
    main()
