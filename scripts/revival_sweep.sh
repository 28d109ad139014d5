#!/bin/bash
# Manual on-chip measurement sweep: sequential bench.py commands, one
# process on the chip at a time.  Run it on the machine that holds the chip
# (through the chip tool: one call, results under chiprun_out/):
#   1. gradient-accumulation sweep on the base preset (the next MFU lever:
#      one AdamW pass per k micro-batches; bf16 accumulator fits HBM).
#      CPU-mesh proxy ladder (tiny, scan-measured step time, 2026-08-05):
#      4488 -> 11102 -> 12238 tokens/s at accum 1 -> 2 -> 4 — the
#      amortized optimizer is worth ~2.7x on a bandwidth-starved backend;
#      these rows put the real-chip numbers next to that.
#   2. ZeRO-1 gather/compute overlap A/B on the wus presets: --wus seq vs
#      --wus overlap, --overlap so each line carries the analyzer's
#      exposed-bytes split for the on-chip schedule (CPU-proxy drop on
#      small: 81% of exposed all-gather bytes; the analytic ~47 ms/step
#      optimizer win quoted in PERF.md is re-measured here)
#   3. serving-engine run at the post-rework SHA (batched prefill + sampling)
#   4. an on-chip smoke of the sampling program (has only ever run on CPU)
# Results append to chiprun_out/BENCH_ACCUM_SWEEP.jsonl (the accum rows
# change the preset's global-batch semantics: keep the "accum" field visible).
cd "$(dirname "$0")/.." || exit 1
mkdir -p chiprun_out
OUT=chiprun_out/BENCH_ACCUM_SWEEP.jsonl
for args in "--accum 2 --grad-dtype bfloat16" "--accum 4 --grad-dtype bfloat16" "--accum 4"; do
    echo "[revival] base $args" >&2
    line=$(timeout 2400 python bench.py --preset base --device tpu $args 2>/dev/null | tail -1)
    [ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
done
# tuner-chosen config on the real chip: the static sweep picks the plan,
# the measured row lands next to the hand-picked accum rows above so the
# ranking can be checked against chip truth (tune_* fields carry the table)
echo "[revival] base --tune" >&2
line=$(timeout 2400 python bench.py --preset base --device tpu --tune 2>/dev/null | tail -1)
[ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
for args in "--wus seq --overlap" "--wus overlap --overlap"; do
    for preset in small base; do
        echo "[revival] $preset $args" >&2
        line=$(timeout 2400 python bench.py --preset $preset --device tpu $args 2>/dev/null | tail -1)
        [ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
    done
done
# MPMD pipeline runtime A/B (per-stage programs + explicit ICI transfers
# vs the lockstep SPMD scan): CPU-proxy numbers (2026-08-06) are pp=4 ZB
# 1.71x tok/s over lockstep 1F1B (bubble 0.43 -> ~0) and pp=2 1.43x; these
# rows measure the same A/B where the transfers ride real ICI instead of
# host RAM, at both pp widths and both schedules
for args in "--pp 2 --pp-runtime both --pp-schedule zb" \
            "--pp 4 --pp-runtime both --pp-schedule zb" \
            "--pp 4 --pp-runtime both --pp-schedule 1f1b"; do
    echo "[revival] pp $args" >&2
    line=$(timeout 2400 python bench.py --device tpu $args 2>/dev/null | tail -1)
    [ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
done
# observability: the MPMD A/B again, but dumping the span trace
# (+ a jax.profiler XLA capture via --otrace-xla) so the on-chip per-stage
# timeline and its trace-vs-analytic bubble crosscheck land as artifacts;
# open chiprun_out/revival_otrace.json in ui.perfetto.dev, the .xla dir in
# tensorboard.  CPU-proxy rel_err (2026-08-06): pp2 0.064, pp4 ~0.000.
echo "[revival] pp --otrace (obs)" >&2
line=$(timeout 2400 python bench.py --device tpu --pp 4 --pp-runtime mpmd \
       --pp-schedule zb --otrace chiprun_out/revival_otrace.json --otrace-xla \
       2>/dev/null | tail -1)
[ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
echo "[revival] sampling smoke" >&2
timeout 1200 env -u JAX_PLATFORMS python - <<'PY' >&2
import numpy as np, sys
sys.path.insert(0, '.')
import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import Engine, GenRequest
paddle.seed(0)
m = LlamaForCausalLM(llama_tiny_config(dtype="bfloat16"))
eng = Engine(m, max_batch=2, num_blocks=16, block_size=128, prefill_buckets=(128,), decode_chunk=8)
p = np.random.default_rng(0).integers(1, 512, size=(20,)).astype(np.int32)
eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=8, temperature=0.8, top_k=50, top_p=0.9))
(out,) = eng.run_to_completion()
print("sampling-on-chip OK:", out.output_ids)
PY
# fusion-transformer A/B on real ICI: stock vs emitted-Pallas-substituted
# program in ONE process (losses must stay bit-identical both directions).
# CPU-proxy numbers (2026-08-07): tiny audited bytes 237.6MB -> 163.7MB
# (-31.1%), wall 57.9 -> 47.7 ms/step; these rows measure the same A/B
# where the fused kernels run compiled on the chip instead of interpret
for preset in tiny base; do
    echo "[revival] $preset --fuse" >&2
    line=$(timeout 2400 python bench.py --preset $preset --device tpu --fuse 2>/dev/null | tail -1)
    [ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
done
# tuner with the fuse=auto axis on-chip: the grid now carries fuse plans
# (admission-failing ones are pruned, never ranked); the chosen row lands
# next to the --fuse A/B above so the byte-model credit can be checked
# against the measured drop
echo "[revival] base --tune (fuse=auto axis)" >&2
line=$(timeout 2400 python bench.py --preset base --device tpu --tune 2>/dev/null | tail -1)
[ -n "$line" ] && echo "$line" >> "$OUT" && echo "$line" | head -c 200 >&2 && echo >&2
# SSD chunked scan vs flash attention, matched token-mixing shape, real
# chip: the O(1)-state scan's step time next to the O(S) flash kernel it
# replaces (B=4, S=2048, H=8, D=64; fwd, jitted, median of 20)
echo "[revival] ssd chunked-scan vs flash step time" >&2
timeout 1200 env -u JAX_PLATFORMS python - <<'PY' >&2
import sys, time
sys.path.insert(0, '.')
import jax, jax.numpy as jnp
import numpy as np
from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.ssd_scan import ssd_scan

B, S, H, D, N = 4, 2048, 8, 64, 64
rng = np.random.default_rng(0)
f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32) * 0.1
q, k, v = f(B, S, H, D), f(B, S, H, D), f(B, S, H, D)
x, b, c = f(B * H, S, D), f(B * H, S, N), f(B * H, S, N)
la = -jnp.abs(f(B * H, S))

def med_ms(fn, *a):
    jax.block_until_ready(fn(*a))          # compile
    ts = []
    for _ in range(20):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))

flash_ms = med_ms(jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)), q, k, v)
ssd_ms = med_ms(jax.jit(lambda x, b, c, la: ssd_scan(x, b, c, la, chunk=128)[0]), x, b, c, la)
print(f"ssd-vs-flash OK: B={B} S={S} H={H} D={D}: "
      f"flash {flash_ms:.2f} ms, ssd chunked scan {ssd_ms:.2f} ms "
      f"({flash_ms / max(ssd_ms, 1e-9):.2f}x)")
PY
