#!/usr/bin/env python
"""The quickest proof that the program still starts on the chip.

One process drives the main path once through the entry points a user calls,
at the full width of the ``base`` model (hidden 2048, intermediate 5632,
16 query / 8 kv heads x 128, vocab 32000, bf16 compute; random weights from
a seed):

- kernels: flash attention, ``rms_norm``, fused AdamW and the two decode
  kernels against their jnp references at these widths, each through its
  public wrapper, whose compiled text must hold a ``tpu_custom_call`` — a
  wrapper that gave way to its reference fails here instead of passing slowly;
- trainer: ``paddle.optimizer.AdamW`` + ``paddle.jit.TrainStep``, 12 layers,
  batch 3 x seq 2048, four steps on one batch; the loss starts near
  ln(vocab), stays finite and falls;
- server: ``serving.Engine`` (max_batch 16, 256 blocks, buckets 128/256/512),
  ``warmup()``, a handful of requests through ``add_request`` /
  ``run_to_completion``; every request finishes and greedy tokens equal
  ``model.generate`` (or, where bf16 parts them, the two tokens' logits
  differ by less than LOGIT_TOL_ULPS bf16 ulps in a dense forward).

``--chips 4`` runs ONLY the sharded trainer (``fleet.init`` dp 2 x mp 2 on a
mesh over four devices, depth cut to 4 layers, widths not, three steps) and
the same model on one device of the same process, and compares losses,
per-device memory and the collectives in the compiled step.

It needs a TPU: without one it prints a message and exits with code 2.  Any
phase that fails raises and the exit code is non-zero.  Earlier lines of
output are one JSON object each; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
``--rehearse`` walks the same control flow on the CPU at the ``tiny`` widths
with interpret-mode kernels and never prints that line.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time

# the model is bench.py's: build_config("base") on the chip, ("tiny") off it
FULL = dict(
    preset="base", batch=3, seq=2048, steps=4,
    engine=dict(max_batch=16, num_blocks=256, prefill_buckets=(128, 256, 512)),
    n_req=6, prompt=(100, 500), new=(16, 48), ctx=1024,
    mesh_layers=4, mesh_batch=4,
)
TINY = dict(
    preset="tiny", batch=2, seq=128, steps=4,
    engine=dict(max_batch=2, num_blocks=16, prefill_buckets=(128,)),
    n_req=3, prompt=(16, 64), new=(4, 8), ctx=256,
    mesh_layers=2, mesh_batch=4,
)
BF16_TOL = 2e-2          # max |kernel - reference| over max(1, max |reference|)
LOGIT_TOL_ULPS = 4       # bf16 ulps at the top logit's magnitude
LOSS_TOL_4CHIP = 0.05    # |sharded - one-device| loss, bf16 compute
MESH_STEPS = 3           # the sharded step compiles again at its second call


def say(**fields):
    print(json.dumps(fields), flush=True)


def require(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------- kernels --

def model_config(bench, sz, on_chip, fp32_params):
    cfg = bench.build_config(sz["preset"],
                             "bfloat16" if on_chip else "float32")
    if not fp32_params:
        cfg.param_dtype = None      # serving keeps weights in the compute dtype
    return cfg


def check_kernels(jax, bench, sz, on_chip):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import adamw, decode_attention as da
    from paddle_tpu.kernels import flash_attention as fa, rms_norm as rn

    m = model_config(bench, sz, on_chip, True)
    H, HK, D = m.num_attention_heads, m.kv_heads, 128
    B, S, HID, INTER = sz["batch"], sz["seq"], m.hidden_size, \
        m.intermediate_size
    act = jnp.bfloat16 if on_chip else jnp.float32
    itp = not on_chip
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rand(shape, dtype=act):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def run(name, kernel, reference, args, tol):
        compiled = jax.jit(kernel).lower(*args).compile()
        if on_chip:
            require("tpu_custom_call" in compiled.as_text(),
                    f"{name}: the wrapper took its jnp branch at the main "
                    f"path's shape")
        got = compiled(*args)
        want = jax.jit(reference)(*args)
        worst = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            g32, w32 = np.asarray(g, np.float32), np.asarray(w, np.float32)
            require(np.isfinite(g32).all(), f"{name}: non-finite output")
            err = float(np.abs(g32 - w32).max() / max(1.0, np.abs(w32).max()))
            worst = max(worst, err)
        require(worst <= tol, f"{name}: max error {worst} over {tol}")
        say(phase="kernel", name=name, max_err=worst, tol=tol)

    q, k, v = rand((B, S, H, D)), rand((B, S, HK, D)), rand((B, S, HK, D))
    scale = 1.0 / math.sqrt(D)
    run("flash_fwd",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           interpret=itp),
        lambda q, k, v: fa._attention_reference(
            q, jnp.repeat(k, H // HK, axis=2), jnp.repeat(v, H // HK, axis=2),
            True, None, scale),
        (q, k, v), BF16_TOL)

    x, w = rand((B, S, HID)), rand((HID,))
    run("rms_norm",
        lambda x, w: rn.rms_norm(x, w, interpret=itp),
        lambda x, w: rn._rms_norm_ref(x, w, 1e-6), (x, w), BF16_TOL)

    hyper = dict(beta1=0.9, beta2=0.95, epsilon=1e-8, weight_decay=0.1)
    p, g = rand((HID, INTER), jnp.float32), rand((HID, INTER), jnp.float32)
    mm = rand((HID, INTER), jnp.float32) * 0.1
    vv = jnp.square(rand((HID, INTER), jnp.float32)) * 0.01
    lr, step = jnp.float32(3e-4), jnp.int32(3)
    run("adamw_fused",
        lambda *a: adamw.adamw_update(*a, interpret=itp, **hyper)[:3],
        lambda *a: adamw.adamw_reference(*a, **hyper),
        (p, g, mm, vv, lr, step), 1e-5)

    e = sz["engine"]
    SB, NB, C = e["max_batch"], e["num_blocks"], sz["ctx"]
    rng = np.random.default_rng(0)
    lengths = jnp.asarray(rng.integers(1, C, size=(SB,)), jnp.int32)
    qd = rand((SB, 1, H, D))
    run("decode_mmha",
        lambda q, k, v, n: da.masked_multihead_attention(q, k, v, n,
                                                         interpret=itp),
        lambda q, k, v, n: da._decode_reference(q, k, v, n, scale),
        (qd, rand((SB, C, HK, D)), rand((SB, C, HK, D)), lengths), BF16_TOL)
    maxb = C // 128
    require(SB * maxb < NB, "block table does not fit the pool")
    table = jnp.asarray(1 + np.arange(SB * maxb).reshape(SB, maxb), jnp.int32)
    run("paged_decode",
        lambda q, k, v, t, n: da.paged_decode_attention(q, k, v, t, n,
                                                        interpret=itp),
        lambda q, k, v, t, n: da._paged_pool_reference(q, k, v, t, n, scale),
        (qd, rand((NB, HK, 128, D)), rand((NB, HK, 128, D)), table, lengths),
        BF16_TOL)


# ---------------------------------------------------------------- trainer --

def build_trainer(paddle, bench, sz, on_chip, layers=None, batch=None):
    """Seeded model + AdamW + TrainStep + one fixed batch: the recipe of
    ``bench.build_pretrain_step``, which cannot cut depth."""
    import numpy as np

    from paddle_tpu.models import LlamaForCausalLM

    cfg = model_config(bench, sz, on_chip, True)
    cfg.num_hidden_layers = layers or cfg.num_hidden_layers
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m.compute_loss(m(ids), ids), opt)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch or sz["batch"], sz["seq"]))
        .astype(np.int32))
    return model, step, ids, cfg


def run_steps(jax, step, ids, n):
    import numpy as np

    losses, times = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(ids)
        jax.block_until_ready(loss._data)
        times.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss._data)))
    require(all(math.isfinite(x) for x in losses), f"loss not finite {losses}")
    return losses, times


def step_text(bench, step, ids):
    return bench.lower_pretrain_step(step, ids).compile().as_text()


def check_trainer(jax, paddle, bench, sz, on_chip):
    from paddle_tpu.utils.compile_cache import cache_counts

    model, step, ids, cfg = build_trainer(paddle, bench, sz, on_chip)
    n_params = sum(p.size for p in model.parameters())
    before = cache_counts()
    losses, times = run_steps(jax, step, ids, sz["steps"])
    after = cache_counts()
    if on_chip:
        require("tpu_custom_call" in step_text(bench, step, ids),
                "train step compiled without a Pallas kernel")
    vocab_ln = math.log(cfg.vocab_size)
    require(abs(losses[0] - vocab_ln) < 1.0,
            f"first loss {losses[0]} not near ln(vocab) {vocab_ln:.2f}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    tokens = sz["batch"] * sz["seq"]
    warm = sorted(times[1:])[len(times[1:]) // 2]
    say(phase="trainer", params=n_params, layers=cfg.num_hidden_layers,
        batch=sz["batch"], seq=sz["seq"], losses=losses,
        first_step_s=times[0], step_ms=1e3 * warm,
        tokens_per_s=tokens / warm,
        cache_hits=after["hits"] - before["hits"],
        cache_misses=after["misses"] - before["misses"],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


# ----------------------------------------------------------------- server --

def check_server(jax, paddle, bench, sz, on_chip):
    import numpy as np

    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import Engine, GenRequest
    from paddle_tpu.utils.compile_cache import cache_counts

    cfg = model_config(bench, sz, on_chip, False)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    eng = Engine(model, **sz["engine"])
    before = cache_counts()
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    after = cache_counts()
    if on_chip:
        require("tpu_custom_call" in eng.lower_decode(1).compile().as_text(),
                "decode program compiled without a Pallas kernel")

    rng = np.random.default_rng(0)
    reqs = [GenRequest(
        prompt_ids=rng.integers(1, cfg.vocab_size, size=(
            int(rng.integers(*sz["prompt"])),)).astype(np.int32),
        max_new_tokens=int(rng.integers(*sz["new"])),
        request_id=f"smoke-{i}") for i in range(sz["n_req"])]
    t0 = time.perf_counter()
    for r in reqs:
        eng.add_request(r)
    done = {o.request_id: o for o in eng.run_to_completion()}
    dt = time.perf_counter() - t0
    require(len(done) == len(reqs),
            f"{len(done)} of {len(reqs)} requests finished")
    for r in reqs:
        out = done[r.request_id]
        require(len(out.output_ids) == r.max_new_tokens
                and out.finish_reason == "length",
                f"{r.request_id}: {len(out.output_ids)} tokens, "
                f"{out.finish_reason}")
    generated = sum(len(o.output_ids) for o in done.values())

    # the repo's bit-match contract (tests/test_serving.py), on the request
    # with the fewest new tokens: one more program to compile, not six
    r = min(reqs, key=lambda r: r.max_new_tokens)
    got = done[r.request_id].output_ids
    ref = np.asarray(model.generate(
        paddle.to_tensor(r.prompt_ids[None, :]),
        max_new_tokens=r.max_new_tokens)._data)[0, len(r.prompt_ids):].tolist()
    match = {"request": r.request_id, "tokens": len(ref), "equal": got == ref}
    if got != ref:
        t = next(i for i, (a, b) in enumerate(zip(got, ref)) if a != b)
        prefix = np.concatenate([r.prompt_ids, np.asarray(ref[:t], np.int32)])
        logits = np.asarray(
            model(paddle.to_tensor(prefix[None, :]))._data[0, -1], np.float32)
        top = float(np.abs(logits).max())
        tol = LOGIT_TOL_ULPS * max(1.0, top) * 2.0 ** -7
        gap = float(abs(logits[got[t]] - logits[ref[t]]))
        match.update(first_diff=t, logit_gap=gap, tol=tol, top_logit=top)
        require(gap <= tol and logits.max() - min(
            logits[got[t]], logits[ref[t]]) <= tol,
            f"engine and generate part at step {t} by more than bf16: {match}")
    say(phase="server", layers=cfg.num_hidden_layers, requests=len(reqs),
        generated_tokens=generated, warmup_s=warm_s,
        cache_hits=after["hits"] - before["hits"],
        cache_misses=after["misses"] - before["misses"],
        serve_s=dt, tokens_per_s=generated / dt, generate_match=match,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))


# ------------------------------------------------------------- four chips --

def check_four_chips(jax, paddle, bench, sz, on_chip):
    import paddle_tpu.distributed.fleet as fleet

    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 found {len(devices)} device(s)")
    layers, batch = sz["mesh_layers"], sz["mesh_batch"]

    # the comparison: the same seeded model and batch on one device
    model, step, ids, cfg = build_trainer(paddle, bench, sz, on_chip, layers, batch)
    one_losses, _ = run_steps(jax, step, ids, MESH_STEPS)
    one_bytes = (devices[0].memory_stats() or {}).get("bytes_in_use")
    del model, step, ids
    gc.collect()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    model, step, ids, cfg = build_trainer(paddle, bench, sz, on_chip, layers, batch)
    homes = {d.id for p in model.parameters()
             for d in p._data.sharding.device_set}
    require(len(homes) == 4, f"parameters live on devices {sorted(homes)}")
    require(any(not p._data.sharding.is_fully_replicated
                for p in model.parameters()), "no parameter is sharded")
    losses, times = run_steps(jax, step, ids, MESH_STEPS)
    text = step_text(bench, step, ids)
    collectives = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                   for k in ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute")}
    require(collectives["all-reduce"] + collectives["reduce-scatter"] > 0,
            f"dp 2 x mp 2 compiled without a reduction: {collectives}")
    if on_chip:
        require("tpu_custom_call" in text,
                "sharded train step compiled without a Pallas kernel")
    diffs = [abs(a - b) for a, b in zip(losses, one_losses)]
    require(max(diffs) <= LOSS_TOL_4CHIP,
            f"sharded {losses} vs one device {one_losses}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices[:4]]
    if all(b is not None for b in in_use) and one_bytes:
        require(min(in_use) > 0.25 * max(in_use),
                f"uneven shares across devices: {in_use}")
        require(max(in_use) < one_bytes,
                f"a device holds {max(in_use)} bytes, the one-device run "
                f"held {one_bytes}")
    say(phase="four_chips", layers=layers, batch=batch, seq=sz["seq"],
        mesh={"dp": 2, "mp": 2}, losses=losses, one_device_losses=one_losses,
        max_loss_diff=max(diffs), tol=LOSS_TOL_4CHIP, collectives=collectives,
        bytes_in_use=in_use, one_device_bytes_in_use=one_bytes,
        step_s=times)


# -------------------------------------------------------------------- main --

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths, interpret-mode kernels; control "
                         "flow only, never prints the ok line")
    args = ap.parse_args()

    import jax

    platform = jax.devices()[0].platform
    if args.rehearse:
        require(platform == "cpu", "--rehearse is for JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax.devices()[0].platform is "
              f"{platform!r}", file=sys.stderr)
        sys.exit(2)
    on_chip = platform == "tpu"
    sz = FULL if on_chip else TINY

    import bench
    import paddle_tpu as paddle
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jaxlib

    say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=_libtpu_version(), device_kind=jax.devices()[0].device_kind,
        devices=len(jax.devices()), compile_cache=cache_dir,
        cache_enabled=bool(jax.config.jax_enable_compilation_cache))

    t0 = time.perf_counter()
    if args.chips == 4:
        check_four_chips(jax, paddle, bench, sz, on_chip)
    else:
        check_kernels(jax, bench, sz, on_chip)
        check_trainer(jax, paddle, bench, sz, on_chip)
        gc.collect()
        check_server(jax, paddle, bench, sz, on_chip)
    device = {"platform": platform, "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    say(phase="end", seconds=time.perf_counter() - t0)
    if on_chip:
        require(device["count"] == args.chips,
                f"ran with {device['count']} device(s) for --chips "
                f"{args.chips}")
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:
        say(rehearsal="passed", device=device)


def _libtpu_version():
    from importlib import metadata

    for name in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(name)
        except metadata.PackageNotFoundError:
            continue
    return None


if __name__ == "__main__":
    main()
