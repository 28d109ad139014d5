"""Training runner: ``paddle.optimizer`` + ``paddle.jit.TrainStep`` on the
configuration's model, under ``fleet.init`` where the configuration names a
``parallel`` layout, fed a new seeded batch every step.

Set-up holds the first step's loss against the plain reference's, computed on
one device from the same seeded weights before any layout exists (for a
sharded cell this is also the comparison with one chip).

The window: one step is kept in flight.  The host dispatches step ``i``,
makes batch ``i + 1`` while the device runs, then reads the loss of step
``i - 1``.  The clock stops at the host read of the last loss;
``train_tok_s`` is the tokens of all steps dispatched in the window over
that time, all chips of the cell together.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from .. import harness, peaks, stats


def weight_sums(model):
    """``{name: sum of |w|}`` of every parameter, in float32."""
    import jax.numpy as jnp

    return {n: float(jnp.sum(jnp.abs(p._data.astype(jnp.float32))))
            for n, p in model.named_parameters()}


def run(*, cell, config, traffic, seed, window, devices, t_start, rehearse):
    import jax
    import paddle_tpu as paddle

    builder = harness.load_module("models", config["builder"])
    reference = harness.load_module("reference", config["reference"])
    gen = harness.load_module("traffic", traffic["generator"]).make(
        traffic, config["vocab_size"], seed)
    batch = gen.next()

    # the plain reference's loss of the first batch, on ONE device, from the
    # seeded model before any layout is set up
    paddle.seed(seed)
    model = builder.build(config)
    t0 = time.perf_counter()
    want = reference.next_token_loss(
        config, builder.top_weights(model),
        [builder.layer_weights(model, i)
         for i in range(config["num_hidden_layers"])], batch)
    ref_s = time.perf_counter() - t0

    parallel = config.get("parallel")
    if parallel:
        # the same seed builds the same weights under the layout (checked by
        # their sums); the one-device copy goes first, with the reference's
        # programs, or the sharded step does not fit beside them
        import paddle_tpu.distributed.fleet as fleet

        sums = weight_sums(model)
        del model
        gc.collect()
        jax.clear_caches()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(parallel)
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(seed)
        model = builder.build(config)
        homes = {d.id for p in model.parameters()
                 for d in p._data.sharding.device_set}
        if len(homes) != cell["chips"]:
            raise SystemExit(f"parameters live on devices {sorted(homes)}")
        again = weight_sums(model)
        if not all(math.isclose(sums[n], again[n], rel_tol=1e-4, abs_tol=1e-3)
                   for n in sums):
            raise SystemExit("the sharded build's weights differ from the "
                             "one-device build's of the same seed")
    o = config["run"]["optimizer"]
    opt = getattr(paddle.optimizer, o["name"])(
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m.compute_loss(m(ids), ids), opt)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:cell["chips"]]]

    # warm-up: the first steps compile (a sharded step twice) and count as
    # set-up; the first loss is the one held against the reference
    warm = []
    for _ in range(int(traffic.get("warm_steps", 2))):
        warm.append(float(np.asarray(step(paddle.to_tensor(batch))._data)))
        batch = gen.next()
    first_ok = reference.loss_agrees(warm[0], want)
    harness.say(phase="setup", params=n_params,
                params_by_config=peaks.param_count(config), reference_loss=want,
                first_loss=warm[0], rel_diff=abs(warm[0] - want) / abs(want),
                rel_tol=reference.TRAIN_LOSS_REL_TOL, reference_s=ref_s,
                warm_losses=warm, bytes_in_use_before_first_step=in_use)

    losses, done_t = [], []
    pending = None
    setup_s = time.perf_counter() - t_start
    t_begin = window.begin()
    while True:
        with harness.span("train_step"):
            loss = step(paddle.to_tensor(batch))
        with harness.span("make_batch"):
            batch = gen.next()
        if pending is not None:
            with harness.span("read_loss"):
                losses.append(float(np.asarray(pending._data)))
            done_t.append(time.perf_counter())
        pending = loss
        if not window.tick():
            break
    losses.append(float(np.asarray(pending._data)))
    t_end = time.perf_counter()
    done_t.append(t_end)
    compiled = window.end()
    memory_peak = harness.peak_memory(devices[:cell["chips"]])

    steps = len(losses)
    failed = sum(1 for x in losses if not math.isfinite(x))
    step_ms = [1e3 * (b - a) for a, b in zip(done_t, done_t[1:])]
    harness.say(**stats.describe("step_host_ms", step_ms, "ms"), steps=steps,
                first_loss=losses[0], last_loss=losses[-1])
    tokens = steps * gen.tokens_per_step
    return {
        "correct": first_ok and failed == 0
        and all(math.isfinite(x) for x in warm),
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tok_s": tokens / (t_end - t_begin),
                       "setup_s": setup_s},
        "compiled": compiled, "memory_peak": memory_peak,
        "layer_inputs": {"tokens_per_step": gen.tokens_per_step,
                         "seq": gen.seq},
    }
