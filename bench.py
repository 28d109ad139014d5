#!/usr/bin/env python
"""Flagship benchmark: Llama pretraining throughput + MFU on one chip.

Driver contract: prints ONE JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.
``vs_baseline`` is measured MFU / 0.40 — the BASELINE.json north-star gate
("Llama pretraining at >=40% MFU").

Presets:
  tiny   — 2-layer toy model, CPU smoke test (CI / verify skill)
  small  — ~0.16B model, quick chip sanity
  base   — ~0.7B Llama-style model, seq 2048 (DEFAULT on TPU; sized for a
           single 16GB v5e chip incl. fp32 AdamW state)
  ocr    — PP-OCRv4-style DBNet detector training (BASELINE configs[3]: the
           conv-heavy fusion-path recipe); images/s + MFU from XLA cost analysis
  moe    — Qwen2-MoE/DeepSeekMoE-style Llama-MoE training (BASELINE configs[4]);
           tokens/s + MFU from XLA cost analysis (routing makes 6P wrong)
  longctx— the 0.7B model at seq 16384 on ONE chip (streaming flash kernels
           page K/V through VMEM; full remat): the long-context capability row
  decode — KV-cache greedy generation (prefill 512 + 512 new tokens):
           serving-path throughput; vs_baseline = fraction of the
           weight-streaming bandwidth bound
  obs    — observability self-check: MPMD trace-vs-analytic bubble
           cross-check, tracing overhead A/B, serving bit-identity +
           lifecycle completeness, Chrome-trace schema validation

Usage: python bench.py [--preset tiny|small|base|longctx|ocr|moe|decode|obs]
       [--device cpu|tpu] [--steps N] [--batch B] [--seq S]
       [--accum K] [--grad-dtype bfloat16|float32]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


# Published peaks of one chip by PJRT device_kind (Google Cloud TPU
# documentation, the per-generation "system architecture" pages): bf16
# FLOP/s and HBM bytes/s.  Longest matching prefix wins: "TPU v5 lite" must
# hit the v5e row, not the bare "TPU v5" (v5p) row.  A kind with no row is
# an error, never a default.
CHIP_PEAKS = {
    "TPU v4": {"flops": 275e12, "hbm": 1200e9},
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9},
    "TPU v5e": {"flops": 197e12, "hbm": 819e9},
    "TPU v5": {"flops": 459e12, "hbm": 2765e9},
    "TPU v5p": {"flops": 459e12, "hbm": 2765e9},
    "TPU v6 lite": {"flops": 918e12, "hbm": 1640e9},
    "TPU v6e": {"flops": 918e12, "hbm": 1640e9},
}


def model_flops_per_token(cfg, seq_len: int) -> float:
    """Training FLOPs per token: 6 * matmul-params (fwd 2P + bwd 4P) plus
    attention score/value matmuls (2*2*S*dh*h FLOPs fwd, halved by causal
    masking, tripled for fwd+bwd)."""
    h, d = cfg.num_attention_heads, cfg.head_dim
    hk = cfg.kv_heads
    hidden, inter, L, V = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers, cfg.vocab_size
    per_layer = hidden * (h + 2 * hk) * d          # qkv
    per_layer += h * d * hidden                    # o
    per_layer += hidden * 2 * inter + inter * hidden  # gate_up + down
    p_matmul = L * per_layer + hidden * V          # + lm_head
    attn = L * (4 * seq_len * d * h) * 0.5         # causal
    return 6.0 * p_matmul + 3.0 * attn


def build_config(preset: str, dtype: str):
    from paddle_tpu.models import llama_tiny_config
    from paddle_tpu.models.llama import LlamaConfig

    if preset == "tiny":
        return llama_tiny_config(dtype=dtype)
    if preset == "small":
        return LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                           num_hidden_layers=12, num_attention_heads=12,
                           num_key_value_heads=4, max_position_embeddings=2048,
                           dtype=dtype, recompute=True)
    if preset == "base":
        # recompute off (full remat measured ~25% slower); fp32-stored params
        # with bf16 compute = master weights WITHOUT a separate master copy
        # (1.4GB less optimizer memory -> fewer XLA activation spills, MFU
        # 0.583 -> 0.636 measured with batch 3, see PERF.md)
        return LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                           num_hidden_layers=12, num_attention_heads=16,
                           num_key_value_heads=8, max_position_embeddings=2048,
                           dtype=dtype, recompute=False,
                           param_dtype="float32" if dtype != "float32" else None)
    if preset == "longctx":
        # the long-sequence capability headline: the SAME 0.7B model at seq
        # 16384 on one chip (b1) — causal flash keeps attention O(S) memory,
        # remat bounds activations; multi-chip scales further via ring
        # attention over 'sep' (context_parallel.py)
        return LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                           num_hidden_layers=12, num_attention_heads=16,
                           num_key_value_heads=8, max_position_embeddings=16384,
                           dtype=dtype, recompute=True,
                           param_dtype="float32" if dtype != "float32" else None)
    raise ValueError(preset)


DEFAULTS = {  # preset -> (batch, seq, steps)
    "tiny": (4, 128, 5),
    "small": (8, 2048, 10),
    "base": (3, 2048, 10),  # b3 beats b4 by ~2% once spills clear (PERF.md)
    "longctx": (1, 16384, 5),
}


def git_short_sha() -> str:
    """Short SHA of this repo's HEAD, or "" (shared provenance helper —
    also used by scripts/capture_evidence.py)."""
    import os
    import subprocess

    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except (subprocess.SubprocessError, OSError):
        return ""


def _stamp(result: dict) -> dict:
    """Capture-time provenance: UTC timestamp + git SHA."""
    result.setdefault("captured_at",
                      time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    sha = git_short_sha()
    if sha:
        result.setdefault("git_sha", sha)
    return result


def _chip_peaks(jax, on_tpu):
    """``(device_kind, peaks row)``; the row is None on the CPU rehearsal
    path and a ``device_kind`` with no row in CHIP_PEAKS raises."""
    dev_kind = jax.devices()[0].device_kind
    if not on_tpu:
        return dev_kind, None
    matches = [k for k in CHIP_PEAKS if dev_kind.startswith(k)]
    if not matches:
        raise ValueError(
            f"no published peaks for device_kind {dev_kind!r}: add a row to "
            f"bench.CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})")
    return dev_kind, CHIP_PEAKS[max(matches, key=len)]


def _peak_flops(jax, on_tpu):
    dev_kind, row = _chip_peaks(jax, on_tpu)
    return dev_kind, row and row["flops"]


def _hbm_bytes_per_s(jax, on_tpu):
    row = _chip_peaks(jax, on_tpu)[1]
    return row and row["hbm"]


def _step_flops_of(lowered) -> float:
    """FLOPs of a lowered step via the shared cost-analysis helper."""
    from paddle_tpu.utils.xla_cost import flops_of_lowered

    return flops_of_lowered(lowered) or 0.0


def build_pretrain_step(preset: str, on_tpu: bool, batch=None, seq=None,
                        steps=None, accum: int = 1, grad_dtype=None,
                        wus: str = "off", plan=None):
    """Construct the pretrain TrainStep for a tiny/small/base/longctx preset.

    Shared by ``main`` and ``scripts/capture_evidence.py`` so the committed
    cost evidence describes the EXACT program the benchmark measures (same
    seed, hyperparams, input generation). Returns
    ``(step_fn, ids, model, cfg, (batch, seq, steps))``.

    ``wus``: ``"off"`` (default), ``"seq"`` (ZeRO-1 ``shard_update`` over a
    dp mesh spanning all devices, sequential tail all-gather) or
    ``"overlap"`` (same sharded update, params re-gathered at the head of
    the next step in layer buckets behind the forward).

    ``plan``: an ``analysis.autotune.PlanConfig`` (the tuner's output, or
    a deserialized ``--plan`` file).  Explicit arguments win; unset ones
    fall back to the plan's batch/seq/accum/grad_dtype/ZeRO fields, and the
    plan's remat setting maps onto the model config (``recompute`` /
    ``recompute_layers``) — so an A/B against a tuned plan needs no code
    edits.
    """
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    if preset not in DEFAULTS:
        raise ValueError(f"not a pretrain preset: {preset!r} "
                         f"(choose from {sorted(DEFAULTS)})")
    if plan is not None:
        batch = batch or plan.batch
        seq = seq or plan.seq
        if accum == 1:
            accum = plan.accum
        grad_dtype = grad_dtype or plan.grad_dtype
        if wus == "off":
            wus = plan.wus
    dtype = "bfloat16" if on_tpu else "float32"
    cfg = build_config(preset, dtype)
    if plan is not None and plan.remat != "off":
        if plan.remat == "full":
            cfg.recompute = True
        elif plan.remat_layers is not None:
            cfg.recompute_layers = plan.remat_layers
    d_batch, d_seq, d_steps = DEFAULTS[preset]
    batch = batch or d_batch
    seq = min(seq or d_seq, cfg.max_position_embeddings)
    steps = steps or d_steps

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 parameters=model.parameters())
    if wus and wus != "off":
        import jax

        import paddle_tpu.distributed as dist

        mesh = dist.ProcessMesh(np.arange(jax.device_count()), ["dp"])
        opt.shard_update(mesh, overlap_gather=(wus == "overlap"))

    def loss_fn(m, ids):
        return m.compute_loss(m(ids), ids)

    step_fn = paddle.jit.TrainStep(model, loss_fn, opt,
                                   accumulate_steps=accum,
                                   grad_dtype=grad_dtype)
    rng = np.random.default_rng(0)
    shape = (accum, batch, seq) if accum > 1 else (batch, seq)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=shape).astype(np.int32))
    return step_fn, ids, model, cfg, (batch, seq, steps)


def lower_pretrain_step(step_fn, *example_args, lr: float = 3e-4):
    """Lower (without executing) a TrainStep's jitted program for the given
    example tensors — the object whose ``compile()`` yields the cost/memory
    analyses. The ONE place the positional ``_jitted.lower`` incantation
    lives (used by every preset here and by scripts/capture_evidence.py)."""
    import jax.numpy as jnp

    from paddle_tpu.framework import random as rnd

    return step_fn._jitted.lower(
        step_fn._params, step_fn._buffers, step_fn._opt_state,
        jnp.asarray(lr, jnp.float32), jnp.asarray(1, jnp.int32),
        rnd.next_key(), tuple(a._data for a in example_args))


def _bytes_fields(lowered, audit=False, label=""):
    """``bytes_per_step`` fields for a BENCH line, from the compiled step's
    cost analysis (fallback: HLO-text fusion audit).  With ``audit=True``
    the ranked per-fusion report goes to stderr (stdout stays one JSON
    line)."""
    import sys

    from paddle_tpu.profiler.fusion_audit import audit_lowered, bytes_per_step

    fields = {}
    try:
        b = bytes_per_step(lowered=lowered)
    except Exception:
        b = None
    if b:
        fields["bytes_per_step"] = float(b)
        fields["bytes_source"] = "xla_cost"
    if audit:
        a = audit_lowered(lowered)
        if a is not None:
            if "bytes_per_step" not in fields and a.total_bytes:
                fields["bytes_per_step"] = float(a.total_bytes)
                fields["bytes_source"] = "hlo_audit"
            fields["audit_pallas_candidates"] = len(a.pallas_candidates())
            print(f"== fusion audit{' (' + label + ')' if label else ''} ==",
                  file=sys.stderr)
            print(a.report(), file=sys.stderr)
    return fields


def _lint_fields(lowered, lint=False, label="", expected=()):
    """``lint_findings``/``lint_codes`` fields for a BENCH line from the
    sharding & communication static analyzer (``paddle_tpu.analysis``):
    donation misses + every compiled collective vs the expected set.  The
    ranked findings report goes to stderr; stdout stays one JSON line."""
    import sys

    if not lint:
        return {}
    from paddle_tpu.analysis import lint_lowered

    try:
        rep = lint_lowered(lowered, expected=expected)
    except Exception as e:  # lint must never break the BENCH contract
        return {"lint_error": repr(e)}
    print(f"== sharding lint{' (' + label + ')' if label else ''} ==",
          file=sys.stderr)
    print(rep.report(), file=sys.stderr)
    return {"lint_findings": len(rep), "lint_codes": rep.counts()}


def _kernel_lint_fields(lint=False, preset=""):
    """``kernel_lint_*`` fields for a BENCH line from the Pallas kernel
    verifier (``paddle_tpu.analysis.pallas_lint``) over the registered
    kernels this preset exercises: finding counts per ``krn-*`` code plus
    the modeled per-kernel resident-VMEM bytes (reported like liveness's
    peak).  The per-kernel summary goes to stderr; stdout stays one JSON
    line."""
    import sys

    if not lint:
        return {}
    from paddle_tpu.kernels import registry as kernel_registry

    try:
        kernel_registry.load_all()
        reports = kernel_registry.check_all(presets=preset or None)
    except Exception as e:  # kernel lint must never break the BENCH contract
        return {"kernel_lint_error": repr(e)}
    total, codes, vmem = 0, {}, {}
    print(f"== kernel lint{' (' + preset + ')' if preset else ''} ==",
          file=sys.stderr)
    for name, rep in sorted(reports.items()):
        total += len(rep)
        for c, n in rep.counts().items():
            codes[c] = codes.get(c, 0) + n
        vmem[name] = int(rep.meta.get("kernel_vmem_bytes", 0))
        print(f"  {name}: {len(rep)} finding(s), "
              f"vmem {vmem[name] / 1e6:.3f} MB", file=sys.stderr)
        if rep:
            print(rep.report(), file=sys.stderr)
    return {"kernel_lint_findings": total, "kernel_lint_codes": codes,
            "kernel_lint_kernels": len(reports),
            "kernel_vmem_bytes": vmem}


def _mem_fields(lowered, mem=False, label="", hbm_budget=None):
    """``peak_bytes``/``mem_findings`` fields for a BENCH line from the
    liveness-based memory lint (``paddle_tpu.analysis.memory_lint``):
    per-device peak-resident bytes cross-validated against XLA's
    ``memory_analysis()``, plus donation/remat advisors.  The ranked
    findings report goes to stderr; stdout stays one JSON line."""
    import sys

    if not mem and hbm_budget is None:
        return {}
    from paddle_tpu.analysis import lint_memory

    try:
        rep = lint_memory(lowered.compile(), hbm_budget=hbm_budget)
    except Exception as e:  # mem lint must never break the BENCH contract
        return {"mem_error": repr(e)}
    print(f"== memory lint{' (' + label + ')' if label else ''} ==",
          file=sys.stderr)
    print(rep.report(), file=sys.stderr)
    fields = {"mem_findings": len(rep), "mem_codes": rep.counts()}
    for k in ("peak_bytes", "xla_peak_bytes", "peak_agreement"):
        if k in rep.meta:
            fields[k] = rep.meta[k]
    return fields


def _overlap_fields(lowered, overlap=False, label=""):
    """``overlap_*`` fields for a BENCH line from the collective-overlap
    analyzer (``paddle_tpu.analysis.overlap``): every collective in the
    scheduled HLO classified as hidden-behind-compute or exposed
    (``comm-exposed``).  The ranked findings report goes to stderr; stdout
    stays one JSON line."""
    import sys

    if not overlap:
        return {}
    from paddle_tpu.analysis import overlap_lowered

    try:
        rep = overlap_lowered(lowered)
    except Exception as e:  # overlap lint must never break the BENCH contract
        return {"overlap_error": repr(e)}
    print(f"== overlap lint{' (' + label + ')' if label else ''} ==",
          file=sys.stderr)
    print(rep.report(), file=sys.stderr)
    return {
        "overlap_findings": len(rep),
        "overlap_collectives": rep.meta["overlap_collectives"],
        "overlap_collective_bytes": rep.meta["overlap_collective_bytes"],
        "overlap_exposed_bytes": rep.meta["overlap_exposed_bytes"],
        "overlap_exposed_fraction": round(
            rep.meta["overlap_exposed_fraction"], 4),
        "overlap_exposed_by_kind": rep.meta["overlap_exposed_by_kind"],
    }


def _bench_decode(jax, paddle, backend, on_tpu, args):
    """Serving path: KV-cache greedy decode throughput (new tokens/s).

    Exercises the incremental ``use_cache`` attention + decode-MHA Pallas
    kernel (reference ``masked_multihead_attention`` /
    ``block_multi_head_attention`` role).  Decode is bandwidth-bound (reads
    every weight per token), so the companion figure is the % of the
    weight-streaming bound: tokens/s * param_bytes / HBM bandwidth."""
    import numpy as np

    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig

    paddle.seed(0)
    dtype = "bfloat16" if on_tpu else "float32"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                          num_hidden_layers=12, num_attention_heads=16,
                          num_key_value_heads=8, max_position_embeddings=2048,
                          dtype=dtype)
        batch, prompt, new = (args.batch or 8), 512, 512
    else:
        from paddle_tpu.models import llama_tiny_config

        cfg = llama_tiny_config(dtype=dtype)
        batch, prompt, new = (args.batch or 2), 16, 16
    model = LlamaForCausalLM(cfg)
    n_params = sum(p.size for p in model.parameters())
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=(batch, prompt)).astype(np.int32))

    out = model.generate(ids, max_new_tokens=new)   # compile + warm
    _ = np.asarray(out._data[:, -1])                # host read = sync
    t0 = time.perf_counter()
    reps = 3 if on_tpu else 1
    for _i in range(reps):
        out = model.generate(ids, max_new_tokens=new)
    _ = np.asarray(out._data[:, -1])
    dt = (time.perf_counter() - t0) / reps

    new_tokens_per_sec = batch * new / dt
    dev_kind, _ = _peak_flops(jax, on_tpu)
    # weight-streaming bound: each decode step reads all param bytes once
    param_bytes = n_params * (2 if dtype == "bfloat16" else 4)
    hbm = _hbm_bytes_per_s(jax, on_tpu)
    steps_per_sec = new / dt
    frac_bound = (steps_per_sec * param_bytes / hbm) if hbm else 0.0
    # bytes/step: whole generate program / new tokens (cached jitted fn)
    bytes_fields = {}
    try:
        from paddle_tpu.framework import random as rnd

        sig, fn = next(iter(model._generate_fns.items()))
        params = {n: p._data for n, p in model.named_parameters()}
        buffers = {n: b._data for n, b in model.named_buffers()}
        lowered = fn.lower(params, buffers, out._data[:, :prompt], rnd.next_key())
        bf = _bytes_fields(lowered, audit=getattr(args, "audit", False),
                           label="decode")
        if bf.get("bytes_per_step"):
            bf["bytes_per_step"] = bf["bytes_per_step"] / new  # per new token
        bf.update(_lint_fields(lowered, getattr(args, "lint", False),
                               label="decode"))
        bf.update(_mem_fields(lowered, getattr(args, "mem", False),
                              label="decode",
                              hbm_budget=getattr(args, "hbm_budget", None)))
        bytes_fields = bf
    except Exception:
        bytes_fields = {"bytes_per_step": float(param_bytes),
                        "bytes_source": "analytic_weight_stream"}
    return {
        **bytes_fields,
        "metric": "llama_decode_new_tokens_per_sec",
        "value": round(new_tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(frac_bound, 4),   # fraction of weight-stream bound
        "mfu": 0.0,
        "device": dev_kind,
        "backend": backend,
        "preset": "decode",
        "params": n_params,
        "batch": batch,
        "prompt_len": prompt,
        "new_tokens": new,
        "decode_ms_per_step": round(1000 * dt / new, 3),
    }


def _bench_fuse(jax, paddle, backend, on_tpu, preset, args):
    """``--fuse`` A/B: the fusion transformer's substituted program vs stock,
    in ONE process (pretrain presets).

    Protocol: audit the stock step's optimized HLO, run the transformer pass
    (``analysis.fusion_transform.plan_transform`` — interpret bit-identity +
    registry admission per site, audit byte model per candidate), then run
    the SAME preset three times: stock, substituted (``plan.apply()``),
    stock again.  Per-step losses must be bit-identical across all three
    legs — the fused-sandwiched-by-stock order proves substitution both
    ways round in one process (no state leaks in either direction).

    Byte accounting: the fused leg's ``bytes_per_step`` is the stock audit
    total minus the verified, admitted region savings
    (``bytes_source: "hlo_audit_model"``) — a ``pallas_call`` is a custom
    call opaque to the textual audit, so the credit comes from the same
    analytic-minimum model that flagged the regions.  ``vs_baseline`` is
    the measured drop over the >=20% acceptance bar."""
    import numpy as np

    from paddle_tpu.analysis.fusion_transform import plan_transform
    from paddle_tpu.profiler.fusion_audit import audit_lowered

    step_fn, ids, model, cfg, (batch, seq, steps) = build_pretrain_step(
        preset, on_tpu, batch=args.batch, seq=args.seq, steps=args.steps,
        accum=max(1, args.accum), grad_dtype=args.grad_dtype)
    n_params = sum(p.size for p in model.parameters())
    lowered = lower_pretrain_step(step_fn, ids)
    audit = audit_lowered(lowered)
    if audit is None or not audit.total_bytes:
        raise RuntimeError("--fuse: could not audit the stock step's HLO")
    stock_total = int(audit.total_bytes)
    # on the chip every site is first compiled at this preset's widths: what
    # the compiler refuses is a fuse-admission-rejected, not a crash mid-run
    from paddle_tpu.kernels import emit

    plan = plan_transform(audit, shapes=emit.llama_site_shapes(
        cfg, batch * seq) if on_tpu else None)
    print(f"== fusion transform ({preset}) ==", file=sys.stderr)
    print(plan.describe(), file=sys.stderr)

    def run_leg(activation):
        import contextlib

        ctx = (contextlib.nullcontext() if activation is None
               else emit.activate(activation))
        with ctx:
            # fresh build per leg (same seed -> identical params); tracing
            # happens inside the scope so the seams see the activation table
            sf, pids, _m, _c, _shape = build_pretrain_step(
                preset, on_tpu, batch=args.batch, seq=args.seq,
                steps=args.steps, accum=max(1, args.accum),
                grad_dtype=args.grad_dtype)
            losses = []
            loss = sf(pids)
            losses.append(np.asarray(loss._data).tobytes())
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = sf(pids)
                losses.append(np.asarray(loss._data).tobytes())
            dt = time.perf_counter() - t0
        return losses, dt

    losses_stock, dt_stock = run_leg(None)
    losses_fused, dt_fused = run_leg(plan.activation())
    losses_stock2, _ = run_leg(None)
    bitident = (losses_stock == losses_fused == losses_stock2)

    fused_total = plan.fused_bytes(stock_total)
    drop = (stock_total - fused_total) / stock_total
    dev_kind, _ = _peak_flops(jax, on_tpu)
    rej_codes = {}
    for r in plan.rejected:
        rej_codes[r["code"]] = rej_codes.get(r["code"], 0) + 1
    return {
        "metric": f"llama_{preset}_fuse_bytes_drop_frac",
        "value": round(drop, 4),
        "unit": "frac_of_stock_bytes",
        "vs_baseline": round(drop / 0.20, 4),
        "mfu": 0.0,
        "device": dev_kind,
        "backend": backend,
        "preset": preset,
        "params": n_params,
        "batch": batch,
        "seq_len": seq,
        "steps": steps,
        "fuse_loss_bitident": bool(bitident),
        "fuse_candidates": plan.candidates,
        "fuse_accepted": len(plan.accepted),
        "fuse_rejected": len(plan.rejected),
        "fuse_sites": plan.sites(),
        "fuse_reject_codes": rej_codes,
        "fuse_bytes_saved": plan.bytes_saved,
        "bytes_per_step_stock": float(stock_total),
        "bytes_per_step_fused": float(fused_total),
        "bytes_per_step": float(fused_total),
        "bytes_source": "hlo_audit_model",
        "stock_step_time_ms": round(1000 * dt_stock / steps, 2),
        "fused_step_time_ms": round(1000 * dt_fused / steps, 2),
    }


def _bench_ocr(jax, paddle, backend, on_tpu, args):
    """DBNet detector train step: images/s; FLOPs from XLA's cost analysis of
    the compiled program (convs don't have a tidy closed form like 6P)."""
    import numpy as np

    from paddle_tpu.models.ocr import db_loss, ocr_det_base, ocr_det_tiny

    paddle.seed(0)
    model = ocr_det_base() if on_tpu else ocr_det_tiny()
    size = 640 if on_tpu else 64
    batch = args.batch or (32 if on_tpu else 2)  # b32 measured 1.35x faster/img than b8
    steps = args.steps or (10 if on_tpu else 3)
    n_params = sum(p.size for p in model.parameters())
    opt = paddle.optimizer.Momentum(learning_rate=1e-3, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(m, img, gt):
        return db_loss(m(img), gt)

    step_fn = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    img = paddle.to_tensor(rng.normal(size=(batch, 3, size, size)).astype(np.float32))
    gt = paddle.to_tensor((rng.random(size=(batch, 1, size, size)) < 0.2).astype(np.float32))

    import time as _time

    loss = step_fn(img, gt)
    first_loss = float(np.asarray(loss._data))  # host read = true sync
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = step_fn(img, gt)
    last_loss = float(np.asarray(loss._data))
    dt = _time.perf_counter() - t0

    # FLOPs of one whole train step from the compiled executable
    lowered = lower_pretrain_step(step_fn, img, gt, lr=1e-3)
    from paddle_tpu.utils.xla_cost import cost_of_lowered

    cost = cost_of_lowered(lowered) or {}
    step_flops = float(cost.get("flops") or 0.0)
    step_bytes = float(cost.get("bytes accessed") or 0.0)

    images_per_sec = batch * steps / dt
    dev_kind, peak = _peak_flops(jax, on_tpu)
    mfu = (step_flops * steps / dt / peak) if peak and step_flops else 0.0
    # conv nets at DBNet scale are bandwidth-bound (PERF.md r3: MFU 0.019 is
    # the wrong lens) — the honest denominator is the roofline over the
    # compiled executable's post-fusion HBM traffic
    hbm = _hbm_bytes_per_s(jax, on_tpu)
    bound_img_s = (batch * hbm / step_bytes) if (hbm and step_bytes) else 0.0
    vs_bound = images_per_sec / bound_img_s if bound_img_s else 0.0
    bytes_fields = _bytes_fields(lowered, audit=getattr(args, "audit", False),
                                 label="ocr")
    bytes_fields.update(_lint_fields(lowered, getattr(args, "lint", False),
                                     label="ocr"))
    bytes_fields.update(_mem_fields(lowered, getattr(args, "mem", False),
                                    label="ocr",
                                    hbm_budget=getattr(args, "hbm_budget", None)))
    return {
        **bytes_fields,
        "metric": "ocr_det_train_images_per_sec",
        "value": round(images_per_sec, 2),
        "unit": "images/s",
        "vs_baseline": round(vs_bound, 4) if bound_img_s else (
            round(mfu / 0.40, 4) if peak else 0.0),
        "mfu": round(mfu, 4),
        "vs_bound": round(vs_bound, 4),
        "bound_images_per_sec": round(bound_img_s, 2),
        "step_bytes_accessed": step_bytes,
        "device": dev_kind,
        "backend": backend,
        "preset": "ocr",
        "params": n_params,
        "batch": batch,
        "image_size": size,
        "steps": steps,
        "step_time_ms": round(1000 * dt / steps, 2),
        "first_loss": round(first_loss, 4),
        "last_loss": round(last_loss, 4),
        "step_flops": step_flops,
    }


def build_moe_step(on_tpu: bool, batch=None, seq=None, steps=None,
                   accum: int = 1):
    """Construct the MoE TrainStep (configs[4] shape).  Mirrors
    ``build_pretrain_step``'s contract so the tuner can sweep the moe
    preset too; shared by ``_bench_moe`` and the autotune tests."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig

    paddle.seed(0)
    dtype = "bfloat16" if on_tpu else "float32"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=1408,
                          num_hidden_layers=12, num_attention_heads=16,
                          num_key_value_heads=8, max_position_embeddings=2048,
                          dtype=dtype, moe_num_experts=8, moe_top_k=2)
        batch, seq, steps = (batch or 4), (seq or 2048), (steps or 10)
    else:
        from paddle_tpu.models import llama_tiny_config

        cfg = llama_tiny_config(dtype=dtype, moe_num_experts=4, moe_top_k=2)
        batch, seq, steps = (batch or 2), (seq or 128), (steps or 3)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters())

    def loss_fn(m, ids):
        return m.compute_loss(m(ids), ids)

    step_fn = paddle.jit.TrainStep(model, loss_fn, opt, accumulate_steps=accum)
    rng = np.random.default_rng(0)
    shape = (accum, batch, seq) if accum > 1 else (batch, seq)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=shape).astype(np.int32))
    return step_fn, ids, model, cfg, (batch, seq, steps)


def _bench_moe(jax, paddle, backend, on_tpu, args):
    """Llama-MoE train step (configs[4] shape: few dense layers' worth of
    active params routed over many experts).  FLOPs from XLA cost analysis —
    top-k routing makes the dense 6P closed form wrong."""
    import numpy as np

    step_fn, ids, model, cfg, (batch, seq, steps) = build_moe_step(
        on_tpu, batch=args.batch, seq=args.seq, steps=args.steps)
    n_params = sum(p.size for p in model.parameters())

    loss = step_fn(ids)
    first_loss = float(np.asarray(loss._data))  # host read = true sync
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_fn(ids)
    last_loss = float(np.asarray(loss._data))
    dt = time.perf_counter() - t0

    lowered = lower_pretrain_step(step_fn, ids)
    step_flops = _step_flops_of(lowered)
    bytes_fields = _bytes_fields(lowered, audit=getattr(args, "audit", False),
                                 label="moe")
    bytes_fields.update(_lint_fields(lowered, getattr(args, "lint", False),
                                     label="moe"))
    bytes_fields.update(_mem_fields(lowered, getattr(args, "mem", False),
                                    label="moe",
                                    hbm_budget=getattr(args, "hbm_budget", None)))

    tokens_per_sec = batch * seq * steps / dt
    dev_kind, peak = _peak_flops(jax, on_tpu)
    mfu = (step_flops * steps / dt / peak) if peak and step_flops else 0.0
    return {
        **bytes_fields,
        "metric": "llama_moe_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4) if peak else 0.0,
        "mfu": round(mfu, 4),
        "device": dev_kind,
        "backend": backend,
        "preset": "moe",
        "params": n_params,
        "experts": cfg.moe_num_experts,
        "top_k": cfg.moe_top_k,
        "batch": batch,
        "seq_len": seq,
        "steps": steps,
        "step_time_ms": round(1000 * dt / steps, 2),
        "first_loss": round(first_loss, 4),
        "last_loss": round(last_loss, 4),
        "step_flops": step_flops,
    }


def _bench_obs(jax, paddle, backend, on_tpu, args):
    """Observability self-check preset (``scripts/obs_gate.sh``): one
    BENCH line proving the obs layer's three contracts.

    1. **Bubble cross-check** — the MPMD op-span timeline's per-stage idle
       fraction agrees with ``schedule_lint.dag_bubble_fraction`` priced
       with the trace's own cost table (``value`` = rel err; a
       mis-ticked span blows it).
    2. **Tracing never perturbs values, and costs < 5%** — a tiny-preset
       A/B (traced vs untraced pretrain steps, min-of-reps) plus a
       serving trace replayed tracing-off/tracing-on with bit-identical
       outputs and a complete per-request lifecycle chain (exactly one
       begin and one end per request id).
    3. **Exportable** — the Chrome trace_event doc passes
       ``obs.validate_chrome_trace``.
    """
    import numpy as np

    from paddle_tpu import obs
    from paddle_tpu.distributed.parallel.mpmd import mpmd_bubble_crosscheck
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.serving import Engine, GenRequest
    from paddle_tpu.serving.router import Router

    # -- 1. trace-vs-analytic MPMD bubble (pp2, small dims: gate budget) --
    cc = mpmd_bubble_crosscheck(n_stages=2, n_micro=4, dim=256, mb=32,
                                steps=5, schedule="ZB")

    # -- 2a. overhead A/B on the tiny pretrain preset ---------------------
    step_fn, ids, _model, _cfg, (_b, _s, _st) = build_pretrain_step(
        "tiny", on_tpu, steps=1)
    step_fn(ids)                        # compile
    n_steps, reps = 6, 3

    def timed():
        t0 = time.perf_counter()
        for _ in range(n_steps):
            loss = step_fn(ids)
        float(np.asarray(loss._data))   # host read = true sync
        return time.perf_counter() - t0

    was_on = obs.trace_enabled()
    t_off, t_on = [], []
    for _ in range(reps):               # interleave: drift cancels
        obs.disable_tracing()
        t_off.append(timed())
        obs.enable_tracing(clear=False)
        t_on.append(timed())
    if not was_on:
        obs.disable_tracing()
    overhead = min(t_on) / max(min(t_off), 1e-9) - 1.0

    # -- 2b. serving bit-identity + lifecycle completeness ----------------
    paddle.seed(0)
    cfg = llama_tiny_config(dtype="float32", max_position_embeddings=1024)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, size=96).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        1, cfg.vocab_size, size=8).astype(np.int32)]) for _ in range(6)]

    def serve_once():
        """Six shared-prefix requests through a one-replica router:
        ``(outputs by request id, the run's registry snapshot)``."""
        obs.reset_metrics()
        eng = Engine(model, max_batch=2, num_blocks=24,
                     prefill_buckets=(128, 256))
        eng.warmup()
        r = Router()
        r.add_replica(eng)
        for p in prompts:
            r.submit(GenRequest(prompt_ids=p, max_new_tokens=8))
        outs = {o.request_id: list(o.output_ids)
                for o in r.run_to_completion()}
        return outs, obs.registry().snapshot()

    obs.disable_tracing()
    outs_off, _ = serve_once()
    tr = obs.enable_tracing()
    outs_on, metrics_on = serve_once()
    events = tr.events()
    identical = outs_on == outs_off
    rids = set(outs_on)
    begins = {e["id"] for e in events
              if e.get("ph") == "b" and e.get("cat") == "serve.request"}
    ends = {e["id"] for e in events
            if e.get("ph") == "e" and e.get("cat") == "serve.request"}
    lifecycle_complete = rids <= begins and rids <= ends
    dup_free = (
        len([e for e in events if e.get("ph") == "b"
             and e.get("cat") == "serve.request"]) == len(begins)
        and len([e for e in events if e.get("ph") == "e"
                 and e.get("cat") == "serve.request"]) == len(ends))

    # -- 3. export schema --------------------------------------------------
    doc = tr.to_chrome_trace(metrics=obs.registry().snapshot())
    problems = obs.validate_chrome_trace(doc)
    if not was_on and not args.otrace:
        obs.disable_tracing()

    gap_snap = metrics_on.get("serve.decode_gap_ms{replica=0}", {})
    dev_kind, _ = _peak_flops(jax, on_tpu)
    return {
        "metric": "obs_crosscheck_rel_err",
        "value": round(cc["rel_err"], 4),
        "unit": "rel_err",
        "trace_bubble": round(cc["trace_bubble"], 4),
        "analytic_bubble": round(cc["analytic_bubble"], 4),
        "n_op_spans": int(cc["n_op_spans"]),
        "overhead_frac": round(overhead, 4),
        "outputs_bit_identical": identical,
        "lifecycle_complete": bool(lifecycle_complete and dup_free),
        "trace_valid": not problems,
        "trace_problems": problems[:5],
        "metrics_families": len(metrics_on),
        "decode_gap_p99_ms": round(gap_snap.get("p99", 0.0), 3),
        "preset": "obs",
        "device": dev_kind,
        "backend": backend,
        "mfu": 0.0,
        "vs_baseline": 0.0,
    }


def _bench_pp(jax, backend, on_tpu, args):
    """``--pp N`` A/B: the lockstep SPMD pipeline vs the MPMD per-stage-
    program runtime (``distributed.parallel.mpmd``) on the same toy model
    and M/2M-differencing protocol, in ONE process — measured bubble and
    tok/s per runtime in one BENCH line.

    The spmd leg runs ``measure_bubble_fraction`` (the compiled lockstep
    1F1B scan: every stage executes the full masked round body, R =
    M + 2(S-1) rounds); the mpmd leg runs ``measure_mpmd_bubble`` with
    ``--pp-schedule`` (1f1b or zb), where stages idle instead of running
    masked rounds, so per-step work is M round-equivalents."""
    from paddle_tpu.analysis.schedule_lint import measure_bubble_fraction
    from paddle_tpu.distributed.parallel.mpmd import measure_mpmd_bubble

    S = args.pp
    M = max(args.accum, 2 * S)
    dim, mb = 512, 64
    runtimes = (("spmd", "mpmd") if args.pp_runtime == "both"
                else (args.pp_runtime,))
    result = {
        "metric": f"pp{S}_pipeline_tokens_per_sec",
        "unit": "tokens/s",
        "device": _peak_flops(jax, on_tpu)[0], "backend": backend,
        "pp": S, "n_micro": M, "pp_schedule": args.pp_schedule,
        "pp_runtime": args.pp_runtime,
    }
    tok = M * mb
    for rt in runtimes:
        if rt == "spmd":
            # lockstep measurement harness covers the 1F1B training round
            r = measure_bubble_fraction(S, M, dim=dim, mb=mb,
                                        schedule="1F1B")
            result["spmd_bubble_measured"] = round(r["measured"], 4)
            result["spmd_bubble_predicted"] = round(r["predicted"], 4)
            result["spmd_tok_s"] = round(tok / r["t_lo_s"], 2)
        else:
            r = measure_mpmd_bubble(S, M, dim=dim, mb=mb,
                                    schedule=args.pp_schedule)
            result["mpmd_bubble_measured"] = round(r["measured"], 4)
            result["mpmd_lockstep_predicted"] = round(
                r["lockstep_predicted"], 4)
            result["mpmd_tok_s"] = round(tok / r["t_lo_s"], 2)
            result["mpmd_transfers_posted"] = int(r["transfers_posted"])
            result["mpmd_transfer_bytes"] = int(r["transfer_bytes"])
            if args.otrace:
                # trace-vs-analytic bubble cross-check: the op spans land
                # in the live tracer (so the --otrace dump holds the
                # timeline the numbers came from)
                from paddle_tpu.distributed.parallel.mpmd import \
                    mpmd_bubble_crosscheck

                cc = mpmd_bubble_crosscheck(S, M, dim=dim, mb=mb, steps=5,
                                            schedule=args.pp_schedule)
                result["trace_bubble"] = round(cc["trace_bubble"], 4)
                result["dag_bubble_analytic"] = round(
                    cc["analytic_bubble"], 4)
                result["trace_vs_analytic_rel_err"] = round(
                    cc["rel_err"], 4)
                result["trace_op_spans"] = int(cc["n_op_spans"])
    if "spmd_tok_s" in result and "mpmd_tok_s" in result:
        result["mpmd_vs_spmd_tok_s"] = round(
            result["mpmd_tok_s"] / max(result["spmd_tok_s"], 1e-9), 4)
    result["value"] = result.get("mpmd_tok_s",
                                 result.get("spmd_tok_s", 0.0))
    result["vs_baseline"] = result.get("mpmd_vs_spmd_tok_s", 0.0)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=None, choices=["tiny", "small", "base", "longctx", "ocr", "moe", "decode", "obs"])
    ap.add_argument("--device", default=None, choices=["cpu", "tpu"])
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation micro-batches per optimizer "
                         "update (pretrain presets; one AdamW pass per "
                         "accum micro-steps — the bandwidth-bound optimizer "
                         "cost amortizes)")
    ap.add_argument("--grad-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="gradient (and accumulator) dtype; bfloat16 halves "
                         "grad HBM traffic and the accumulator footprint "
                         "(the loss-scaling-free TPU recipe)")
    ap.add_argument("--audit", action="store_true",
                    help="print the per-fusion bytes-accessed-vs-minimum "
                         "report (profiler.fusion_audit) to stderr; stdout "
                         "stays one JSON line")
    ap.add_argument("--lint", action="store_true",
                    help="run the sharding & communication static analyzer "
                         "(paddle_tpu.analysis) on the compiled step: "
                         "donation misses + unintended collectives; adds "
                         "lint_findings/lint_codes to the BENCH line, ranked "
                         "report to stderr")
    ap.add_argument("--mem", action="store_true",
                    help="run the liveness-based memory lint "
                         "(paddle_tpu.analysis.memory_lint) on the compiled "
                         "step: peak-resident bytes cross-validated against "
                         "XLA's memory_analysis(), donation/remat advisors; "
                         "adds peak_bytes/mem_findings/mem_codes to the "
                         "BENCH line, ranked report to stderr")
    ap.add_argument("--hbm-budget", type=int, default=None,
                    help="per-device HBM budget in bytes; implies --mem and "
                         "adds the mem-over-budget check")
    ap.add_argument("--overlap", action="store_true",
                    help="run the collective-overlap analyzer "
                         "(paddle_tpu.analysis.overlap) on the compiled "
                         "step: each collective classified as hidden-behind-"
                         "compute or comm-exposed; adds overlap_* fields to "
                         "the BENCH line, ranked report to stderr")
    ap.add_argument("--wus", default="off",
                    choices=["off", "seq", "overlap"],
                    help="ZeRO-1 weight-update sharding for the pretrain "
                         "presets: 'seq' = shard_update with the sequential "
                         "tail all-gather, 'overlap' = head-of-next-step "
                         "bucketed gather behind the forward; on CPU forces "
                         "an 8-device host mesh")
    ap.add_argument("--fuse", action="store_true",
                    help="pretrain presets: run the fusion-transformer A/B "
                         "(analysis.fusion_transform over the audit's "
                         "pallas-candidate worklist) — stock, substituted, "
                         "stock again in one process with bit-identical "
                         "per-step losses required; reports the audited "
                         "bytes_per_step drop (>=20% bar in vs_baseline)")
    ap.add_argument("--audit-only", action="store_true",
                    help="pretrain presets: lower + compile + cost-analyse "
                         "the step but skip the timed run (bytes_per_step "
                         "without executing — lets the bytes gate cover "
                         "presets too slow to run on the CPU proxy)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="run a serialized PlanConfig JSON (see "
                         "paddle_tpu.analysis.autotune) instead of the named "
                         "preset defaults; explicit --batch/--seq/--accum/"
                         "--wus flags still win over plan fields")
    ap.add_argument("--tune", action="store_true",
                    help="run the static auto-parallel sweep "
                         "(paddle_tpu.analysis.autotune) over the preset's "
                         "candidate grid, print the ranked table to stderr, "
                         "adopt the chosen plan for the run, and add tune_* "
                         "fields to the BENCH line")
    ap.add_argument("--tune-out", default=None, metavar="PATH",
                    help="with --tune: write the chosen plan as JSON here "
                         "(replayable via --plan)")
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline-stage count (>= 2) for the pipeline-"
                         "runtime A/B: measure bubble fraction and tok/s of "
                         "the lockstep SPMD schedule vs the MPMD per-stage-"
                         "program runtime on an S-device mesh (CPU: forced "
                         "host devices) and emit one BENCH line")
    ap.add_argument("--pp-runtime", default="both",
                    choices=["spmd", "mpmd", "both"],
                    help="with --pp: which pipeline runtime(s) to measure; "
                         "'both' A/Bs them in one process")
    ap.add_argument("--pp-schedule", default="zb", choices=["1f1b", "zb"],
                    help="with --pp: schedule the MPMD runtime executes "
                         "(the spmd leg always measures the lockstep 1F1B "
                         "harness)")
    ap.add_argument("--otrace", default=None, metavar="PATH",
                    help="enable the obs span tracer for the whole run and "
                         "write a Chrome/Perfetto trace_event JSON (with "
                         "the metrics-registry snapshot under 'metrics') "
                         "here at exit; with --pp ... mpmd this also runs "
                         "the trace-vs-analytic bubble cross-check and adds "
                         "trace_bubble/dag_bubble_analytic fields")
    ap.add_argument("--otrace-xla", action="store_true",
                    help="with --otrace: additionally capture a "
                         "jax.profiler device trace into <PATH>.xla/ "
                         "(TensorBoard/XPlane format — compiled-program "
                         "timings the host-side span tracer cannot see)")
    args = ap.parse_args()
    if args.audit_only:
        args.audit = True
    if args.hbm_budget is not None:
        args.mem = True
    # read the plan file with plain json BEFORE the jax import: whether the
    # plan wants a ZeRO dp mesh decides the 8-host-device XLA flag below
    plan_dict = None
    if args.plan:
        with open(args.plan) as f:
            plan_dict = json.load(f)

    if args.device == "cpu":
        # rehearsal and tests: counts and control flow, never a speed
        if (args.wus != "off"
                or (args.tune and args.preset in ("small", "base"))
                or args.pp >= 2
                or args.preset == "obs"
                or (plan_dict or {}).get("zero")):
            # the ZeRO-1 dp mesh needs devices to shard over; fake 8 host
            # devices (must land before the first jax import in-process).
            # --tune only needs them where the grid has ZeRO candidates
            # (small/base) — the 8-way split slows the single-program
            # timed run, so tiny/moe sweeps stay on one device.
            # --pp needs the S-device pipeline mesh the same way
            import os

            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

        if jax.default_backend() != "tpu":
            sys.exit(f"bench.py: --device tpu (the default) needs a TPU and "
                     f"jax.default_backend() is {jax.default_backend()!r}; "
                     f"no measurement is made without one (--device cpu "
                     f"rehearses counts and control flow)")
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    preset = (args.preset or (plan_dict or {}).get("preset")
              or ("base" if on_tpu else "tiny"))

    import numpy as np

    import paddle_tpu as paddle

    if args.otrace:
        import atexit

        from paddle_tpu import obs as _obs

        _obs.reset_metrics()
        _obs.enable_tracing()
        if args.otrace_xla:
            jax.profiler.start_trace(args.otrace + ".xla")

        def _dump_otrace():
            if args.otrace_xla:
                try:
                    jax.profiler.stop_trace()
                except RuntimeError:
                    pass               # already stopped / never started
            tr = _obs.tracer()
            if tr is not None:
                tr.dump(args.otrace, metrics=_obs.registry().snapshot())
                print(f"[obs] trace written to {args.otrace}",
                      file=sys.stderr)

        # atexit covers every preset's return path with one hook
        atexit.register(_dump_otrace)

    if args.pp >= 2:
        result = _bench_pp(jax, backend, on_tpu, args)
        print(json.dumps(_stamp(result)))
        return

    if preset == "obs":
        result = _bench_obs(jax, paddle, backend, on_tpu, args)
        print(json.dumps(_stamp(result)))
        return

    run_plan = None
    if plan_dict is not None:
        from paddle_tpu.analysis.autotune import PlanConfig

        run_plan = PlanConfig.from_dict(plan_dict)

    tune_fields = {}
    if args.tune and preset in ("tiny", "small", "base", "longctx", "moe"):
        import paddle_tpu.analysis.autotune as at

        def _tune_builder(p):
            if p.preset == "moe":
                sf, pids, _m, _c, (b, s, _st) = build_moe_step(
                    on_tpu, batch=p.batch, seq=p.seq, accum=p.accum)
            else:
                sf, pids, _m, _c, (b, s, _st) = build_pretrain_step(
                    p.preset, on_tpu, plan=p)
            return (lower_pretrain_step(sf, pids),
                    max(1, p.accum) * b * s)

        budget = args.hbm_budget or at.default_budget(preset, on_tpu)
        res = at.sweep(preset, _tune_builder, hbm_budget=budget,
                       on_tpu=on_tpu, n_devices=jax.device_count(),
                       log=lambda m: print(m, file=sys.stderr))
        print(res.table(), file=sys.stderr)
        tune_fields = res.to_meta()
        if res.chosen is not None:
            run_plan = res.chosen.plan
            if args.tune_out:
                run_plan.save(args.tune_out)

    if args.fuse:
        if preset not in DEFAULTS:
            raise SystemExit(f"--fuse supports the pretrain presets "
                             f"{sorted(DEFAULTS)}, not {preset!r}")
        result = _bench_fuse(jax, paddle, backend, on_tpu, preset, args)
        print(json.dumps(_stamp(result)))
        return

    if preset == "decode":
        result = _bench_decode(jax, paddle, backend, on_tpu, args)
        result.update(_kernel_lint_fields(args.lint, preset))
        print(json.dumps(_stamp(result)))
        return
    if preset == "ocr":
        result = _bench_ocr(jax, paddle, backend, on_tpu, args)
        result.update(_kernel_lint_fields(args.lint, preset))
        print(json.dumps(_stamp(result)))
        return
    if preset == "moe":
        if run_plan is not None:
            args.batch = args.batch or run_plan.batch
            args.seq = args.seq or run_plan.seq
        result = _bench_moe(jax, paddle, backend, on_tpu, args)
        result.update(tune_fields)
        result.update(_kernel_lint_fields(args.lint, preset))
        print(json.dumps(_stamp(result)))
        return

    fuse_act = None
    if run_plan is not None and run_plan.fuse == "auto":
        # adopted fuse=auto plan: substitute the verified emitted kernels for
        # the whole run (the ExitStack keeps the activation alive through
        # trace, lower and the timed loop; the process ends with it open)
        import contextlib

        from paddle_tpu.kernels import emit as _emit
        fuse_shapes = None
        if on_tpu and preset in DEFAULTS:
            rows = ((args.batch or run_plan.batch or DEFAULTS[preset][0])
                    * (args.seq or run_plan.seq or DEFAULTS[preset][1]))
            fuse_shapes = _emit.llama_site_shapes(
                build_config(preset, "bfloat16"), rows)
        fuse_act = _emit.verified_activation(shapes=fuse_shapes)
        _fuse_stack = contextlib.ExitStack()
        _fuse_stack.enter_context(_emit.activate(fuse_act))

    # mirror build_pretrain_step's plan resolution so the tokens/s math
    # below sees the effective accum/wus
    accum = max(1, args.accum)
    eff_wus = args.wus
    if run_plan is not None:
        if accum == 1:
            accum = max(1, run_plan.accum)
        if eff_wus == "off":
            eff_wus = run_plan.wus
    step_fn, ids, model, cfg, (batch, seq, steps) = build_pretrain_step(
        preset, on_tpu, batch=args.batch, seq=args.seq, steps=args.steps,
        accum=accum, grad_dtype=args.grad_dtype, wus=eff_wus, plan=run_plan)
    n_params = sum(p.size for p in model.parameters())

    lowered = lower_pretrain_step(step_fn, ids)
    bytes_fields = _bytes_fields(lowered, audit=args.audit, label=preset)
    bytes_fields.update(_lint_fields(lowered, args.lint, label=preset))
    bytes_fields.update(_kernel_lint_fields(args.lint, preset))
    bytes_fields.update(_mem_fields(lowered, args.mem, label=preset,
                                    hbm_budget=args.hbm_budget))
    bytes_fields.update(_overlap_fields(lowered, args.overlap, label=preset))
    if eff_wus != "off":
        bytes_fields["wus"] = eff_wus
    bytes_fields.update(tune_fields)
    if run_plan is not None:
        bytes_fields["plan"] = run_plan.label()
    if fuse_act is not None:
        bytes_fields["fuse_sites"] = sorted(fuse_act)

    if args.audit_only:
        print(json.dumps(_stamp({
            **bytes_fields,
            "metric": f"llama_{preset}_pretrain_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0, "mfu": 0.0,
            "audit_only": True,
            "device": _peak_flops(jax, on_tpu)[0], "backend": backend,
            "preset": preset, "params": n_params, "batch": batch,
            "accum": accum, "seq_len": seq, "steps": 0,
        })))
        return

    # warmup/compile
    loss = step_fn(ids)
    jax.block_until_ready(loss._data)
    first_loss = float(np.asarray(loss._data))

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step_fn(ids)
    # the host read of the last loss is the sync point
    last_loss = float(np.asarray(loss._data))
    dt = time.perf_counter() - t0

    tokens_per_sec = accum * batch * seq * steps / dt
    flops_per_token = model_flops_per_token(cfg, seq)
    achieved = tokens_per_sec * flops_per_token

    dev_kind, peak = _peak_flops(jax, on_tpu)
    mfu = achieved / peak if peak else 0.0

    result = {
        **bytes_fields,
        "metric": f"llama_{preset}_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4) if peak else 0.0,
        "mfu": round(mfu, 4),
        "device": dev_kind,
        "backend": backend,
        "preset": preset,
        "params": n_params,
        "batch": batch,
        "accum": accum,
        "seq_len": seq,
        "steps": steps,
        "step_time_ms": round(1000 * dt / steps, 2),
        "first_loss": round(first_loss, 4),
        "last_loss": round(last_loss, 4),
        "flops_per_token": flops_per_token,
    }
    print(json.dumps(_stamp(result)))


if __name__ == "__main__":
    main()
