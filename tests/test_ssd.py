"""SSD/Mamba model family: chunked-scan kernel bit-parity, decode-from-state
bit-identity, serving integration, and the router's graceful degradation for
recurrent-cache replicas.

The contracts under test (ISSUE: O(1)-cache decode):

- the Pallas chunked scan in interpret mode is BIT-identical to
  ``ssd_scan_reference`` (they share the chunk-math helpers);
- chunked duality matches the token-by-token recurrence oracle to float
  tolerance (reassociation only);
- a pure-SSD stack's prefill-then-decode logits are BIT-identical to the
  full-sequence forward at every step — decode carries zero-initialized
  intra-chunk buffers whose padded rows are exact no-ops;
- serving through the ``RecurrentState`` backend reproduces ``generate``
  greedy outputs exactly, takes zero KV blocks, and releases state slots
  exactly once;
- the router scores prefix affinity 0 for recurrent/hybrid replicas and
  falls back to headroom + load, still completing everything exactly once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import flags
from paddle_tpu.kernels.ssd_scan import (
    ssd_recurrence_reference, ssd_scan, ssd_scan_reference)
from paddle_tpu.models import (
    LlamaForCausalLM, llama_tiny_config, ssd_tiny_config,
    ssd_tiny_hybrid_config, SSDForCausalLM)
from paddle_tpu.serving import Engine, GenRequest, RecurrentState
from paddle_tpu.serving.router import Router

_raw = lambda t: np.asarray(t._data if hasattr(t, "_data") else t)


def _operands(G=3, T=32, N=8, P=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((G, T, P)).astype(np.float32)
    b = rng.standard_normal((G, T, N)).astype(np.float32)
    c = rng.standard_normal((G, T, N)).astype(np.float32)
    la = -np.abs(rng.standard_normal((G, T)).astype(np.float32)) * 0.1
    return x, b, c, la


# ------------------------------------------------------------------ kernel --

def test_kernel_interpret_bit_identical_to_reference():
    x, b, c, la = _operands()
    y_k, s_k = ssd_scan(x, b, c, la, chunk=16, interpret=True)
    y_r, s_r = ssd_scan_reference(jnp.asarray(x), jnp.asarray(b),
                                  jnp.asarray(c), jnp.asarray(la), chunk=16)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_r))
    assert np.array_equal(np.asarray(s_k), np.asarray(s_r))


def test_chunked_matches_recurrence_oracle():
    x, b, c, la = _operands()
    y_c, s_c = ssd_scan_reference(jnp.asarray(x), jnp.asarray(b),
                                  jnp.asarray(c), jnp.asarray(la), chunk=8)
    y_t, s_t = ssd_recurrence_reference(jnp.asarray(x), jnp.asarray(b),
                                        jnp.asarray(c), jnp.asarray(la))
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_t),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_t),
                               rtol=2e-5, atol=2e-5)


def test_chunk_size_invariance():
    x, b, c, la = _operands()
    y8, s8 = ssd_scan_reference(jnp.asarray(x), jnp.asarray(b),
                                jnp.asarray(c), jnp.asarray(la), chunk=8)
    y16, s16 = ssd_scan_reference(jnp.asarray(x), jnp.asarray(b),
                                  jnp.asarray(c), jnp.asarray(la), chunk=16)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y16),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s8), np.asarray(s16),
                               rtol=2e-5, atol=2e-5)


def test_zero_padded_rows_are_exact_noops():
    """Zero rows (x=b=c=0, la=0) past the valid region leave both the valid
    outputs AND the final state bit-identical — the property the decode
    path's zero-initialized intra-chunk buffers lean on."""
    x, b, c, la = _operands(T=16)
    pad = lambda a: np.concatenate(
        [a, np.zeros((a.shape[0], 16) + a.shape[2:], np.float32)], axis=1)
    y0, s0 = ssd_scan_reference(jnp.asarray(x), jnp.asarray(b),
                                jnp.asarray(c), jnp.asarray(la), chunk=16)
    y1, s1 = ssd_scan_reference(jnp.asarray(pad(x)), jnp.asarray(pad(b)),
                                jnp.asarray(pad(c)), jnp.asarray(pad(la)),
                                chunk=16)
    assert np.array_equal(np.asarray(y0), np.asarray(y1)[:, :16])
    assert np.array_equal(np.asarray(s0), np.asarray(s1))


def test_kernel_grads_match_reference_grads():
    x, b, c, la = _operands(G=2, T=16, N=4, P=8)

    def loss_k(*a):
        y, s = ssd_scan(*a, chunk=8, interpret=True)
        return jnp.sum(y * y) + jnp.sum(s)

    def loss_r(*a):
        y, s = ssd_scan_reference(*a, chunk=8)
        return jnp.sum(y * y) + jnp.sum(s)

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(
        jnp.asarray(x), jnp.asarray(b), jnp.asarray(c), jnp.asarray(la))
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(
        jnp.asarray(x), jnp.asarray(b), jnp.asarray(c), jnp.asarray(la))
    for a, r in zip(gk, gr):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-6, atol=1e-6)


def test_chunk_must_divide_t():
    x, b, c, la = _operands(T=20)
    with pytest.raises(ValueError, match="not a multiple"):
        ssd_scan(x, b, c, la, chunk=16, interpret=True)


# ------------------------------------------------------------------- model --

@pytest.fixture(scope="module")
def ssd_model():
    paddle.seed(0)
    return SSDForCausalLM(ssd_tiny_config())


@pytest.fixture(scope="module")
def hybrid_model():
    paddle.seed(1)
    return SSDForCausalLM(ssd_tiny_hybrid_config())


def _ids(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, n)),
                       jnp.int32)


def test_prefill_then_decode_bitwise_vs_full_forward():
    """THE decode contract: at every step, decoding one token from the
    recurrent state yields logits bit-identical to re-running the whole
    prefix densely.  Prompt length deliberately not a multiple of the
    chunk size.

    Hidden width 64, not the tiny config's 128: XLA's CPU backend picks a
    different kernel for a one-row matmul once the contraction reaches 128
    (``(h @ w)[13:14] != h[13:14] @ w`` bitwise), so at 128 the dense
    projections differ before the recurrence under test is reached."""
    paddle.seed(0)
    model = SSDForCausalLM(ssd_tiny_config(hidden_size=64,
                                           intermediate_size=192))
    cfg = model.config
    ids = _ids(cfg, 37)
    # ONE full forward is the oracle for every step: the chunk math is
    # exactly causal (masked entries are literal 0.0), so position t is
    # bitwise-independent of later tokens
    full = _raw(model(ids))
    assert np.array_equal(full[:, :13],
                          _raw(model(ids[:, :13])))     # causality, once
    cache = model.init_cache(1, 64)
    logits_p, cache = model(ids[:, :13], cache=cache)
    assert np.array_equal(_raw(logits_p), full[:, :13])
    for t in range(13, 37):
        step, cache = model(ids[:, t:t + 1], cache=cache)
        assert np.array_equal(_raw(step)[:, 0], full[:, t]), f"step {t}"


def test_hybrid_prefill_then_decode_close_to_full_forward(hybrid_model):
    """Hybrid stacks inherit the attention layers' incremental-decode
    numerics (not bitwise vs dense — same as llama); the SSD layers stay
    exact underneath, so the drift is the usual fp32 epsilon."""
    model, cfg = hybrid_model, hybrid_model.config
    ids = _ids(cfg, 29, seed=1)
    full = _raw(model(ids))
    cache = model.init_cache(1, 64)
    _, cache = model(ids[:, :13], cache=cache)
    for t in range(13, 29):
        step, cache = model(ids[:, t:t + 1], cache=cache)
        np.testing.assert_allclose(_raw(step)[:, 0], full[:, t],
                                   rtol=1e-4, atol=1e-5)


def test_training_uses_kernel_under_interpret_flag(ssd_model):
    """FLAGS_pallas_interpret routes training through the Pallas kernel
    (interpret mode); logits must be bit-identical to the reference path —
    the model-level restatement of the kernel parity contract."""
    model, cfg = ssd_model, ssd_model.config
    ids = _ids(cfg, 32, seed=2)
    base = _raw(model(ids))
    flags.set_flags({"pallas_interpret": True})
    try:
        fused = _raw(model(ids))
    finally:
        flags.set_flags({"pallas_interpret": False})
    assert np.array_equal(base, fused)


def test_loss_finite_both_families(ssd_model, hybrid_model):
    for model in (ssd_model, hybrid_model):
        ids = _ids(model.config, 32, seed=3)
        loss = _raw(model.compute_loss(model(ids), ids))
        assert np.isfinite(loss) and loss > 0


def test_generate_shapes_and_determinism(ssd_model):
    ids = _ids(ssd_model.config, 9, seed=4)
    out1 = _raw(ssd_model.generate(ids, max_new_tokens=6))
    out2 = _raw(ssd_model.generate(ids, max_new_tokens=6))
    assert out1.shape == (1, 15)
    assert np.array_equal(out1, out2)


# ----------------------------------------------------------------- serving --

def _serve(model, prompts, max_new=8, **kw):
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 16)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_buckets", (32, 64))
    eng = Engine(model, **kw)
    for i, p in enumerate(prompts):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=max_new,
                                   temperature=0.0, request_id=f"r{i}"))
    outs = {o.request_id: o for o in eng.run_to_completion()}
    return eng, outs


def _gen_ref(model, prompts, max_new=8):
    return [_raw(model.generate(jnp.asarray(p)[None, :],
                                max_new_tokens=max_new))[0, len(p):]
            for p in prompts]


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=(n,)).astype(np.int32)
            for n in lengths]


def _device_nbytes(eng):
    return sum(a.nbytes for a in jax.tree.leaves(eng.backend.device))


@pytest.mark.parametrize("lengths,max_new", [
    ((7, 13, 24), 8),
    ((7, 13, 24, 18, 9, 21), 16),        # six on four slots: slots are reused
])
def test_engine_pure_ssd_matches_generate(ssd_model, lengths, max_new):
    prompts = _prompts(ssd_model.config, lengths)
    eng, outs = _serve(ssd_model, prompts, max_new=max_new)
    assert isinstance(eng.backend, RecurrentState)
    assert not eng.prefix_cache          # forced off: nothing to hash
    assert len(outs) == len(prompts)
    for i, ref in enumerate(_gen_ref(ssd_model, prompts, max_new)):
        assert np.array_equal(outs[f"r{i}"].output_ids, ref), f"r{i}"
    # O(1) residency: zero KV blocks ever claimed, every state slot released
    assert eng.backend.available() == 0 and "k" not in eng.backend.device
    assert eng.backend._live == {}
    plan = eng.memory_plan()
    assert plan["kv_pool_bytes"] == 0
    # the plan is the allocation: what it prices is what the device holds
    assert plan["state_bytes"] == _device_nbytes(eng) > 0
    curve = plan["per_seq_cache_bytes"]
    assert curve[4096] == curve[16384] == curve[65536]   # FLAT


def test_engine_hybrid_matches_generate(hybrid_model):
    prompts = _prompts(hybrid_model.config, (7, 13, 24), seed=1)
    eng, outs = _serve(hybrid_model, prompts)
    assert eng.backend.kind == "hybrid" and not eng.prefix_cache
    for i, ref in enumerate(_gen_ref(hybrid_model, prompts)):
        assert np.array_equal(outs[f"r{i}"].output_ids, ref), f"r{i}"
    # both ledgers clean: KV blocks reclaimed AND state slots released
    assert eng.backend.pages._ref == {} and eng.backend.state._live == {}
    assert len(eng.backend.pages._free) == eng.num_blocks - 1  # 0 is trash
    plan = eng.memory_plan()
    assert plan["state_bytes"] > 0 and plan["kv_pool_bytes"] > 0
    assert plan["state_bytes"] + plan["kv_pool_bytes"] == _device_nbytes(eng)
    curve = plan["per_seq_cache_bytes"]
    assert curve[16384] > curve[4096]                    # attention share grows


def test_memory_plan_refuses_oversized_state(ssd_model):
    """``state_bytes`` counts against the HBM budget exactly like the KV
    pool: a budget smaller than the slots' state residency is refused at
    construction, before any device allocation."""
    with pytest.raises(ValueError, match="exceeds hbm_budget_bytes"):
        Engine(ssd_model, num_blocks=4, block_size=16, max_batch=4,
               prefill_buckets=(32,), hbm_budget_bytes=100_000)


# ------------------------------------------------------------------ router --

def test_router_degrades_to_headroom_load_for_recurrent(ssd_model):
    """Satellite: prefix-affinity scoring must not assume a block chain.
    A recurrent replica scores affinity 0 (graceful degradation), headroom
    comes from the backend, and a mixed llama+ssd replica set completes
    every request exactly once."""
    paddle.seed(0)
    llama = LlamaForCausalLM(llama_tiny_config())
    r = Router()
    r.add_replica(Engine(llama, max_batch=2, num_blocks=16, block_size=128,
                         prefill_buckets=(128,)))
    r.add_replica(Engine(ssd_model, max_batch=2, num_blocks=16,
                         block_size=16, prefill_buckets=(32,)))
    ssd_eng = r._replicas[1]
    prompt = _prompts(ssd_model.config, (12,))[0]
    assert Router._affinity(ssd_eng, prompt) == 0
    assert r.replica_headroom_bytes(1) == ssd_eng.backend.headroom_bytes()
    rids = [r.submit(GenRequest(prompt_ids=p, max_new_tokens=4,
                                temperature=0.0))
            for p in _prompts(ssd_model.config, (12, 9, 15, 11))]
    outs = r.run_to_completion()
    assert sorted(o.request_id for o in outs) == sorted(rids)
    assert {t.replica for t in r._tracked.values()} <= {0, 1}
    # recurrent replica's ledger is clean after the storm
    assert ssd_eng.backend._live == {}


# ------------------------------------------------------- arrival-paced --

def test_loadgen_trace_through_recurrent_replica(ssd_model):
    """Satellite: an arrival-paced trace drives a pure RecurrentState
    replica through the ``Router`` end to end — every request completes,
    decode rounds are observed, and the slot ledger is clean afterwards (no
    block chain was ever needed)."""
    import time

    cfg = ssd_model.config
    r = Router()
    r.add_replica(Engine(ssd_model, max_batch=4, num_blocks=16,
                         block_size=16, prefill_buckets=(32, 64)))
    eng = r._replicas[0]
    assert eng.backend.kind == "recurrent"
    # six Poisson arrivals at 200 req/s, decode-heavy short prompts first so
    # decode is in flight when the long ones land; prompt + new tokens must
    # fit the 2*max_bucket context capacity per slot
    rng = np.random.default_rng(0)
    trace, t = [], 0.0
    for i in range(6):
        n, new = (8, 16) if i % 2 == 0 else (48, 4)
        trace.append((t, rng.integers(1, cfg.vocab_size,
                                      size=n).astype(np.int32), new))
        t += float(rng.exponential(1.0 / 200.0))
    submitted, outputs = [], {}
    t0 = time.perf_counter()
    while trace or r.has_work():
        now = time.perf_counter() - t0
        while trace and trace[0][0] <= now:
            _, ids, new = trace.pop(0)
            submitted.append(r.submit(GenRequest(
                prompt_ids=ids, max_new_tokens=new, temperature=0.0)))
        if not r.has_work():
            time.sleep(min(trace[0][0] - now, 0.01))
            continue
        for o in r.step():
            outputs[o.request_id] = list(o.output_ids)
    assert sorted(outputs) == sorted(submitted) and len(submitted) == 6
    assert sorted(map(len, outputs.values())) == [4, 4, 4, 16, 16, 16]
    assert eng.stats["decode_calls"] > 0
    # prefix caching is structurally unsupported: nothing was ever looked up
    assert eng.stats["prefix_lookup_blocks"] == 0
    assert eng.stats["prefix_hit_blocks"] == 0
    assert eng.backend._live == {} and eng.backend.available() == 0


def test_loadgen_recurrent_headroom_beats_paged_at_long_context(ssd_model):
    """Satellite payoff: the flat per-slot footprint turns into ADMISSION
    headroom.  Under the same cache-byte budget, memory_plan()'s per-seq
    curve admits orders of magnitude more concurrent 64k-context sequences
    on the RecurrentState replica than PagedKV, and the engine's
    hbm_budget admission enforces the same arithmetic up front."""
    paddle.seed(0)
    llama = LlamaForCausalLM(llama_tiny_config())
    ssd_eng = Engine(ssd_model, max_batch=4, num_blocks=16, block_size=16,
                     prefill_buckets=(32, 64))
    kv_eng = Engine(llama, max_batch=2, num_blocks=16, block_size=128,
                    prefill_buckets=(128,))
    ssd_plan = ssd_eng.memory_plan()
    kv_plan = kv_eng.memory_plan()

    # footprint shape: flat vs linear in context length
    ssd_curve = ssd_plan["per_seq_cache_bytes"]
    kv_curve = kv_plan["per_seq_cache_bytes"]
    assert ssd_curve[4096] == ssd_curve[16384] == ssd_curve[65536]
    assert kv_curve[65536] > kv_curve[16384] > kv_curve[4096]
    assert kv_curve[65536] == 16 * kv_curve[4096]        # ~linear in blocks

    # same cache-byte budget -> concurrent 64k sequences each side admits
    budget = 64 << 20
    kv_batch = budget // kv_curve[65536]
    ssd_batch = budget // ssd_curve[65536]
    assert ssd_batch > 100 * max(1, kv_batch)

    # the engine's up-front admission enforces it: a paged pool sized for
    # ONE 64k sequence blows a budget that admits a 64-slot recurrent
    # replica (refused in Python, before any allocation)
    blocks_64k = 65536 // 128
    with pytest.raises(ValueError, match="exceeds hbm_budget_bytes"):
        Engine(llama, max_batch=1, num_blocks=blocks_64k, block_size=128,
               prefill_buckets=(128,), hbm_budget_bytes=16 << 20)
    wide = Engine(ssd_model, max_batch=64, num_blocks=16, block_size=16,
                  prefill_buckets=(32, 64), hbm_budget_bytes=16 << 20)
    assert wide.backend.free_slots() == 64
    assert wide.memory_plan()["total_bytes"] <= 16 << 20


def test_8b_footprint_flat_against_linear():
    """The headline the family exists for, priced from the configs alone
    (no 8B parameter is made): one sequence of the SSD-8B stack holds the
    same 403.7 MB at 4k and at 64k tokens, where Llama-3-8B's KV grows to
    21 times that."""
    from paddle_tpu.models import ssd_8b_config
    from paddle_tpu.models.llama import llama3_8b_config
    from paddle_tpu.models.ssd import ssd_cache_spec
    from paddle_tpu.serving import make_backend

    spec = ssd_cache_spec(ssd_8b_config())
    assert spec["state_bytes_per_slot"] == 403_701_760
    ssd = make_backend(spec, num_blocks=1, block_size=128, max_slots=1)
    assert ssd.seq_bytes(4096) == ssd.seq_bytes(65536) == 403_701_760
    lcfg = llama3_8b_config()
    llama = make_backend(
        {"kinds": ("attention",) * lcfg.num_hidden_layers,
         "state_bytes_per_slot": 0, "kv_layers": lcfg.num_hidden_layers,
         "kv_bytes_per_token_layer": 2 * lcfg.kv_heads * lcfg.head_dim * 2},
        num_blocks=1, block_size=128, max_slots=1)
    assert llama.seq_bytes(65536) == 16 * llama.seq_bytes(4096)
    assert round(llama.seq_bytes(65536) / ssd.seq_bytes(65536), 2) == 21.28
