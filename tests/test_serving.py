"""serving.Engine: continuous batching over the paged KV cache.

Reference counterparts: ``block_multi_head_attention_kernel.cu`` (paged
attention) + the inference product's dynamic batching. Greedy outputs must
be bit-identical to ``model.generate`` regardless of batching, admission
order, or eviction."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.kernels import decode_attention as da
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import K_SHORT, Engine, GenRequest


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config())


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=(p,)).astype(np.int32)
            for p in lengths]


def _reference(model, prompts, max_new):
    refs = []
    for p in prompts:
        out = model.generate(paddle.to_tensor(p[None, :]), max_new_tokens=max_new)
        refs.append(np.asarray(out._data)[0, len(p):].tolist())
    return refs


def _assert_pool_reclaimed(eng):
    """No live owners, and the free pool plus the ref-0 prefix-cache LRU
    partition the usable blocks exactly (no leaks, no double frees)."""
    assert not eng._ref, f"live refs after drain: {eng._ref}"
    pool = sorted(list(eng._free) + list(eng._lru.values()))
    assert pool == list(range(1, eng.num_blocks))
    np.testing.assert_array_equal(eng._tbl, 0)


# ---------------------------------------------------------------------------
# paged kernel numerics
# ---------------------------------------------------------------------------

def test_paged_decode_kernel_matches_gather_reference():
    rng = np.random.RandomState(0)
    B, H, Hk, D, bs, NB, MAXB = 4, 8, 4, 64, 128, 16, 4
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(NB, Hk, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(NB, Hk, bs, D).astype(np.float32))
    tbl = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8],
                                [9, 10, 11, 12], [0, 0, 0, 0]], np.int32))
    lengths = jnp.asarray(np.array([200, 384, 37, 0], np.int32))
    sm = 1.0 / np.sqrt(D)
    ref = da._paged_pool_reference(q, kp, vp, tbl, lengths, sm)
    out = da._pallas_paged_decode(q, kp, vp, tbl, lengths, sm, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # inactive slot (length 0) must be exactly zero, not DMA garbage
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)


def test_write_paged_token_and_prefill_roundtrip():
    Hk, D, bs, NB = 2, 64, 128, 6
    kp = jnp.zeros((NB, Hk, bs, D), jnp.float32)
    vp = jnp.zeros((NB, Hk, bs, D), jnp.float32)
    rng = np.random.RandomState(1)
    # prefill 3 blocks worth into blocks [2, 4, 5]
    P = 3 * bs
    ks = jnp.asarray(rng.randn(P, Hk, D).astype(np.float32))
    vs = jnp.asarray(rng.randn(P, Hk, D).astype(np.float32))
    blocks = jnp.asarray(np.array([2, 4, 5], np.int32))
    kp, vp = da.write_paged_prefill(kp, vp, blocks, ks, vs)
    np.testing.assert_allclose(np.asarray(kp[4, :, 7]), np.asarray(ks[bs + 7]))
    # append one token at length=200 (block idx 1 -> physical 4, slot 72)
    tbl = jnp.asarray(np.array([[2, 4, 5, 0]], np.int32))
    lengths = jnp.asarray(np.array([200], np.int32))
    k_new = jnp.asarray(rng.randn(1, 1, Hk, D).astype(np.float32))
    v_new = jnp.asarray(rng.randn(1, 1, Hk, D).astype(np.float32))
    kp, vp = da.write_paged_token(kp, vp, tbl, lengths, k_new, v_new)
    np.testing.assert_allclose(np.asarray(kp[4, :, 200 % bs]),
                               np.asarray(k_new[0, 0]))


# lengths per decode step, blocks of 8: 0 = an inactive slot, whose table
# row points at the trash block
_TOKEN_WRITES = {
    "ragged": [[13, 3, 22, 9]],
    "first_slot_of_a_block": [[8, 16, 13, 3]],
    "last_slot_of_a_block": [[7, 23, 15, 3]],
    "into_the_next_block": [[7, 14, 15, 3], [8, 15, 16, 4], [9, 16, 17, 5]],
    "inactive_slots_share_the_trash_block": [[0, 11, 0, 0], [0, 12, 0, 0]],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("steps", _TOKEN_WRITES.values(),
                         ids=_TOKEN_WRITES.keys())
def test_write_paged_token_matches_per_sequence_loop(steps, dtype):
    B, Hk, D, bs, MAXB = 4, 2, 16, 8, 3
    NB = 1 + B * MAXB
    rng = np.random.RandomState(2)
    k0, v0 = (jnp.asarray(rng.randn(NB, Hk, bs, D), dtype) for _ in "kv")
    kp, vp = k0, v0
    want_k, want_v = np.array(k0), np.array(v0)
    trash = np.zeros(want_k.shape, bool)
    for lengths in steps:
        lengths = np.asarray(lengths, np.int32)
        tbl = 1 + np.arange(B * MAXB, dtype=np.int32).reshape(B, MAXB)
        tbl[lengths == 0] = 0
        k_new, v_new = (jnp.asarray(rng.randn(B, 1, Hk, D), dtype)
                        for _ in "kv")
        kp, vp = da.write_paged_token(kp, vp, jnp.asarray(tbl),
                                      jnp.asarray(lengths), k_new, v_new)
        for b in range(B):
            phys, slot = tbl[b, lengths[b] // bs], lengths[b] % bs
            want_k[phys, :, slot] = np.asarray(k_new[b, 0])
            want_v[phys, :, slot] = np.asarray(v_new[b, 0])
            trash[phys, :, slot] |= lengths[b] == 0
    assert kp.dtype == dtype and kp.shape == k0.shape
    # which inactive slot's token the trash block keeps is nobody's concern;
    # everything else, written or not, is bit-identical to the loop's pool
    for got, want in ((kp, want_k), (vp, want_v)):
        np.testing.assert_array_equal(np.asarray(got)[~trash], want[~trash])
    assert not trash[1:].any()


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_engine_greedy_parity_with_generate(model):
    cfg = model.config
    prompts = _prompts(cfg, (17, 33, 64, 100))
    refs = _reference(model, prompts, 12)
    eng = Engine(model, max_batch=3, num_blocks=32, block_size=128,
                 prefill_buckets=(128,))
    for p in prompts:
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=12))
    outs = {o.request_id: o for o in eng.run_to_completion()}
    assert len(outs) == 4
    for i in range(4):
        assert outs[f"req-{i + 1}"].output_ids == refs[i], f"req {i + 1}"
        assert outs[f"req-{i + 1}"].finish_reason == "length"
    # continuous batching actually happened: 4 requests through 3 slots
    assert eng.stats["prefills"] == 4


def test_engine_eviction_preserves_greedy_output(model):
    cfg = model.config
    # only 5 usable blocks: two 128-bucket seqs fit (1 block each) but the
    # moment both need a second block one must be evicted and retried
    prompts = _prompts(cfg, (120, 126, 100), seed=3)
    refs = _reference(model, prompts, 16)
    eng = Engine(model, max_batch=3, num_blocks=5, block_size=128,
                 prefill_buckets=(128,))
    for p in prompts:
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=16))
    outs = {o.request_id: o for o in eng.run_to_completion()}
    assert eng.stats["evictions"] >= 1, "eviction path not exercised"
    for i in range(3):
        assert outs[f"req-{i + 1}"].output_ids == refs[i], f"req {i + 1}"


def test_engine_eos_stops(model):
    cfg = model.config
    prompts = _prompts(cfg, (24,), seed=5)
    refs = _reference(model, prompts, 32)
    eos = refs[0][3]  # force a stop at the 4th generated token
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,))
    eng.add_request(GenRequest(prompt_ids=prompts[0], max_new_tokens=32,
                               eos_token_id=eos))
    (out,) = eng.run_to_completion()
    assert out.finish_reason == "stop"
    assert out.output_ids == refs[0][:3]


def test_engine_capacity_errors(model):
    eng = Engine(model, max_batch=1, num_blocks=4, block_size=128,
                 prefill_buckets=(128,))
    # per-slot capacity = 2 * 128 with a single 128 bucket
    with pytest.raises(ValueError, match="capacity"):
        eng.add_request(GenRequest(prompt_ids=np.zeros(250, np.int32),
                                   max_new_tokens=64))


def test_block_accounting_invariant_after_eviction(model):
    """After everything finishes, every usable block must be back in the free
    list and all table rows must point at the trash block (no leaks even when
    slots are evicted mid-allocation-loop)."""
    cfg = model.config
    prompts = _prompts(cfg, (120, 126, 100, 90), seed=7)
    eng = Engine(model, max_batch=3, num_blocks=5, block_size=128,
                 prefill_buckets=(128,))
    for p in prompts:
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=16))
    eng.run_to_completion()
    _assert_pool_reclaimed(eng)


def test_impossible_request_raises_not_spins(model):
    eng = Engine(model, max_batch=2, num_blocks=3, block_size=128,
                 prefill_buckets=(512,))
    # bucket 512 needs 4 blocks; the pool only ever has 2 usable
    with pytest.raises(ValueError, match="blocks"):
        eng.add_request(GenRequest(prompt_ids=np.ones(300, np.int32),
                                   max_new_tokens=4))


def test_paged_decode_fused_matches_reference():
    """Fused-heads paged kernel (one DMA per block for all kv heads,
    grid (B,)) == gather reference (VERDICT r4 #7 serve-overhead fix)."""
    rng = np.random.RandomState(1)
    B, H, Hk, D, bs, NB, MAXB = 4, 8, 4, 64, 128, 16, 4
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(NB, Hk, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(NB, Hk, bs, D).astype(np.float32))
    tbl = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8],
                                [9, 10, 11, 12], [0, 0, 0, 0]], np.int32))
    lengths = jnp.asarray(np.array([200, 384, 37, 0], np.int32))
    sm = 1.0 / np.sqrt(D)
    ref = da._paged_pool_reference(q, kp, vp, tbl, lengths, sm)
    out = da._pallas_paged_decode_fused(q, kp, vp, tbl, lengths, sm,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)


def test_engine_chunked_decode_matches_stepwise(model):
    """Chunked on-device decode (k > 1) is a pure overhead optimization:
    greedy outputs, block accounting, and step counts must match the
    step-at-a-time engine exactly."""
    cfg = model.config
    prompts = _prompts(cfg, (17, 33, 64), seed=3)

    def run(chunk):
        eng = Engine(model, max_batch=3, num_blocks=32, block_size=128,
                     prefill_buckets=(128,), decode_chunk=chunk)
        for p in prompts:
            eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=13))
        outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
        return outs, eng.stats["generated_tokens"], eng._available()

    outs1, gen1, free1 = run(1)
    outs8, gen8, free8 = run(8)
    assert outs8 == outs1
    assert gen8 == gen1
    assert free8 == free1 == 31


def test_engine_warmup_compiles_ladder(model):
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,), decode_chunk=8)
    eng.warmup()
    assert sorted(eng._decode_fns) == [1, 2, 4, 8]
    assert sorted(eng._prefill_fns) == [(128, 1), (128, 2)]
    # warmup is invisible to serving: a real request still round-trips
    p = _prompts(eng.cfg, (20,), seed=5)[0]
    eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=5))
    (out,) = eng.run_to_completion()
    ref = _reference(model, [p], 5)[0]
    assert out.output_ids == ref


def test_engine_decode_chunk_lints_clean(model):
    """The static analyzers on the decode chunk as the engine lowers it: no
    donation miss and no collective (the cache's device state, the last
    tokens and the token buffer are donated), no memory finding, and the
    liveness peak agrees with XLA's own ``memory_analysis()`` within 10%
    — the exact cross-check ``memory_plan()`` names."""
    from paddle_tpu.analysis import lint_lowered, lint_memory

    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,))
    lowered = eng.lower_decode(1)
    assert lint_lowered(lowered).counts() == {}
    rep = lint_memory(lowered.compile())
    assert rep.counts() == {}
    assert abs(rep.meta["peak_agreement"] - 1.0) <= 0.1


def test_engine_eos_mid_chunk_discards_tail(model):
    """With chunking, a sequence that hits eos mid-chunk must emit exactly
    the pre-eos tokens (the chunk's tail sub-steps are discarded)."""
    cfg = model.config
    p = _prompts(cfg, (24,), seed=7)[0]
    ref = _reference(model, [p], 32)[0]
    eos = ref[2]                     # force a stop 3 tokens in
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,), decode_chunk=16)
    eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=32,
                               eos_token_id=eos))
    (out,) = eng.run_to_completion()
    assert out.finish_reason == "stop"
    assert out.output_ids == ref[:2]
    # the slot and all its blocks were reclaimed despite the mid-chunk stop
    assert eng._available() == eng.num_blocks - 1


def test_engine_drain_mode_single_sync(model):
    """Without eos, run_to_completion defers every readback: the whole trace
    materializes in exactly one sync, and outputs match streaming step()."""
    cfg = model.config
    prompts = _prompts(cfg, (17, 33, 64, 100), seed=9)

    def run(streaming):
        eng = Engine(model, max_batch=3, num_blocks=32, block_size=128,
                     prefill_buckets=(128,), decode_chunk=8)
        for p in prompts:
            eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=11))
        if streaming:
            outs = []
            while eng.has_work():
                outs.extend(eng.step())
        else:
            outs = eng.run_to_completion()
        return {o.request_id: o.output_ids for o in outs}, eng.stats

    drained, dstats = run(streaming=False)
    stepped, _ = run(streaming=True)
    assert drained == stepped
    assert dstats["evictions"] == 0
    assert dstats["syncs"] == 1, dstats["syncs"]


def test_engine_sampling_top_k1_equals_greedy(model):
    """top_k=1 with temperature > 0 leaves only the argmax token in the
    nucleus, so sampled output must equal the greedy run exactly — a strong
    end-to-end check of the per-request top-k/top-p filtering."""
    cfg = model.config
    p = _prompts(cfg, (30,), seed=11)[0]
    ref = _reference(model, [p], 9)[0]
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,), decode_chunk=4)
    eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=9,
                               temperature=0.7, top_k=1))
    (out,) = eng.run_to_completion()
    assert out.output_ids == ref
    # nucleus-only variant: top_p <= 0 must still keep the top token (the
    # filter floors p at a tiny positive value), so this equals greedy too
    eng2 = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                  prefill_buckets=(128,), decode_chunk=4)
    eng2.add_request(GenRequest(prompt_ids=p, max_new_tokens=9,
                                temperature=0.7, top_p=0.0))
    (out2,) = eng2.run_to_completion()
    assert out2.output_ids == ref


def test_engine_mixed_greedy_and_sampled_batch(model):
    """A greedy request and a sampling request share one decode program;
    the greedy row must stay bit-identical to model.generate."""
    cfg = model.config
    pg, ps = _prompts(cfg, (25, 40), seed=13)
    ref = _reference(model, [pg], 10)[0]
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,), decode_chunk=4)
    eng.add_request(GenRequest(prompt_ids=pg, max_new_tokens=10))
    eng.add_request(GenRequest(prompt_ids=ps, max_new_tokens=10,
                               temperature=0.9, top_k=40, top_p=0.9))
    outs = {o.request_id: o for o in eng.run_to_completion()}
    assert outs["req-1"].output_ids == ref
    sampled = outs["req-2"].output_ids
    assert len(sampled) == 10
    assert all(0 <= t < cfg.vocab_size for t in sampled)


def test_engine_fuzz_mixed_workload(model):
    """Deterministic stress: 12 requests with random prompt/budget sizes,
    mixed greedy/sampling/eos, through a tight pool (evictions likely) and
    chunked drain scheduling.  Every greedy no-eos row must match
    model.generate; every request must be emitted exactly once; the block
    pool must be fully reclaimed."""
    cfg = model.config
    rng = np.random.default_rng(123)
    eng = Engine(model, max_batch=3, num_blocks=8, block_size=128,
                 prefill_buckets=(128, 256), decode_chunk=8)
    reqs = []
    for i in range(12):
        P = int(rng.integers(10, 200))
        p = rng.integers(1, cfg.vocab_size, size=(P,)).astype(np.int32)
        mn = int(rng.integers(1, 20))
        kind = i % 3
        if kind == 0:        # greedy, no eos -> exact-match oracle
            reqs.append((p, GenRequest(prompt_ids=p, max_new_tokens=mn), "greedy"))
        elif kind == 1:      # greedy with eos from its own reference
            ref = _reference(model, [p], mn)[0]
            eos = ref[len(ref) // 2] if len(ref) > 1 else None
            reqs.append((p, GenRequest(prompt_ids=p, max_new_tokens=mn,
                                       eos_token_id=eos), "eos"))
        else:                # sampling
            reqs.append((p, GenRequest(prompt_ids=p, max_new_tokens=mn,
                                       temperature=0.8, top_k=50, top_p=0.9),
                         "sample"))
    for _, r, _ in reqs:
        eng.add_request(r)
    outs = {o.request_id: o for o in eng.run_to_completion()}
    assert len(outs) == 12, sorted(outs)
    for (p, r, kind) in reqs:
        out = outs[r.request_id]
        if kind == "greedy":
            ref = _reference(model, [p], r.max_new_tokens)[0]
            assert out.output_ids == ref, r.request_id
            assert out.finish_reason == "length"
        elif kind == "eos":
            ref = _reference(model, [p], r.max_new_tokens)[0]
            if r.eos_token_id is not None and r.eos_token_id in ref:
                cut = ref.index(r.eos_token_id)
                assert out.output_ids == ref[:cut], r.request_id
            assert out.finish_reason in ("stop", "length")
        else:
            assert len(out.output_ids) <= r.max_new_tokens
            assert all(0 <= t < cfg.vocab_size for t in out.output_ids)
    # pool fully reclaimed, no leaked or double-freed blocks
    _assert_pool_reclaimed(eng)


def test_eviction_requeue_preserves_sampling_knobs(model):
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,))
    p = _prompts(model.config, (20,), seed=17)[0]
    eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=8,
                               temperature=0.9, top_k=40, top_p=0.85))
    eng._round(streaming=False)     # admit + prefill + one chunk
    slot = next(s for s in eng._slots if s.req is not None)
    eng._evict(slot)
    requeued = eng._waiting[0]
    assert (requeued.temperature, requeued.top_k, requeued.top_p) == \
        (0.9, 40, 0.85)


def test_engine_streaming_step_with_slot_churn(model):
    """Streaming step() (sync-per-round) through more requests than slots:
    outputs must match drain mode exactly, and each step only returns
    requests that finished in THAT round (streaming contract)."""
    cfg = model.config
    prompts = _prompts(cfg, (17, 33, 64, 100, 40), seed=21)
    eng_d = Engine(model, max_batch=2, num_blocks=32, block_size=128,
                   prefill_buckets=(128,), decode_chunk=8)
    for p in prompts:
        eng_d.add_request(GenRequest(prompt_ids=p, max_new_tokens=9))
    drained = {o.request_id: o.output_ids for o in eng_d.run_to_completion()}

    eng_s = Engine(model, max_batch=2, num_blocks=32, block_size=128,
                   prefill_buckets=(128,), decode_chunk=8)
    for p in prompts:
        eng_s.add_request(GenRequest(prompt_ids=p, max_new_tokens=9))
    stepped = {}
    rounds = 0
    while eng_s.has_work():
        outs = eng_s.step()
        rounds += 1
        for o in outs:
            assert o.request_id not in stepped, "double emission"
            stepped[o.request_id] = o.output_ids
        assert rounds < 100, "no progress"
    assert stepped == drained
    assert eng_s.stats["syncs"] >= 3      # streaming really synced per round


def test_eos_stats_match_emitted_tokens(model):
    """ADVICE.md serving/__init__.py:531 — generated_tokens is counted at
    dispatch time (per ledger cell); when an eos cut discards a chunk tail
    the stat must be reconciled so it equals the emitted output_ids."""
    cfg = model.config
    prompts = _prompts(cfg, (24, 40), seed=11)
    refs = _reference(model, prompts, 32)
    eos = refs[0][3]                 # stop request 1 four tokens in
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,), decode_chunk=16)
    eng.add_request(GenRequest(prompt_ids=prompts[0], max_new_tokens=32,
                               eos_token_id=eos))
    eng.add_request(GenRequest(prompt_ids=prompts[1], max_new_tokens=32))
    outs = eng.run_to_completion()
    emitted = sum(len(o.output_ids) for o in outs)
    assert eng.stats["generated_tokens"] == emitted


def test_eos_stats_eos_as_first_token(model):
    """The degenerate cut: the prefill's first sampled token IS the eos —
    zero tokens emitted, zero counted."""
    cfg = model.config
    p = _prompts(cfg, (24,), seed=5)[0]
    eos = _reference(model, [p], 1)[0][0]
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128,))
    eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=8,
                               eos_token_id=eos))
    (out,) = eng.run_to_completion()
    assert out.finish_reason == "stop" and out.output_ids == []
    assert eng.stats["generated_tokens"] == 0


def test_evict_aborts_when_sync_frees_blocks(model, monkeypatch):
    """ADVICE.md serving/__init__.py:359 — the eviction victim is chosen
    before _evict's _sync_pending() runs; if that sync releases blocks (a
    backlog eos finishing another slot), the preemption must be aborted
    instead of recompute-requeueing a healthy sequence."""
    cfg = model.config
    eng = Engine(model, max_batch=2, num_blocks=6, block_size=128,
                 prefill_buckets=(128,))
    for p in _prompts(cfg, (100, 110), seed=9):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=8))
    eng._admit()
    slot_a, slot_b = [s for s in eng._slots if s.req is not None]
    eng._free.clear()                # growth pressure: nothing free

    def sync_releases_a():
        if slot_a.req is not None:
            eng._release(slot_a)     # the pending eos materializes

    monkeypatch.setattr(eng, "_sync_pending", sync_releases_a)
    eng._evict(slot_b)
    assert slot_b.req is not None, "preemption not aborted"
    assert eng.stats["evictions"] == 0
    assert eng._free, "released blocks must be available to the caller"

    # with nothing reclaimable the eviction must still proceed as before
    monkeypatch.setattr(eng, "_sync_pending", lambda: None)
    eng._free.clear()
    eng._evict(slot_b)
    assert slot_b.req is None
    assert eng.stats["evictions"] == 1


# ---------------------------------------------------------------------------
# prefix caching (ISSUE 11): refcounted shared blocks, LRU reclaim
# ---------------------------------------------------------------------------

def _shared_prefix_prompts(cfg, n, prefix_len=260, tail_len=8, seed=3):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, size=prefix_len).astype(np.int32)
    return [np.concatenate([shared, rng.integers(1, cfg.vocab_size,
                                                 size=tail_len).astype(np.int32)])
            for _ in range(n)]


def test_prefix_cache_shared_prompt_prefills_once(model):
    """A prefix appearing N times prefills exactly once: every later
    admission takes all cacheable blocks as hits, and greedy outputs stay
    bit-identical to cache-off and to model.generate."""
    cfg = model.config
    prompts = _shared_prefix_prompts(cfg, 4)          # 268 tokens each
    refs = _reference(model, prompts, 6)
    n_cacheable = (len(prompts[0]) - 1) // 128        # = 2 full blocks

    def run(cache):
        eng = Engine(model, max_batch=2, num_blocks=24, block_size=128,
                     prefill_buckets=(128, 256, 512), prefix_cache=cache)
        reqs = [GenRequest(prompt_ids=p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            eng.add_request(r)
        outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
        return [outs[r.request_id] for r in reqs], eng

    outs_on, eng_on = run(True)
    outs_off, eng_off = run(False)
    assert outs_on == refs
    assert outs_off == refs                           # bit-identical on/off
    # accounting: requests 2..4 each hit the full cacheable prefix
    assert eng_on.stats["prefix_hit_blocks"] == 3 * n_cacheable
    assert eng_on.stats["prefix_hit_tokens"] == 3 * n_cacheable * 128
    assert eng_off.stats["prefix_hit_blocks"] == 0
    # the shared blocks prefilled once: cache-on skipped 3 repeat prefills
    assert (eng_on.stats["prefill_tokens"]
            < eng_off.stats["prefill_tokens"])
    # exactly the prefix's chain survives in the index
    assert len(eng_on._index) == n_cacheable
    _assert_pool_reclaimed(eng_on)
    _assert_pool_reclaimed(eng_off)


def test_prefix_refcount_shared_block_survives_owner_eviction(model):
    """Refcounted eviction: a block shared by two live slots must never be
    freed while any owner is alive — evicting one owner decrefs, the
    survivor keeps decoding from the same physical block, and the evicted
    request still completes correctly after re-admission."""
    cfg = model.config
    prompts = _shared_prefix_prompts(cfg, 2)
    refs = _reference(model, prompts, 8)
    eng = Engine(model, max_batch=2, num_blocks=24, block_size=128,
                 prefill_buckets=(128, 256, 512))
    reqs = [GenRequest(prompt_ids=p, max_new_tokens=8) for p in prompts]
    for r in reqs:
        eng.add_request(r)
    eng._round(streaming=False)     # both admitted, prefix shared
    slots = [s for s in eng._slots if s.req is not None]
    assert len(slots) == 2
    shared = [b for b in slots[0].blocks if b in slots[1].blocks]
    assert shared, "admissions did not share the prefix blocks"
    for b in shared:
        assert eng._ref[b] == 2
    eng._evict(slots[1])               # one owner preempted
    for b in shared:
        assert eng._ref[b] == 1, "shared block lost its surviving owner"
        assert b not in eng._free and b not in eng._lru.values(), \
            "shared block freed while an owner is live"
    outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
    assert [outs[r.request_id] for r in reqs] == refs
    _assert_pool_reclaimed(eng)


def test_prefix_lru_reclaim_under_pressure(model):
    """Ref-0 cached blocks are reclaimable: when the free list alone cannot
    satisfy an admission, the oldest LRU entries are deregistered and
    reused, and the evicted hashes disappear from the index."""
    cfg = model.config
    prompts = _shared_prefix_prompts(cfg, 1)          # 268 tokens, 3 blocks
    fresh = _prompts(cfg, (500,), seed=11)[0]         # needs 4 blocks
    refs = _reference(model, [prompts[0]], 4) + _reference(model, [fresh], 4)
    eng = Engine(model, max_batch=1, num_blocks=6, block_size=128,
                 prefill_buckets=(128, 256, 512))
    r1 = GenRequest(prompt_ids=prompts[0], max_new_tokens=4)
    eng.add_request(r1)
    outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
    assert len(eng._lru) == 2          # prefix parked at ref 0
    parked_hashes = set(eng._index)
    r2 = GenRequest(prompt_ids=fresh, max_new_tokens=4)
    eng.add_request(r2)                # 4 blocks needed, only 3 free
    outs.update({o.request_id: o.output_ids
                 for o in eng.run_to_completion()})
    assert [outs[r1.request_id], outs[r2.request_id]] == refs
    # at least one of the parked prefix blocks was reclaimed: its hash is
    # gone from the index (the fresh prompt's own chain replaces it)
    assert len(parked_hashes & set(eng._index)) < len(parked_hashes), \
        "LRU reclaim did not deregister"
    _assert_pool_reclaimed(eng)


def test_evict_vs_sync_release_keeps_refcounts_consistent(model):
    """Extends the PR-7 eviction/sync race regression to refcounted blocks:
    a sync that releases a prefix-sharing slot mid-_evict must leave the
    shared blocks owned by the survivor (no double-free, no LRU parking
    while a ref is live)."""
    cfg = model.config
    prompts = _shared_prefix_prompts(cfg, 2)
    eng = Engine(model, max_batch=2, num_blocks=8, block_size=128,
                 prefill_buckets=(128, 256, 512))
    for p in prompts:
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=8))
    eng._round(streaming=False)
    slot_a, slot_b = [s for s in eng._slots if s.req is not None]
    shared = [b for b in slot_a.blocks if b in slot_b.blocks]
    assert shared and all(eng._ref[b] == 2 for b in shared)
    eng._free.clear()
    orig_sync = eng._sync_pending

    def sync_releases_a():
        orig_sync()
        if slot_a.req is not None:
            eng._release(slot_a)
    eng._sync_pending = sync_releases_a
    eng._evict(slot_b)                 # sync frees a's suffix -> abort
    assert slot_b.req is not None, "preemption not aborted"
    for b in shared:
        assert eng._ref[b] == 1, \
            "release of one owner must only decref shared blocks"
        assert b not in eng._free and b not in eng._lru.values()


def test_trash_block_nan_garbage_never_leaks(model):
    """The trash block may hold arbitrary garbage — including NaN (a
    warmup prefill past the model's position table writes exactly that).
    The paged gather paths contract p@v over masked positions with weight
    0, and 0*NaN = NaN, so V must be zeroed under the mask: greedy outputs
    must be bit-identical to generate with an all-NaN trash block."""
    cfg = model.config
    prompts = _prompts(cfg, (20, 100), seed=5)
    refs = _reference(model, prompts, 8)
    eng = Engine(model, max_batch=2, num_blocks=8, block_size=128,
                 prefill_buckets=(128,))
    pools = eng.backend.device
    nan = jnp.full_like(np.asarray(pools["k"][0][0]), jnp.nan)
    eng.backend.device = {kv: tuple(p.at[0].set(nan) for p in pools[kv])
                          for kv in ("k", "v")}
    reqs = [GenRequest(prompt_ids=p, max_new_tokens=8) for p in prompts]
    for r in reqs:
        eng.add_request(r)
    outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
    assert [outs[r.request_id] for r in reqs] == refs


# ---------------------------------------------------------------------------
# chunked prefill (ISSUE 11)
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_monolithic(model):
    """Splitting a long prompt's prefill into chunks must not change a
    single output token vs the monolithic prefill (and both must match
    generate)."""
    cfg = model.config
    prompts = _prompts(cfg, (200, 20, 150), seed=7)
    refs = _reference(model, prompts, 6)

    def run(chunk):
        eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                     prefill_buckets=(128, 256), prefill_chunk=chunk)
        reqs = [GenRequest(prompt_ids=p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            eng.add_request(r)
        outs = {o.request_id: o.output_ids for o in eng.run_to_completion()}
        return [outs[r.request_id] for r in reqs], eng

    outs_c, eng_c = run(128)
    outs_m, eng_m = run(None)
    assert outs_c == refs and outs_m == refs
    assert eng_c.stats["chunk_prefills"] > 0
    assert eng_m.stats["chunk_prefills"] == 0
    _assert_pool_reclaimed(eng_c)


def test_chunked_prefill_interleaves_with_decode(model):
    """A long prompt admitted mid-decode prefills in chunks BETWEEN decode
    rounds (decode keeps advancing) and neither stream corrupts the other —
    the regression shape of the trash-block NaN bug."""
    cfg = model.config
    short, long_ = _prompts(cfg, (16, 230), seed=13)
    ref_s = _reference(model, [short], 16)[0]
    ref_l = _reference(model, [long_], 6)[0]
    eng = Engine(model, max_batch=2, num_blocks=16, block_size=128,
                 prefill_buckets=(128, 256), prefill_chunk=128,
                 decode_chunk=4)
    eng.add_request(GenRequest(prompt_ids=short, max_new_tokens=16,
                               request_id="s"))
    outs = {}
    rounds = 0
    while eng.has_work():
        rounds += 1
        if rounds == 2:
            eng.add_request(GenRequest(prompt_ids=long_, max_new_tokens=6,
                                       request_id="l"))
        for o in eng.step():
            outs[o.request_id] = o.output_ids
    assert outs["s"] == ref_s
    assert outs["l"] == ref_l
    assert eng.stats["chunk_prefills"] >= 2
    _assert_pool_reclaimed(eng)


# ---------------------------------------------------------------------------
# the length of a decode chunk (serve.decode_chunks{k, why})
# ---------------------------------------------------------------------------

def _chunks_counted(eng):
    """``{(k, why): count}`` of this engine's ``serve.decode_chunks``.  The
    registry is the process's, so the engine gets a label of its own (the
    router's way) and no other engine's chunks are read."""
    return {(int(e["labels"]["k"]), e["labels"]["why"]): int(e["value"])
            for name, e in obs.registry().snapshot().items()
            if name.startswith("serve.decode_chunks")
            and e["labels"].get("replica") == eng.obs_replica}


def _todays_length(budget, cap=32):
    """The rule before the short chunk: the largest power of two within
    the longest remaining budget and ``decode_chunk``."""
    return 1 << (min(budget, cap).bit_length() - 1)


# name -> (max_batch, num_blocks, [(prompt length, max_new_tokens)], the
# entry point, {(k, why): chunks} expected, requests left waiting and slots
# left free by it); every engine has the default decode_chunk of 32, and a
# budget reads one less after the prefill's token
_CHUNK_POLICY = {
    "free_slot": (2, 16, [(40, 41)], "step",
                  {(K_SHORT, "admissible"): 1}, 0, 1),
    "every_slot_taken": (2, 16, [(40, 41), (50, 41)], "step",
                         {(_todays_length(40), "blocked"): 1}, 0, 0),
    # two usable blocks: the first request holds one and grows into the
    # other (its budget ends with the chunk, so its slot is free again),
    # the second wants two for its 256-token bucket and waits
    "pool_holds_the_queue_back": (3, 3, [(100, 33), (200, 8)], "step",
                                  {(_todays_length(32), "blocked"): 1}, 1, 3),
    "budget_under_k_short": (2, 16, [(40, 6)], "step",
                             {(_todays_length(5), "admissible"): 1}, 0, 1),
    "run_to_completion": (2, 16, [(40, 41)], "run_to_completion",
                          {(_todays_length(40), "batch"): 1,
                           (_todays_length(8), "batch"): 1}, 0, 2),
}


_chunk_policy_labels = itertools.count()


@pytest.mark.parametrize("case", _CHUNK_POLICY.values(),
                         ids=_CHUNK_POLICY.keys())
def test_chunk_length_follows_the_schedulers_state(model, case):
    """A streaming round is held to ``K_SHORT`` decode steps only while an
    arrival could be admitted at once; every other round keeps the length
    the longest budget and ``decode_chunk`` give."""
    max_batch, num_blocks, reqs, entry, expected, waiting, free = case
    assert K_SHORT < 32                   # else no case tells the rules apart
    eng = Engine(model, max_batch=max_batch, num_blocks=num_blocks,
                 block_size=128, prefill_buckets=(128, 256))
    # a label no other case's engine had (an id() is reused once the last
    # case's engine is freed, and the registry keeps its counts)
    eng.obs_replica = f"chunk-policy-{next(_chunk_policy_labels)}"
    prompts = _prompts(model.config, [p for p, _ in reqs], seed=41)
    for p, (_, m) in zip(prompts, reqs):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=m))
    getattr(eng, entry)()
    assert _chunks_counted(eng) == expected
    assert eng.stats["decode_calls"] == sum(expected.values())
    assert eng.stats["decode_steps"] == sum(
        k * n for (k, _), n in expected.items())
    assert len(eng._waiting) == waiting
    assert sum(s.req is None for s in eng._slots) == free


def test_short_chunks_keep_greedy_outputs_token_for_token(model):
    """Requests land between ``step()`` calls while others decode; the
    chunk lengths the scheduler picks (short with a slot free, long with
    none) give the tokens of ``decode_chunk=1``."""
    cfg = model.config
    prompts = _prompts(cfg, (17, 33, 64, 100, 40, 21), seed=43)
    budgets = (30, 70, 12, 37, 9, 20)
    due = (0, 0, 1, 2, 4, 5)               # step() calls before it arrives

    def run(**engine_args):
        eng = Engine(model, max_batch=3, num_blocks=32, block_size=128,
                     prefill_buckets=(128,), **engine_args)
        eng.obs_replica = f"short-chunks-{id(eng)}"
        outs, n_steps, sent = {}, 0, 0
        while sent < len(prompts) or eng.has_work():
            while sent < len(prompts) and due[sent] <= n_steps:
                eng.add_request(GenRequest(
                    prompt_ids=prompts[sent], max_new_tokens=budgets[sent],
                    request_id=f"r{sent}"))
                sent += 1
            for o in eng.step():
                outs[o.request_id] = o.output_ids
            n_steps += 1
            assert n_steps < 400, "no progress"
        return outs, eng

    outs, eng = run()
    picked = _chunks_counted(eng)
    stepwise, _ = run(decode_chunk=1)
    assert outs == stepwise
    assert [len(outs[f"r{i}"]) for i in range(6)] == list(budgets)
    # both rules chose chunks, and only the blocked one went past K_SHORT
    assert {why for _, why in picked} == {"admissible", "blocked"}
    assert max(k for k, why in picked if why == "admissible") == K_SHORT
    assert max(k for k, why in picked if why == "blocked") > K_SHORT
    _assert_pool_reclaimed(eng)
