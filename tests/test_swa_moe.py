"""The window/full attention, sparse-expert decoder (``models/swa_moe.py``:
MiMo-V2-Flash's layer equations) against the benchmark's plain reference, on
the CPU at a tiny size:

(a) the model's forward = the reference's logits, dense and prefill-then-
    decode through the cache past twice the window;
(b) through ``serving.Engine``: mixed requests, chunks of 1-32 steps, an
    evicted and re-admitted request;
(c) each kernel in interpret mode against its XLA reference; the prefill
    kernel with the prompts' true lengths (the void query blocks);
(d) the shares of an expert layer add up to the uncut layer;
and the spans, counters, gauges and the cell's rehearsal.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.models import swa_moe_decoder as builder
from benchmarks.reference import mla_moe_decoder as moe_reference
from benchmarks.reference import swa_moe_decoder as reference
from paddle_tpu import obs, serving
from paddle_tpu.framework import flags
from paddle_tpu.incubate.moe import dropless
from paddle_tpu.kernels import gqa_attention as gqa
from paddle_tpu.models.swa_moe import (COUNTERS, SwaMoeForCausalLM,
                                       swa_moe_tiny_config)
from paddle_tpu.serving import Engine, GenRequest
from paddle_tpu.serving.cache_backend import (HybridCache, WindowKV,
                                              make_backend)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
# widths at which the Pallas kernels' block shapes apply (interpret mode)
KERNEL_WIDTHS = dict(hidden_size=128, intermediate_size=256,
                     moe_intermediate_size=128, num_hidden_layers=3,
                     num_attention_heads=4, swa_num_attention_heads=4,
                     num_key_value_heads=1, swa_num_key_value_heads=2,
                     head_dim=192, swa_head_dim=192, v_head_dim=128,
                     swa_v_head_dim=128, sliding_window=128,
                     hybrid_layer_pattern=(0, 1, 0), moe_layer_freq=(0, 1, 1))


def _reference_config(cfg):
    keys = ("num_attention_heads", "num_key_value_heads",
            "swa_num_key_value_heads", "head_dim", "v_head_dim",
            "partial_rotary_factor", "rope_theta", "swa_rope_theta",
            "sliding_window", "attention_value_scale", "num_experts_per_tok",
            "norm_topk_prob", "layernorm_epsilon")
    return {**{k: getattr(cfg, k) for k in keys},
            "experts_held_first": cfg.experts_held[0]}


def _model(seed=3, **overrides):
    paddle.seed(seed)
    return SwaMoeForCausalLM(swa_moe_tiny_config(**overrides))


def _reference_logits(model, ids):
    cfg = model.config
    layers = [builder.layer_weights(model, i)
              for i in range(cfg.num_hidden_layers)]
    logits, _ = reference.forward(_reference_config(cfg),
                                  builder.top_weights(model), layers, ids)
    return np.asarray(logits)


@pytest.fixture
def interpret_kernels():
    before = flags.get_flag("pallas_interpret")
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": before})


# ------------------------------------------------------- (a) full forward --

@pytest.mark.parametrize("seed", [3, 11])
def test_forward_matches_reference(seed):
    """Both layer kinds in the published order, prompts of 2.5 windows."""
    model = _model(seed)
    ids = np.random.default_rng(seed).integers(
        1, 512, size=(2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids))._data)
    for b in range(2):
        assert np.abs(got[b] - _reference_logits(model, ids[b])).max() < TOL


def test_forward_matches_reference_through_the_kernels(interpret_kernels):
    """The same at widths the Pallas kernels take (192-wide keys, 128-wide
    values, a window of 128 over 256 positions), in the interpreter:
    ``gqa_prefill_attn`` of both layer kinds and ``moe_grouped_mm`` against
    the reference."""
    model = _model(5, **KERNEL_WIDTHS)
    ids = np.random.default_rng(5).integers(1, 512, size=(1, 256)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids))._data)
    assert np.abs(got[0] - _reference_logits(model, ids[0])).max() < TOL


@pytest.mark.parametrize("prompt_len", [37, 5])
def test_prefill_then_decode_past_twice_the_window(prompt_len):
    """A padded prefill, its write into pools and rings, then 45 decode
    steps one at a time through the cache, slot 1 of 2 (slot 0 idle): the
    ring wraps (positions pass 2 x 16), blocks of 16 are crossed, and every
    step's logits are the reference's at that position.  The short prompt
    fills its ring only part of the way."""
    model = _model(3)
    P, N, bs = prompt_len, 45, 16
    ids = np.random.default_rng(0).integers(1, 512, size=P + N).astype(
        np.int32)
    want = _reference_logits(model, ids)
    be = make_backend(model.cache_spec(), 32, bs, 2)
    dev = be.init_device(model)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :P] = ids[:P]
    with jax.default_matmul_precision("highest"):
        cache = be.prefill_cache(model.init_cache(1, 48),
                                 jnp.asarray([P], jnp.int32))
        logits, new = model(jnp.asarray(padded), cache=cache)
        assert tuple(logits.shape) == (1, 1, 512)
        assert np.abs(np.asarray(logits._data)[0, 0] - want[P - 1]).max() < TOL
        dev = be.write_prefill(dev, new, jnp.asarray([1]),
                               jnp.asarray([[1, 2, 3]], jnp.int32))
        tbl = np.zeros((2, 8), np.int32)
        tbl[1, :6] = [1, 2, 3, 4, 5, 6]
        lens = jnp.asarray([0, P], jnp.int32)
        for t in range(N):
            step = be.step_cache(dev, jnp.asarray(tbl), lens)
            tok = jnp.asarray([[0], [ids[P + t]]], jnp.int32)
            logits, new = model(tok, cache=step)
            dev, lens = be.take_device(new), new["lengths"]
            got = np.asarray(logits._data)[1, 0]
            assert np.abs(got - want[P + t]).max() < TOL, t
    assert list(np.asarray(lens)) == [0, P + N]
    # the idle slot's ring was never written
    assert not np.asarray(dev["window"][0]["k"][0]).any()


# ------------------------------------------- (b) the engine and the cache --

def _serve_with_logits(model, requests, monkeypatch, **engine):
    """``test_mla_moe.py``'s recorder: requests one after the other, each
    alone in slot 0, with the logits every program sampled from."""
    seen = []
    sample = serving._sample_batch

    def recording(logits, key, temps, top_ks, top_ps):
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg[0])), logits,
                           ordered=True)
        return sample(logits, key, temps, top_ks, top_ps)

    monkeypatch.setattr(serving, "_sample_batch", recording)
    eng = Engine(model, **engine)
    outs, logits = {}, {}
    for rid, prompt, n_new in requests:
        del seen[:]
        eng.add_request(GenRequest(prompt_ids=prompt, max_new_tokens=n_new,
                                   request_id=rid))
        (out,) = eng.run_to_completion()
        jax.effects_barrier()
        outs[rid], logits[rid] = list(out.output_ids), np.stack(seen)
    return eng, outs, logits


@pytest.mark.parametrize("kernels", ["xla", "pallas_interpret"])
def test_engine_logits_match_reference(kernels, monkeypatch, request):
    """Prefill, then decode chunks through pools and rings, against the
    reference's full forward at every position; with the kernels in the
    interpreter ``gqa_paged_decode`` walks two blocks of 32 and the ring of
    128 is filled part of the way."""
    if kernels == "pallas_interpret":
        request.getfixturevalue("interpret_kernels")
        model, bs, buckets, n_new = _model(7, **KERNEL_WIDTHS), 32, (128,), 12
    else:
        model, bs, buckets, n_new = _model(7), 16, (16, 32, 64), 40
    rng = np.random.default_rng(1)
    first = rng.integers(1, 512, size=bs + 5).astype(np.int32)
    second = rng.integers(1, 512, size=9).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng, outs, logits = _serve_with_logits(
            model, [("first", first, n_new), ("second", second, 10)],
            monkeypatch, max_batch=4, num_blocks=24, block_size=bs,
            prefill_buckets=buckets, decode_chunk=8)
    be = eng.backend
    assert isinstance(be, HybridCache) and isinstance(be.state, WindowKV)
    assert not eng.prefix_cache and eng.prefill_chunk is None
    for rid, prompt in (("first", first), ("second", second)):
        want = _reference_logits(model, np.concatenate(
            [prompt, np.asarray(outs[rid], np.int32)]))[len(prompt) - 1:-1]
        assert logits[rid].shape == want.shape
        assert np.abs(logits[rid] - want).max() < TOL, rid
        assert outs[rid] == list(want.argmax(-1)), rid
    assert be.pages._ref == {} and be.state._live == {}


@pytest.mark.parametrize("decode_chunk", [1, 4, 32])
def test_engine_serves_mixed_requests_and_resumes_an_evicted_one(
        decode_chunk):
    """Short and long requests share four slots and too few blocks: the
    youngest is preempted, requeued with its tokens folded into its prompt
    (its ring is rebuilt from the longer prompt) and still ends with the
    greedy tokens of dense decoding, under chunks of 1, 4 and up to 32
    steps."""
    model = _model(9)
    rng = np.random.default_rng(2)
    lens = [(30, 30), (5, 41), (58, 12), (30, 30), (17, 3)]
    prompts = [rng.integers(1, 512, size=p).astype(np.int32) for p, _ in lens]
    eng = Engine(model, max_batch=4, num_blocks=11, block_size=16,
                 prefill_buckets=(16, 32, 64), decode_chunk=decode_chunk)
    for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=n,
                                   request_id=f"r{i}"))
    with jax.default_matmul_precision("highest"):
        outs = {o.request_id: list(o.output_ids)
                for o in eng.run_to_completion()}
    assert eng.stats["evictions"] >= 1
    for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
        assert len(outs[f"r{i}"]) == n
        ids = np.concatenate([p, np.asarray(outs[f"r{i}"], np.int32)])
        want = _reference_logits(model, ids)[len(p) - 1:-1]
        got = want[np.arange(n), outs[f"r{i}"]]
        assert (want.max(-1) - got).max() < TOL, f"r{i}"
    assert eng.backend.pages._ref == {} and eng.backend.state._live == {}


# ------------------------------------------------------- (c) the kernels --

def _qkv(seed, S, H, hk, B=1):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731
    return mk(B, S, H, 192), mk(B, S, hk, 192), mk(B, S, hk, 128), mk(H)


@pytest.mark.parametrize("hk", [4, 8])
@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("window", [None, 128])
def test_prefill_kernel_against_its_reference(window, sinks, hk):
    """384 positions: three query blocks of 128, a band of two key blocks or
    the causal range (one key block of 384); 16 query heads over 4 or 8 KV
    heads."""
    q, k, v, b = _qkv(hk, 384, 16, hk)
    b = b if sinks else None
    with jax.default_matmul_precision("highest"):
        want = gqa._prefill_reference(q, k, v, 0.07, window, b)
        got = gqa.gqa_prefill_attention(q, k, v, 0.07, window, b,
                                        interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


def test_prefill_kernel_streams_key_blocks_and_bounds_the_band():
    """1,024 positions: a full layer's query block meets two key blocks of
    512 (the second skipped above the diagonal); a window layer's grid has
    two steps a query block however long the sequence; a window wider than a
    block (200) takes three."""
    q, k, v, b = _qkv(1, 1024, 4, 2)
    with jax.default_matmul_precision("highest"):
        for window in (None, 128, 200):
            want = gqa._prefill_reference(q, k, v, 0.07, window, b)
            got = gqa._pallas_prefill(q, k, v, 0.07, window, b,
                                      interpret=True)
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    with pytest.raises(ValueError):
        gqa.gqa_prefill_attention(q[:, :100], k[:, :100], v[:, :100], 0.07,
                                  interpret=True)


# true lengths of four prompts in a 1,024-position bucket: inside a query
# block (and past the first key block of a full layer), on a block's edge,
# the whole bucket, one token
N_VALID = (600, 512, 1024, 1)


@pytest.mark.parametrize("hk,sinks", [(4, False), (8, True)])
@pytest.mark.parametrize("window", [None, 128, 200])
def test_prefill_kernel_skips_the_padding(window, hk, sinks):
    """``n_valid`` a prompt: the rows of every query block that holds a
    real position are the kernel's own ``n_valid=None`` rows bit for bit
    (the same operations in the same order), the real rows are the
    reference's of the UNPADDED prompt, the query blocks past the prompt's
    end come back exactly 0; the reference zeroes every row at or past
    ``n_valid``."""
    q, k, v, b = _qkv(hk, 1024, 8, hk, B=len(N_VALID))
    b = b if sinks else None
    n_valid = jnp.asarray(N_VALID, jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(gqa.gqa_prefill_attention(
            q, k, v, 0.07, window, b, interpret=True))
        got = np.asarray(gqa.gqa_prefill_attention(
            q, k, v, 0.07, window, b, interpret=True, n_valid=n_valid))
        ref = np.asarray(gqa._prefill_reference(q, k, v, 0.07, window, b,
                                                n_valid))
        for i, n in enumerate(N_VALID):
            live = -(-n // 128) * 128
            assert np.array_equal(got[i, :live], whole[i, :live]), n
            assert not got[i, live:].any() and not ref[i, n:].any(), n
            want = np.asarray(gqa._prefill_reference(
                q[i:i + 1, :n], k[i:i + 1, :n], v[i:i + 1, :n], 0.07, window,
                b))[0]
            assert np.abs(got[i, :n] - want).max() < TOL, n
            assert np.abs(ref[i, :n] - want).max() < TOL, n


@pytest.mark.parametrize("window", [None, 128])
def test_prefill_kernel_reads_no_padding_into_a_real_row(window):
    """NaN in the padded positions' q and k, and in their v from the end of
    the last key block a real row visits: the real rows are finite and
    unchanged.  (A padded VALUE inside a visited key block is multiplied by
    a probability of 0 and has to be finite: that is why a void query block
    stores zeros for the next layer, and what the docstring asks of a
    caller.)"""
    q, k, v, b = _qkv(2, 1024, 8, 4, B=len(N_VALID))
    n_valid = jnp.asarray(N_VALID, jnp.int32)
    bq, bk = gqa._prefill_blocks(1024, window)
    pos = np.arange(1024)[None, :]
    lens = np.asarray(N_VALID)[:, None]
    padded = pos >= lens
    # the end of the diagonal block of the last query block that holds a
    # real position
    visited = (gqa._band((lens - 1) // bq, bq, bk, window, np.maximum)[1]
               + 1) * bk
    nan = lambda x, where: jnp.where(                             # noqa: E731
        jnp.asarray(where)[:, :, None, None], jnp.nan, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gqa.gqa_prefill_attention(
            q, k, v, 0.07, window, b, interpret=True, n_valid=n_valid))
        got = np.asarray(gqa.gqa_prefill_attention(
            nan(q, padded), nan(k, padded), nan(v, pos >= visited), 0.07,
            window, b, interpret=True, n_valid=n_valid))
    for i, n in enumerate(N_VALID):
        assert np.isfinite(got[i, :n]).all(), n
        assert np.array_equal(got[i, :n], want[i, :n]), n
        assert not got[i, -(-n // bq) * bq:].any(), n


@pytest.mark.parametrize("window", [None, 128, 200, 700])
def test_prefill_steps_run_where_the_mask_and_the_prompt_meet(window):
    """The grid steps of a 1,024-position call, walked with the helper the
    kernel walks them with, against the mask itself: a step runs exactly
    where some pair of its blocks is inside the mask and its query block
    holds a real position; and against the closed forms: a full layer (128
    x 512) runs ``i // 4 + 1`` steps a live query block, a band of two
    128-blocks one step for the first query block and two for the others;
    the counter's pairs are these steps'."""
    S = 1024
    bq, bk = gqa._prefill_blocks(S, window)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    for n in (S,) + N_VALID:
        steps = 0
        for qi in range(S // bq):
            first, last = gqa._band(qi, bq, bk, window)
            for kb in range(S // bk):
                block = seen[qi * bq:(qi + 1) * bq, kb * bk:(kb + 1) * bk]
                runs = qi * bq < n and first <= kb <= last
                assert runs == (qi * bq < n and bool(block.any()))
                steps += runs
        live = -(-n // bq)
        if window is None:
            assert steps == sum(q // 4 + 1 for q in range(live))
        elif window == 128:
            assert steps == 2 * live - 1
        assert float(gqa.prefill_pairs_run(S, window, jnp.asarray([n]))[0]) \
            == steps * bq * bk
    assert steps == 1                                     # one token
    # the cell's bucket of 8,192: 544 steps a KV head
    assert gqa.prefill_pairs_run(8192, None, jnp.asarray([8192]))[0] \
        == 544 * 128 * 512


def _filled_pools(seed, lens, hk, bs=32, nb=24, maxb=6):
    """Pools holding ``lens[b]`` tokens of slot ``b``, written a block at a
    time, and what was written in token order."""
    rng = np.random.default_rng(seed)
    k_pool, v_pool = gqa.init_kv_pools(nb, bs, hk, 192, 128, jnp.float32)
    tbl = np.zeros((len(lens), maxb), np.int32)
    free, ks, vs = iter(range(1, nb)), [], []
    for b, n in enumerate(lens):
        n_blocks = -(-n // bs)
        tbl[b, :n_blocks] = [next(free) for _ in range(n_blocks)]
        k = rng.normal(size=(maxb * bs, hk, 192)).astype(np.float32)
        v = rng.normal(size=(maxb * bs, hk, 128)).astype(np.float32)
        if n_blocks:
            k_pool, v_pool = gqa.write_kv_prefill(
                k_pool, v_pool, jnp.asarray(tbl[b, :n_blocks]),
                jnp.asarray(k[:n_blocks * bs]), jnp.asarray(v[:n_blocks * bs]))
        ks.append(k)
        vs.append(v)
    return k_pool, v_pool, jnp.asarray(tbl), np.stack(ks), np.stack(vs)


@pytest.mark.parametrize("path", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("hk", [4, 8])
def test_paged_decode_against_dense_attention(path, hk):
    """Lengths that end inside a block, inside its first and its second
    half, on a block's edge, one token, and an idle slot: the kernel's walk
    over the live blocks against softmax over the tokens that were
    written."""
    lens = [70, 64, 33, 1, 0, 150, 17]
    k_pool, v_pool, tbl, k, v = _filled_pools(hk, lens, hk)
    q = jnp.asarray(np.random.default_rng(9).normal(
        size=(len(lens), 64, 192)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(gqa.gqa_paged_decode_attention(
            q, k_pool, v_pool, tbl, jnp.asarray(lens, jnp.int32), 0.07,
            interpret=path == "pallas_interpret"))
    rep = 64 // hk
    for b, n in enumerate(lens):
        if n == 0:
            assert not got[b].any()
            continue
        qb = np.asarray(q[b]).reshape(hk, rep, 192)
        s = np.einsum("grd,tgd->grt", qb, k[b, :n]) * 0.07
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("grt,tgd->grd", p / p.sum(-1, keepdims=True),
                         v[b, :n]).reshape(64, 128)
        assert np.abs(got[b] - want).max() < TOL, (b, n)


def test_token_and_prefill_writes_agree_and_rows_are_whole_lanes():
    """A sequence written a token at a time lands where the prefill write
    puts it; a key block is its tokens' 128-wide parts then 64-wide tails in
    pairs (token i beside token i + bs / 2), and unpacks to what went in."""
    hk, bs = 4, 32
    rng = np.random.default_rng(4)
    k = rng.normal(size=(70, hk, 192)).astype(np.float32)
    v = rng.normal(size=(70, hk, 128)).astype(np.float32)
    pad = lambda x: np.concatenate(                               # noqa: E731
        [x, np.zeros((96 - 70,) + x.shape[1:], np.float32)])
    empty = gqa.init_kv_pools(8, bs, hk, 192, 128, jnp.float32)
    assert empty[0].shape == (8, hk, 48, 128) and empty[1].shape == (
        8, hk, bs, 128)
    blocks = jnp.asarray([5, 2, 7], jnp.int32)
    whole = gqa.write_kv_prefill(*empty, blocks, jnp.asarray(pad(k)),
                                 jnp.asarray(pad(v)))
    tbl = jnp.asarray([[0, 0, 0], [5, 2, 7]], jnp.int32)
    by_token = empty
    for t in range(70):
        by_token = gqa.write_kv_token(
            *by_token, tbl, jnp.asarray([0, t], jnp.int32),
            jnp.asarray(np.stack([np.ones_like(k[t]), k[t]])),
            jnp.asarray(np.stack([np.ones_like(v[t]), v[t]])))
    for a, b in zip(whole, by_token):
        # block 0 is the trash block: the idle slot wrote there
        assert np.array_equal(np.asarray(a)[1:], np.asarray(b)[1:])
    block = np.asarray(whole[0])[2]                    # tokens 32 .. 63
    assert np.array_equal(block[:, :bs], k[32:64, :, 64:].swapaxes(0, 1))
    assert np.array_equal(block[:, bs:, :64], k[32:48, :, :64].swapaxes(0, 1))
    assert np.array_equal(block[:, bs:, 64:], k[48:64, :, :64].swapaxes(0, 1))
    assert np.array_equal(np.asarray(gqa.unpack_k_blocks(whole[0]))[2],
                          k[32:64].swapaxes(0, 1))


def test_ring_holds_the_last_window_positions():
    """After a prompt of n valid tokens (of a padded bucket) the ring's
    place j holds the latest valid position congruent to j; a token write
    puts position p at place p % W; attention over the ring equals attention
    over the last W positions, with the sink in the denominator; an idle
    slot's ring is untouched and its output zero."""
    W, hk = 16, 2
    rng = np.random.default_rng(6)
    k = rng.normal(size=(3, 64, hk, 48)).astype(np.float32)
    v = rng.normal(size=(3, 64, hk, 32)).astype(np.float32)
    n_valid = np.asarray([40, 16, 5])
    rows = np.asarray(gqa.ring_rows(jnp.asarray(k), jnp.asarray(n_valid), W))
    for b, n in enumerate(n_valid):
        for p in range(max(0, n - W), n):
            assert np.array_equal(rows[b, :, p % W], k[b, p]), (b, p)
    ring = gqa.init_ring(4, W, hk, 48, 32, jnp.float32)
    ring = {"k": ring["k"].at[:3].set(rows),
            "v": ring["v"].at[:3].set(gqa.ring_rows(
                jnp.asarray(v), jnp.asarray(n_valid), W))}
    lengths = jnp.asarray([40, 16, 5, 0], jnp.int32)
    new_k = rng.normal(size=(4, hk, 48)).astype(np.float32)
    new_v = rng.normal(size=(4, hk, 32)).astype(np.float32)
    ring = gqa.write_ring_token(ring, lengths, jnp.asarray(new_k),
                                jnp.asarray(new_v))
    assert not np.asarray(ring["k"][3]).any()
    q = rng.normal(size=(4, 8, 48)).astype(np.float32)
    sinks = rng.normal(size=(8,)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(gqa.ring_decode_attention(
            jnp.asarray(q), ring, jnp.where(lengths > 0, lengths + 1, 0),
            0.2, jnp.asarray(sinks)))
    assert not got[3].any()
    for b, n in enumerate(n_valid):
        ks = np.concatenate([k[b, :n], new_k[b][None]])[-W:]
        vs = np.concatenate([v[b, :n], new_v[b][None]])[-W:]
        s = np.einsum("grd,tgd->grt", q[b].reshape(hk, 4, 48), ks) * 0.2
        e = np.exp(s)
        p = e / (e.sum(-1, keepdims=True) + np.exp(sinks).reshape(hk, 4, 1))
        want = np.einsum("grt,tgd->grd", p, vs).reshape(8, 32)
        assert np.abs(got[b] - want).max() < TOL, b


# ----------------------------------------------- (d) a share of the experts --

def _expert_layer(held=None, seed=21):
    paddle.seed(seed)
    return dropless.DroplessMoE(64, 32, 8, 2, dtype="float32", held=held)


def test_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight experts in four shares of two, two a token: each share routes
    over all eight, computes its own two, and the four outputs add up to the
    uncut layer's and to the uncut reference's, to float32 rounding; the
    rows a share counts as held and as elsewhere add up to all the rows."""
    whole = _expert_layer()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 50, 64)).astype(
        np.float32))
    valid = jnp.asarray(np.random.default_rng(1).random((3, 50)) < 0.8)
    with jax.default_matmul_precision("highest"):
        want, stats = whole(x, valid=valid)
        total, rows = 0.0, 0.0
        for first in (0, 2, 4, 6):
            share = _expert_layer(held=(first, 2))
            assert share.w_gate_up.shape[0] == 2
            assert share.gate_weight.shape == whole.gate_weight.shape
            share.gate_weight._data = whole.gate_weight._data
            share.w_gate_up._data = whole.w_gate_up._data[first:first + 2]
            share.w_down._data = whole.w_down._data[first:first + 2]
            y, s = share(x, valid=valid)
            assert s.shape == (4,) and float(s[0] + s[3]) == float(stats[0])
            total, rows = total + y._data, rows + float(s[0])
        tokens = x.reshape(-1, 64)
        ref, _ = moe_reference.experts(
            tokens, {"router": whole.gate_weight._data,
                     "router_bias": whole.e_score_correction_bias._data,
                     "experts_gate_up": whole.w_gate_up._data,
                     "experts_down": whole.w_down._data,
                     "shared_gate_up": jnp.zeros((64, 2)),
                     "shared_down": jnp.zeros((1, 64))},
            top_k=2, scale=1.0, norm_topk=True)
    assert stats.shape == (3,) and rows == float(stats[0])
    assert np.abs(np.asarray(total) - np.asarray(want._data)).max() < 1e-6
    ref = np.where(np.asarray(valid).reshape(-1, 1), np.asarray(ref), 0.0)
    assert np.abs(np.asarray(want._data).reshape(-1, 64) - ref).max() < 1e-6


@pytest.mark.parametrize("skew", [False, True])
def test_a_share_computes_its_rows_a_segment_at_a_time(skew, monkeypatch):
    """Two of sixteen experts held: past ``_SEGMENT_FLOOR`` rows the sorted
    rows are computed in segments of a quarter of the call (twice the
    share's expected rows), only those that hold held rows; the result is
    the unsegmented one, also when every token chooses a held expert and all
    four segments run."""
    rng = np.random.default_rng(3)
    T, k, E = 64, 2, 16
    x = jnp.asarray(rng.normal(size=(T, 32)).astype(np.float32))
    idx = rng.integers(0, 2 if skew else E, size=(T, k)).astype(np.int32)
    w = jnp.asarray(rng.random((T, k)).astype(np.float32))
    gu = jnp.asarray(rng.normal(size=(2, 32, 16)).astype(np.float32))
    dn = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    valid = jnp.asarray(rng.random(T) < 0.9)
    assert dropless._segment(131072, 1 / 16) == 16384
    assert dropless._segment(131072, 1.0) == 131072
    assert dropless._segment(256, 1 / 16) == 256
    with jax.default_matmul_precision("highest"):
        want, stats = dropless.dropless_experts(
            x, jnp.asarray(idx), w, gu, dn, valid=valid, num_experts=E)
        monkeypatch.setattr(dropless, "_SEGMENT_FLOOR", 16)
        assert dropless._segment(T * k, 2 / E) == T * k // 4
        got, stats2 = jax.jit(lambda *a: dropless.dropless_experts(
            *a, valid=valid, num_experts=E))(x, jnp.asarray(idx), w, gu, dn)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.array_equal(np.asarray(stats), np.asarray(stats2))
    held = (idx < 2) & np.asarray(valid)[:, None]
    assert float(stats[0]) == held.sum()
    assert float(stats[3]) == (~(idx < 2) & np.asarray(valid)[:, None]).sum()


# ------------------------------------------------ spans, counters, gauges --

def test_spans_counters_and_gauges_of_a_served_request():
    model = _model()
    cfg = model.config
    ids = np.random.default_rng(0).integers(1, 512, size=(1, 40)).astype(
        np.int32)
    obs.reset_metrics()
    tracer = obs.enable_tracing()
    try:
        model(jnp.asarray(ids))                       # eager: host spans
        eng = Engine(model, max_batch=2, num_blocks=12, block_size=16,
                     prefill_buckets=(64,), decode_chunk=4)
        eng.add_request(GenRequest(prompt_ids=ids[0], max_new_tokens=9,
                                   request_id="r"))
        while eng.has_work():
            eng.step()
        events = tracer.events()
    finally:
        obs.disable_tracing()
    assert {"moe.route", "moe.experts", "attn.full", "attn.window",
            "serve.readback", "cache.counters"} <= {e["name"] for e in events}
    snap = obs.registry().snapshot()
    layers, k = model.n_expert_layers, cfg.num_experts_per_tok
    assert (layers, len(COUNTERS)) == (6, 13)
    assert snap["moe.steps"]["value"] == 8
    assert snap["moe.prefill_calls"]["value"] == 1
    # every routed row is held here or elsewhere
    assert snap["moe.rows"]["value"] + snap["moe.prefill_rows"]["value"] \
        + snap["moe.rows_elsewhere"]["value"] == (8 + 40) * layers * k
    assert 0 < snap["moe.prefill_rows"]["value"] < 40 * layers * k
    # pairs inside the mask, in units of 1,024: 40 * 41 / 2 and the band's
    assert "attn.prefill_kilo_pairs_full" not in snap      # 820 pairs: 0
    assert snap["cache.window_bytes_per_slot"]["value"] == \
        model.cache_spec()["state_bytes_per_slot"] == 5 * 16 * 4 * 80 * 4
    assert snap["cache.kv_bytes_per_token"]["value"] == 2 * 2 * 80 * 4
    assert snap["cache.kv_blocks_live"]["value"] == 0
    assert snap["cache.window_slots_live"]["value"] == 0
    # the readback's span carries the running totals and the gauges
    marks = [e["args"] for e in events if e["name"] == "cache.counters"]
    assert marks[-1]["moe.steps"] == 8
    assert max(m["cache.window_slots_live"] for m in marks) == 1
    # 40 + 9 tokens end in the fourth block of 16; the last token is
    # sampled and never written
    assert max(m["cache.kv_blocks_live"] for m in marks) in (3, 4)
    assert eng.stats["syncs"] <= eng.stats["decode_calls"] + 1
    text = eng.lower_decode(1).compile().as_text()
    for scope in ("moe.route", "moe.experts", "attn.full", "attn.window"):
        assert scope in text, scope


def test_pairs_inside_the_mask_are_counted_by_layer_kind():
    """A prompt of 1,500 tokens in a bucket of 2,048: 1500 * 1501 / 2 causal
    pairs and 128 * 129 / 2 + (1500 - 128) * 128 in the band, in units of
    1,024, whatever the bucket."""
    model = _model(3, max_position_embeddings=4096, sliding_window=128,
                   num_hidden_layers=2)
    be = make_backend(model.cache_spec(), 4, 128, 1)
    ids = np.zeros((1, 2048), np.int32)
    cache = be.prefill_cache(model.init_cache(1, 2048),
                             jnp.asarray([1500], jnp.int32))
    _, new = model(jnp.asarray(ids), cache=cache)
    got = dict(zip(COUNTERS, np.asarray(new["counters"])))
    assert got["attn.prefill_kilo_pairs_full"] == 1500 * 1501 // 2 // 1024
    assert got["attn.prefill_kilo_pairs_window"] == (
        128 * 129 // 2 + (1500 - 128) * 128) // 1024
    assert got["moe.prefill_calls"] == 1 and got["moe.steps"] == 0


@pytest.mark.parametrize("kernels,n,bucket", [
    ("xla", 300, 512), ("xla", 600, 1024), ("xla", 1000, 1024),
    ("pallas_interpret", 130, 256)])
def test_padded_prompt_attends_its_true_length(kernels, n, bucket, request):
    """A prompt padded to its bucket through ``forward``: the logits at
    ``n_valid - 1``, the full layers' K/V of the real positions and the
    rings' rows are the unpadded prompt's (rows past ``n_valid`` come back
    0 from attention and feed nothing real), and the counters read the
    pairs of the block steps ``gqa_prefill_attn`` runs for it: the query
    blocks of 128 that hold a real position, against key blocks of 512
    (full: ``i // 4 + 1`` a query block) or 128 (window: two a query block,
    one for the first)."""
    if kernels == "pallas_interpret":
        request.getfixturevalue("interpret_kernels")
        model = _model(5, **KERNEL_WIDTHS)
    else:
        model = _model(3, max_position_embeddings=4096, sliding_window=128,
                       num_hidden_layers=2)
    be = make_backend(model.cache_spec(), 4, 128, 1)
    ids = np.random.default_rng(n).integers(1, 512, size=(1, bucket)).astype(
        np.int32)

    def prefill(tokens, n_valid):
        cache = be.prefill_cache(model.init_cache(1, tokens.shape[1]),
                                 jnp.asarray([n_valid], jnp.int32))
        return model(jnp.asarray(tokens), cache=cache)

    with jax.default_matmul_precision("highest"):
        logits, new = prefill(ids, n)
        want_logits, want = prefill(ids[:, :n], n)
    assert np.abs(np.asarray(logits._data) - np.asarray(want_logits._data)
                  ).max() < TOL
    for got_kv, want_kv in zip(new["kv"], want["kv"]):
        for a, b in zip(got_kv, want_kv):
            assert np.abs(np.asarray(a)[:, :n] - np.asarray(b)).max() < TOL
    for got_ring, want_ring in zip(new["window"], want["window"]):
        for name in ("k", "v"):
            assert np.abs(np.asarray(got_ring[name])
                          - np.asarray(want_ring[name])).max() < TOL
    got = dict(zip(COUNTERS, np.asarray(new["counters"])))
    live = -(-n // 128)
    assert got["attn.prefill_kilo_pairs_run_full"] == sum(
        i // (min(bucket, 512) // 128) + 1 for i in range(live)) \
        * 128 * min(bucket, 512) // 1024
    assert got["attn.prefill_kilo_pairs_run_window"] == (2 * live - 1) * 16
    assert got["attn.prefill_kilo_pairs_full"] == n * (n + 1) // 2 // 1024


# ------------------------------------------- the cell's limit, in bfloat16 --

def test_token_checker_pads_by_the_request_and_holds_bf16(capsys):
    """The comparison that decides ``correct`` on 48 tokens of a bfloat16
    engine: it passes its own limit, sets a quarter aside, and pads the
    sequence to the next ``STEP`` positions, not to the traffic's longest."""
    prompt = np.random.default_rng(13).integers(1, 512, size=20).astype(
        np.int32)
    model = _model(13, dtype="bfloat16")
    eng = Engine(model, max_batch=2, num_blocks=24, block_size=16,
                 prefill_buckets=(64,), decode_chunk=8)
    eng.add_request(GenRequest(prompt_ids=prompt, max_new_tokens=48))
    out = list(eng.run_to_completion()[0].output_ids)
    checker = reference.TokenChecker(_reference_config(model.config), 512, 48)
    checker.STEP = 128
    capsys.readouterr()
    reading = checker.worst_gap_ulps(
        builder.top_weights(model), lambda i: builder.layer_weights(model, i),
        model.config.num_hidden_layers, prompt, out)
    line = json.loads(capsys.readouterr().out)
    assert checker.pad_len == 128 and checker.ULPS == 16
    assert reading <= checker.ULPS
    assert line["set_aside"] == 12 and line["positions"] == 48


# ---------------------------------------------------- the cell's rehearsal --

@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearses(trace):
    """``serve_swa_moe_sat``'s files walk the benchmark's control flow on the
    CPU: the cell is guarded off the chip, and the traced run names the
    cache's metric from the backend's gauges."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "serve_swa_moe_sat", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["failed"] == 0
    if trace:
        assert {"cache_bytes_per_live_token", "moe_max_expert_load"} <= set(
            last["metrics_named"])
