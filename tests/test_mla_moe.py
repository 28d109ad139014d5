"""The latent-attention, sparse-expert decoder on the serving path, at a small
size on the CPU with seeded random weights: the model against the plain
reference (``benchmarks/reference/mla_moe_decoder.py``), prefill and decode
through ``serving.Engine`` and the latent cache against the reference's full
forward, absorbed against expanded attention, the dropless expert layer
against a per-token loop, the latent backend's byte accounting, the spans
and counters, and the new cell's rehearsal.

Tolerances.  The small model runs in float32 and the reference in float32 at
the highest matmul precision, so the two differ by the order of the sums
alone: logits of magnitude ~1 agree to a few 1e-6, and 2e-5 is the limit
throughout (a wrong row, position, mask or expert moves them by 1e-2 and
more).  The one bfloat16 case is held to the cell's own limit in bf16 units
in the last place, and has to fail when the experts' products are rounded to
float8.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.models import mla_moe_decoder as builder
from benchmarks.reference import mla_moe_decoder as reference
from paddle_tpu import obs, serving
from paddle_tpu.framework import flags
from paddle_tpu.incubate.moe import dropless
from paddle_tpu.kernels import grouped_matmul, mla_attention
from paddle_tpu.models.mla_moe import (COUNTERS, MlaMoeForCausalLM,
                                       mla_moe_tiny_config)
from paddle_tpu.serving import Engine, GenRequest, LatentKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
# widths at which the Pallas kernels' block shapes apply (interpret mode)
KERNEL_WIDTHS = dict(hidden_size=128, intermediate_size=256,
                     moe_intermediate_size=128, num_attention_heads=2,
                     kv_lora_rank=128, qk_nope_head_dim=128,
                     qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=4)


def _reference_config(cfg):
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps")
    return {k: getattr(cfg, k) for k in keys}


def _model(seed=3, **overrides):
    paddle.seed(seed)
    return MlaMoeForCausalLM(mla_moe_tiny_config(**overrides))


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


# ------------------------------------------------------- (1) full forward --

@pytest.mark.parametrize("seed", [3, 11])
def test_forward_matches_reference(seed):
    model = _model(seed)
    ids = np.random.default_rng(seed).integers(
        1, 512, size=(2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids))._data)
    for b in range(2):
        assert np.abs(got[b] - _reference_logits(model, ids[b])).max() < TOL


def test_forward_matches_reference_through_the_kernels(interpret_kernels):
    """The same at widths the Pallas kernels take, in the interpreter:
    ``mla_prefill_attn`` and ``moe_grouped_mm`` against the reference."""
    model = _model(5, **KERNEL_WIDTHS)
    ids = np.random.default_rng(5).integers(1, 512, size=(1, 128)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids))._data)
    assert np.abs(got[0] - _reference_logits(model, ids[0])).max() < TOL


# ------------------------------------------- (2) the engine and the cache --

def _serve_with_logits(model, requests, monkeypatch, **engine):
    """Serve ``requests`` [(id, prompt, n_new)] one after the other, each to
    completion and so alone in slot 0, recording the logits every program
    sampled from.  Returns the engine and per request the emitted tokens and
    the [n_new, vocab] logits they were the argmax of (a chunk is never
    longer than the budget that is left, so every recorded row counts)."""
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
    """Prefill, then 24 decode steps through the latent cache, against the
    reference's full forward at every position: a prompt that crosses a block
    boundary (and decodes across another), and a second request that hits
    the first one's prefix block and prefills its suffix as a chunk."""
    if kernels == "pallas_interpret":
        request.getfixturevalue("interpret_kernels")
        model, bs, buckets = _model(7, **KERNEL_WIDTHS), 32, (128,)
    else:
        model, bs, buckets = _model(7), 16, (16, 32, 64)
    tiles = obs.registry().counter
    took = [tiles("moe.grouped_mm_programs", tm=t) for t in (64, 128)]
    before = [c.value for c in took]
    rng = np.random.default_rng(1)
    first = rng.integers(1, 512, size=bs + 5).astype(np.int32)
    second = np.concatenate(
        [first[:bs], rng.integers(1, 512, size=9).astype(np.int32)])
    with jax.default_matmul_precision("highest"):
        eng, outs, logits = _serve_with_logits(
            model, [("first", first, 25), ("second", second, 10)],
            monkeypatch, max_batch=4, num_blocks=24, block_size=bs,
            prefill_buckets=buckets, decode_chunk=8)
    assert isinstance(eng.backend, LatentKV)
    assert eng.stats["prefix_hit_blocks"] == 1 and \
        eng.stats["chunk_prefills"] == 1
    for rid, prompt in (("first", first), ("second", second)):
        n = len(outs[rid])
        want = _reference_logits(model, np.concatenate(
            [prompt, np.asarray(outs[rid], np.int32)]))[len(prompt) - 1:-1]
        assert logits[rid].shape == want.shape
        assert np.abs(logits[rid] - want).max() < TOL, rid
        assert outs[rid] == list(want.argmax(-1)), rid
    assert eng.backend._ref == {}          # every block went back
    # the decode programs (4 slots x 2 experts a token) took the 64-row tile,
    # the prefill programs (128 x 2 rows) the next rung; XLA's path none
    assert all((c.value > b) == (kernels == "pallas_interpret")
               for c, b in zip(took, before))


def test_engine_evicts_and_resumes_through_the_latent_cache():
    """Too few blocks for three long requests: the youngest is preempted,
    requeued with its tokens folded into its prompt, and still ends with the
    greedy tokens of the reference."""
    model = _model(9)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 512, size=30).astype(np.int32)
               for _ in range(3)]
    eng = Engine(model, max_batch=3, num_blocks=9, block_size=16,
                 prefill_buckets=(32,), decode_chunk=4, prefix_cache=False)
    for i, p in enumerate(prompts):
        eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=30,
                                   request_id=f"r{i}"))
    with jax.default_matmul_precision("highest"):
        outs = {o.request_id: list(o.output_ids)
                for o in eng.run_to_completion()}
    assert eng.stats["evictions"] >= 1
    for i, p in enumerate(prompts):
        ids = np.concatenate([p, np.asarray(outs[f"r{i}"], np.int32)])
        want = _reference_logits(model, ids)[len(p) - 1:-1]
        top = want.max(-1)
        got = want[np.arange(30), outs[f"r{i}"]]
        assert (top - got).max() < TOL, f"r{i}"


# ------------------------------------------- (3) absorbed against expanded --

@pytest.mark.parametrize("path", ["xla", "pallas_interpret"])
def test_absorbed_decode_equals_expanded_attention(path):
    """On the same cache, both in float32: scores over the latent with the
    up-projection folded into the query, against keys and values rebuilt
    from the latent and ordinary attention over them."""
    rng = np.random.default_rng(4)
    B, H, rank, rope, nope, vd, bs, nb, maxb = 3, 4, 128, 64, 32, 48, 32, 12, 4
    lengths = np.array([70, 33, 0], np.int32)
    tbl = np.zeros((B, maxb), np.int32)
    tbl[0, :3], tbl[1, :2] = [3, 5, 7], [2, 9]
    rows = rng.normal(size=(B, maxb * bs, rank + rope)).astype(np.float32)
    pool = mla_attention.init_latent_pool(nb, bs, rank, rope, jnp.float32)
    for b in range(2):
        pool = mla_attention.write_latent_prefill(
            pool, jnp.asarray(tbl[b]), jnp.asarray(rows[b]), rank)
    w_k = rng.normal(size=(rank, H, nope)).astype(np.float32) / 8
    w_v = rng.normal(size=(rank, H, vd)).astype(np.float32) / 8
    q_nope = rng.normal(size=(B, H, nope)).astype(np.float32)
    q_rope = rng.normal(size=(B, H, rope)).astype(np.float32)
    scale = 1.0 / np.sqrt(nope + rope)
    with jax.default_matmul_precision("highest"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_k)
        o_lat = mla_attention.latent_decode_attention(
            q_lat, jnp.asarray(q_rope), pool, jnp.asarray(tbl),
            jnp.asarray(lengths), scale, interpret=path != "xla")
        got = np.asarray(jnp.einsum("bhr,rhv->bhv", o_lat, w_v))
    assert np.all(got[2] == 0)                       # the inactive slot
    for b in range(2):
        L = lengths[b]
        c, k_r = rows[b, :L, :rank], rows[b, :L, rank:]
        k_nope = np.einsum("tr,rhn->thn", c, w_k)
        v = np.einsum("tr,rhv->thv", c, w_v)
        s = (np.einsum("hn,thn->ht", q_nope[b], k_nope)
             + q_rope[b] @ k_r.T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("ht,thv->hv", p, v)
        assert np.abs(got[b] - want).max() < TOL


# blocks of 32 tokens, tables of 6: (lengths, ring) of each case
RING_CASES = {
    # a slot of 5 and of 6 blocks behind a ring of 3, and of 4: slot edges
    # crossed with a smaller ring than a slot's blocks
    "ring_smaller_than_slots": ([150, 192, 97], 3),
    "ring_of_4_smaller": ([150, 192, 97], 4),
    # a ring of 8 over slots of 1-2 blocks: prefetches reach several slots on
    "ring_larger_than_slots": ([20, 33, 64, 1, 40], 8),
    "empty_between_and_at_both_ends": ([0, 70, 0, 0, 33, 0], 4),
    "every_slot_empty": ([0, 0, 0], 4),
    # a block edge, mid-block, an odd token, one token, one past an edge
    "block_edge_mid_odd": ([64, 45, 47, 1, 65], 3),
    "one_slot_uses_every_entry": ([192, 0, 31], 4),
}


@pytest.mark.parametrize("case", RING_CASES)
def test_latent_decode_ring_against_reference(case, monkeypatch):
    """``mla_paged_decode`` in the interpreter against ``_decode_reference``
    in float32: the ring of block DMAs runs across slot edges and empty
    slots, a slot's last block is cut at any token, and neither the trash
    block nor the rows past a length are read into a result (both are NaN
    here)."""
    lengths, ring = RING_CASES[case]
    monkeypatch.setattr(mla_attention, "RING", ring)
    rng = np.random.default_rng(len(lengths) * 7 + ring)
    B, H, rank, rope, bs, maxb = len(lengths), 4, 128, 64, 32, 6
    lengths = np.asarray(lengths, np.int32)
    n_live = -(-lengths // bs)
    nb = 1 + int(n_live.sum())
    tbl = np.zeros((B, maxb), np.int32)
    ids = rng.permutation(np.arange(1, nb))           # blocks in any order
    for b, at in enumerate(np.cumsum(n_live) - n_live):
        tbl[b, :n_live[b]] = ids[at:at + n_live[b]]
    pool = rng.normal(size=(nb, bs // 2, 2 * (rank + rope))).astype(
        np.float32)
    pool[0] = np.nan                                   # the trash block
    for b in range(B):
        if lengths[b] % bs:                            # past the length
            blk = pool[tbl[b, n_live[b] - 1]]
            for t in range(lengths[b] % bs, bs):
                r, odd = t // 2, t % 2
                blk[r, odd * rank:(odd + 1) * rank] = np.nan
                blk[r, 2 * rank + odd * rope:2 * rank + (odd + 1) * rope] = \
                    np.nan
    q_lat = jnp.asarray(rng.normal(size=(B, H, rank)).astype(np.float32))
    q_rope = jnp.asarray(rng.normal(size=(B, H, rope)).astype(np.float32))
    args = (q_lat, q_rope, jnp.asarray(pool), jnp.asarray(tbl),
            jnp.asarray(lengths), 0.07)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla_attention.latent_decode_attention(
            *args, interpret=True))
        want = np.asarray(mla_attention._decode_reference(*args))
    assert np.isfinite(got).all()
    assert np.all(got[lengths == 0] == 0)
    assert np.abs(got - want).max() < TOL


def test_latent_decode_programs_counted_by_ring(monkeypatch):
    """``mla.decode_programs{ring}`` counts one a traced call on the
    kernel's path, none a compiled one and none on XLA's path."""
    def count(ring):
        return obs.registry().counter("mla.decode_programs", ring=ring).value

    args = (jnp.zeros((2, 4, 128)), jnp.zeros((2, 4, 64)),
            jnp.zeros((3, 16, 384)), jnp.asarray([[1, 2], [0, 0]]),
            jnp.asarray([40, 0]), 0.07)
    before = count(mla_attention.RING)
    attend = jax.jit(functools.partial(
        mla_attention.latent_decode_attention, interpret=True),
        static_argnums=5)
    attend(*args)
    attend(*args)
    mla_attention.latent_decode_attention(*args)      # XLA's path: no ring
    assert count(mla_attention.RING) - before == 1
    monkeypatch.setattr(mla_attention, "RING", 6)
    before = count(6)
    jax.jit(functools.partial(mla_attention.latent_decode_attention,
                              interpret=True), static_argnums=5).lower(*args)
    assert count(6) - before == 1
    assert obs.registry().snapshot()["mla.decode_programs{ring=6}"][
        "labels"] == {"ring": 6}


def test_token_chunk_and_prefill_writes_agree():
    """The three ways a row reaches the pool leave the same pool."""
    rng = np.random.default_rng(6)
    rank, rope, bs, nb = 32, 16, 16, 6
    rows = rng.normal(size=(2 * bs, rank + rope)).astype(np.float32)
    blocks = jnp.asarray([4, 2], jnp.int32)
    empty = mla_attention.init_latent_pool(nb, bs, rank, rope, jnp.float32)
    whole = mla_attention.write_latent_prefill(empty, blocks,
                                               jnp.asarray(rows), rank)
    tbl = jnp.asarray([[4, 2, 0]], jnp.int32)
    chunked = mla_attention.write_latent_chunk(
        empty, tbl, jnp.asarray([0]), jnp.asarray(rows[None]), rank)
    by_token = empty
    for t in range(2 * bs):
        by_token = mla_attention.write_latent_token(
            by_token, tbl, jnp.asarray([t]), jnp.asarray(rows[t:t + 1]), rank)
    assert np.array_equal(np.asarray(whole), np.asarray(chunked))
    assert np.array_equal(np.asarray(whole), np.asarray(by_token))
    c, k_r = mla_attention.unpack_rows(np.asarray(whole)[4], rank)
    assert np.array_equal(c, rows[:bs, :rank])
    assert np.array_equal(k_r, rows[:bs, rank:])


# ------------------------------------------------- (4) the dropless layer --

@pytest.mark.parametrize("path", ["xla", "pallas_interpret"])
def test_dropless_experts_under_a_skewed_router(path):
    """A selection bias that sends every token to expert 0 first and never to
    experts 5-7: one expert gets half of all rows, three get none, nothing
    is dropped and the result equals a loop over each token's choices."""
    rng = np.random.default_rng(8)
    T, hidden, width, E, k = 75, 128, 128, 8, 2
    x = rng.normal(size=(T, hidden)).astype(np.float32)
    router = rng.normal(size=(hidden, E)).astype(np.float32) * 0.05
    bias = np.array([5.0, 0, 0, 0, 0, -5.0, -5.0, -5.0], np.float32)
    w_gu = rng.normal(size=(E, hidden, 2 * width)).astype(np.float32) * 0.05
    w_dn = rng.normal(size=(E, width, hidden)).astype(np.float32) * 0.05
    valid = np.ones((T,), bool)
    valid[-5:] = False                      # a bucket's padding
    with jax.default_matmul_precision("highest"):
        idx, w = dropless.sigmoid_topk_route(jnp.asarray(x), router, bias, k,
                                             scale=2.0)
        got, stats = dropless.dropless_experts(
            jnp.asarray(x), idx, w, jnp.asarray(w_gu), jnp.asarray(w_dn),
            valid=jnp.asarray(valid), interpret=path != "xla")
    idx, w, got = np.asarray(idx), np.asarray(w), np.asarray(got)
    assert (idx[:, 0] == 0).all() and not np.isin(idx, (5, 6, 7)).any()
    sizes = np.bincount(idx[valid].ravel(), minlength=E)
    assert sizes[0] == 70 and (sizes[5:] == 0).all()
    assert list(np.asarray(stats)) == [140.0, (sizes > 0).sum(), 70.0]
    s = 1 / (1 + np.exp(-(x @ router)))
    want = np.zeros_like(x)
    for t in range(T - 5):
        chosen = s[t, idx[t]]
        for e, s_e in zip(idx[t], chosen):
            g, u = np.split(x[t] @ w_gu[e], 2)
            want[t] += 2.0 * s_e / chosen.sum() * ((g / (1 + np.exp(-g)) * u)
                                                   @ w_dn[e])
    assert np.abs(got - want).max() < TOL
    assert np.all(got[-5:] == 0)


def _ragged(rows, top, seed, heavy=None):
    """64 group sizes of 0..``top`` rows (edges fall anywhere in a tile)
    that leave a trailing stretch of ``rows`` to no group; ``heavy``: one
    group that holds most of the rows."""
    sizes = np.random.default_rng(seed).integers(0, top + 1, size=64)
    sizes[::9] = 0
    if heavy is not None:
        sizes[37] = heavy
    assert 0 < rows - sizes.sum() < rows // 4, sizes.sum()
    return [int(n) for n in sizes]


# a decode step's few rows in 4 groups, then prefill shapes: 64 groups over
# one short prompt's rows (256 x 6) and over the 1024 bucket's (1024 x 6)
GROUPED_CASES = {
    "skewed": (128, [10, 0, 100, 18]), "one_group": (128, [0, 0, 0, 128]),
    "odd_edges": (128, [33, 31, 1, 0]), "no_rows": (128, [0, 0, 0, 0]),
    "prefill_1536": (1536, _ragged(1536, 50, seed=1)),
    "prefill_6144": (6144, _ragged(6144, 200, seed=2)),
    "prefill_6144_one_heavy": (6144, _ragged(6144, 24, seed=3, heavy=4500)),
}


def _grouped_case(name):
    rows, sizes = GROUPED_CASES[name]
    rng = np.random.default_rng(sum(sizes))
    a = rng.normal(size=(rows, 128)).astype(np.float32)
    b = rng.normal(size=(len(sizes), 128, 256)).astype(np.float32)
    want, r = np.zeros((rows, 256), np.float32), 0
    for g, n in enumerate(sizes):
        want[r:r + n] = a[r:r + n] @ b[g]
        r += n
    return (jnp.asarray(a), jnp.asarray(b), jnp.asarray(sizes, jnp.int32),
            want)


@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_matmul_kernel_against_a_loop(case):
    a, b, sizes, want = _grouped_case(case)
    with jax.default_matmul_precision("highest"):
        for interpret in (False, True):
            got = grouped_matmul.grouped_matmul(a, b, sizes,
                                                interpret=interpret)
            assert np.abs(np.asarray(got) - want).max() < 1e-4


@pytest.mark.parametrize("tm", [64, 128, 256, 512])
def test_grouped_matmul_rows_do_not_know_their_tile(tm):
    """Whatever tile the ladder would choose: the same inputs give the same
    rows under every tile, one block of 64 rows or 128-row blocks of which
    those with none of the group's rows are skipped (``K`` is never split, a
    row's sum is its own)."""
    with jax.default_matmul_precision("highest"):
        for case in ("skewed", "prefill_1536", "prefill_6144_one_heavy"):
            a, b, sizes, want = _grouped_case(case)
            rows = a.shape[0]
            got = np.asarray(grouped_matmul._pallas_gmm(
                jnp.pad(a, ((0, -rows % tm), (0, 0))), b, sizes, tm,
                interpret=True))[:int(sizes.sum())]
            assert np.abs(got - want[:len(got)]).max() < 1e-4, case


def test_grouped_matmul_programs_counted_by_tile():
    """The tile follows the call's rows as it always did (what a pair
    computes no longer follows the tile: 128-row blocks of it, chip table in
    PERF.md section 6, PR 36): a decode step's 192 rows over 64 experts keep
    the 64-row tile, one prompt of the 1024 or 2048 bucket and the widest
    prefill call take 512-row tiles.  ``moe.grouped_mm_programs{tm}`` counts
    one a traced call, none a compiled one."""
    def count(tm):
        return obs.registry().counter("moe.grouped_mm_programs", tm=tm).value

    def gmm(a, b, sizes):
        return grouped_matmul.grouped_matmul(a, b, sizes, interpret=True)

    b = jnp.zeros((64, 128, 128), jnp.float32)
    sizes = jnp.full((64,), 3, jnp.int32)
    before = {tm: count(tm) for tm in (64, 256, 512)}
    decode = jax.jit(gmm)
    decode(jnp.zeros((192, 128), jnp.float32), b, sizes)
    decode(jnp.zeros((192, 128), jnp.float32), b, sizes)
    # XLA's ragged product has no tile: nothing counted
    grouped_matmul.grouped_matmul(jnp.zeros((192, 128), jnp.float32), b, sizes)
    assert [count(tm) - n for tm, n in before.items()] == [1, 0, 0]
    for rows in (1536, 6144, 12288, 98304):
        jax.jit(gmm).lower(jax.ShapeDtypeStruct((rows, 128), jnp.float32),
                           b, sizes)
    assert [count(tm) - n for tm, n in before.items()] == [1, 1, 3]
    assert obs.registry().snapshot()["moe.grouped_mm_programs{tm=512}"][
        "labels"] == {"tm": 512}


# ---------------------------------------------- (5) the backend's numbers --

def test_latent_backend_accounting():
    """``seq_bytes``, ``pool_bytes`` and ``memory_plan()`` are the issue's
    arithmetic at this test's sizes: (rank + rope) values a token a layer,
    times the layers, times the block's tokens, times the blocks; and the
    pools on the device hold exactly that, with no padded lane."""
    model = _model()
    cfg = model.config
    num_blocks, bs = 24, 16
    eng = Engine(model, max_batch=4, num_blocks=num_blocks, block_size=bs,
                 prefill_buckets=(16, 32, 64))
    row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4          # float32
    assert model.cache_spec()["kv_bytes_per_token_layer"] == row
    block = row * cfg.num_hidden_layers * bs
    be = eng.backend
    assert be.block_bytes == block and be.pool_bytes() == num_blocks * block
    assert be.seq_bytes(1) == block and be.seq_bytes(bs + 1) == 2 * block
    plan = eng.memory_plan()
    assert plan["kv_pool_bytes"] == num_blocks * block
    assert plan["state_bytes"] == 0
    assert plan["per_seq_cache_bytes"][4096] == 4096 // bs * block
    assert sum(p.nbytes for p in be.device["latent"]) == be.pool_bytes()
    assert be.supports_prefix_cache and be.supports_chunked_prefill
    assert be.migrate(40)["bytes"] == 3 * block
    # at the published widths: 1,152 B a token a layer in bf16
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    big = MlaMoeConfig(num_hidden_layers=9)
    assert big.latent_width * 2 == 1152
    assert 9 * 1152 * 128 * 1536 == 2_038_431_744


# ------------------------------------------------ spans and counters (obs) --

def test_spans_and_counters_of_a_served_request():
    model = _model()
    ids = np.random.default_rng(0).integers(1, 512, size=(1, 40)).astype(
        np.int32)
    obs.reset_metrics()
    tracer = obs.enable_tracing()
    try:
        model(jnp.asarray(ids))                       # eager: host spans
        cache = {"latent": model.init_latent_pools(4, 16, "float32"),
                 "block_table": jnp.asarray([[1, 2, 3]], jnp.int32),
                 "lengths": jnp.asarray([3], jnp.int32),
                 "counters": jnp.zeros((len(COUNTERS),), jnp.int32)}
        model(jnp.asarray(ids[:, :1]), cache=cache)
        eng = Engine(model, max_batch=2, num_blocks=12, block_size=16,
                     prefill_buckets=(64,), decode_chunk=4)
        eng.add_request(GenRequest(prompt_ids=ids[0], max_new_tokens=9,
                                   request_id="r"))
        while eng.has_work():
            eng.step()
        names = {e["name"] for e in tracer.events()}
    finally:
        obs.disable_tracing()
    assert {"moe.route", "moe.experts", "mla.prefill_attn",
            "mla.decode_attn", "serve.readback"} <= names
    snap = obs.registry().snapshot()
    layers, k = model.n_expert_layers, model.config.num_experts_per_tok
    assert snap["moe.steps"]["value"] == 8
    assert snap["moe.rows"]["value"] == 8 * layers * k
    assert snap["moe.prefill_rows"]["value"] == 40 * layers * k
    assert snap["moe.prefill_calls"]["value"] == 1
    assert layers <= snap["moe.experts_touched"]["value"] / 8 <= layers * k
    assert snap["moe.max_expert_rows"]["value"] == 8 * layers
    assert snap["mla.prefill_kilo_pairs"]["value"] == 40 * 40 // 1024
    assert snap["cache.latent_bytes_per_token"]["value"] == 48 * 4
    assert snap["cache.latent_blocks_live"]["value"] == 0
    # no sync beyond the engine's own: one readback a step
    assert eng.stats["syncs"] <= eng.stats["decode_calls"] + 1
    # the scopes are in the compiled decode program, for the device trace
    text = eng.lower_decode(1).compile().as_text()
    for scope in ("moe.route", "moe.experts", "mla.decode_attn"):
        assert scope in text, scope


# ------------------------------------------- the cell's limit, in bfloat16 --

@pytest.mark.parametrize("seed", [13, 14])
def test_token_checker_holds_bf16_and_sees_float8(seed, monkeypatch, capsys):
    """The comparison that decides ``correct``, with its own limits, on 96
    tokens over a vocabulary of 4,096, six layers 256 wide.  A bfloat16
    engine passes with next to no position over the limit.  The same engine
    with the experts' products rounded to float8 (e4m3, 3 bits of mantissa
    against bfloat16's 7) overturns the argmax at several times as many
    positions, by far more than the limit.  At this size that is still under
    the quarter of the positions the checker sets aside for routing flips;
    at the published widths on the chip it is 42-60% of them, and the cell's
    limit refuses it (PERF.md section 6, PR 35)."""
    prompt = np.random.default_rng(seed).integers(1, 4096, size=20).astype(
        np.int32)
    plain = dropless.grouped_matmul

    def rounded(lhs, rhs, sizes, **kw):
        f8 = jnp.float8_e4m3fn
        return plain(lhs.astype(f8).astype(lhs.dtype),
                     rhs.astype(f8).astype(rhs.dtype), sizes, **kw)

    seen = {}
    for experts in ("bfloat16", "float8"):
        model = _model(seed, dtype="bfloat16", num_hidden_layers=6,
                       hidden_size=256, vocab_size=4096)
        monkeypatch.setattr(dropless, "grouped_matmul",
                            rounded if experts == "float8" else plain)
        eng = Engine(model, max_batch=2, num_blocks=24, block_size=16,
                     prefill_buckets=(64,), decode_chunk=8)
        eng.add_request(GenRequest(prompt_ids=prompt, max_new_tokens=96))
        out = list(eng.run_to_completion()[0].output_ids)
        checker = reference.TokenChecker(_reference_config(model.config),
                                         128, 96)
        capsys.readouterr()
        reading = checker.worst_gap_ulps(
            builder.top_weights(model),
            lambda i: builder.layer_weights(model, i),
            model.config.num_hidden_layers, prompt, out)
        seen[experts] = (reading, json.loads(capsys.readouterr().out))
    reading, line = seen["bfloat16"]
    assert reading <= checker.ULPS and line["over_limit"] <= 2
    assert line["set_aside"] == 24 and line["positions"] == 96
    _, low = seen["float8"]
    assert low["over_limit"] >= line["over_limit"] + 4
    assert low["largest_ulps"] > checker.ULPS


# ---------------------------------------------------- the cell's rehearsal --

def test_new_cell_rehearses():
    """``serve_moe_mla_sat``'s files walk the benchmark's control flow on the
    CPU (about half a minute): the cell is guarded off the chip."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "serve_moe_mla_sat", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["failed"] == 0
