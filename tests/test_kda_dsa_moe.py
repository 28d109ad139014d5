"""The delta-rule / learned-sparse attention, sparse-expert decoder
(``models/kda_dsa_moe.py``: GLM-5.3-Flash's layer equations) against the
benchmark's plain reference, on the CPU at a tiny size:

(a) the KDA kernels (chunked prefill in the interpreter, decode) against
    the literal token-by-token recurrence;
(b) the indexer's selection and the sparse attention kernels against their
    references and the reference model's selection;
(c) Sinkhorn, the clamped SwiGLU, the shares of an expert layer;
(d) the model's forward and ``serving.Engine``'s prefill + decode against
    the reference's logits, and three wrong readings of the equations that
    the comparison refuses;
(e) the hybrid cache of latent pages and KDA slots.

Tolerances: float32 everywhere; 2e-5 on logits of magnitude ~1 is the
float32 error of a few layers computed in another order (the reference runs
the recurrence token by token, the program's prefill in chunks);
kernel-level checks are tighter where the arithmetic is the same.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.models import kda_dsa_moe_decoder as builder
from benchmarks.reference import kda_dsa_moe_decoder as reference
from paddle_tpu import obs, serving
from paddle_tpu.framework import flags
from paddle_tpu.incubate.moe.dropless import DroplessMoE
from paddle_tpu.kernels import dsa_attention as dsa
from paddle_tpu.kernels import kda
from paddle_tpu.kernels.swiglu import swiglu
from paddle_tpu.models.kda_dsa_moe import (KdaDsaMoeForCausalLM,
                                           kda_dsa_moe_tiny_config, sinkhorn)
from paddle_tpu.models.mla_moe import MlaMoeForCausalLM, mla_moe_tiny_config
from paddle_tpu.serving import Engine, GenRequest
from paddle_tpu.serving.cache_backend import (HybridCache, KdaState, LatentKV,
                                              make_backend)

TOL = 2e-5


@pytest.fixture
def interpret_kernels():
    before = flags.get_flag("pallas_interpret")
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": before})


def _model(seed=3, **overrides):
    paddle.seed(seed)
    return KdaDsaMoeForCausalLM(kda_dsa_moe_tiny_config(**overrides))


def _reference_config(cfg):
    keys = ("rms_norm_eps", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
            "index_n_heads", "index_head_dim", "index_topk", "index_kpool",
            "num_attention_heads", "qk_nope_head_dim", "v_head_dim",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "swiglu_limit")
    return {**{k: getattr(cfg, k) for k in keys},
            "experts_held_first": cfg.experts_held[0],
            "linear_attn_config": {"num_heads": cfg.linear_num_heads,
                                   "head_dim": cfg.linear_head_dim,
                                   "gate_lower_bound": cfg.gate_lower_bound},
            "assumed": {"index_rope_dim": cfg.index_rope_dim,
                        "index_rope_theta": cfg.index_rope_theta}}


def _reference_logits(model, ids, readings=None):
    """The reference's logits at every position of ``ids``, the sequence
    padded after its end to a multiple of 128 (causal: no position reads
    what follows it)."""
    cfg = model.config
    n = len(ids)
    padded = np.zeros((-(-n // 128) * 128,), np.int32)
    padded[:n] = ids
    layers = [builder.layer_weights(model, i)
              for i in range(cfg.num_hidden_layers)]
    rc = _reference_config(cfg)
    logits, _, _ = jax.jit(lambda top, layers, ids: reference.forward(
        rc, top, layers, ids, readings=readings))(
            builder.top_weights(model), layers, padded)
    return np.asarray(logits)[:n]


def _unit(rng, *shape):
    x = rng.normal(size=shape)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


# ------------------------------------------------------------ (a) KDA ------

@pytest.mark.parametrize("L,n_valid,lower", [
    (128, 128, -5.0),      # every decay near e^-5: the factors' range
    (192, 130, -1.0),      # a prompt two past a chunk's edge, then padding
])
def test_kda_prefill_kernel_against_the_recurrence(L, n_valid, lower,
                                                   interpret_kernels):
    """The chunked WY / UT form (chunks of 64, sub-chunks of 16) against the
    literal recurrence: outputs of the real positions and the final state.
    1e-5: float32 products of unit keys in another order."""
    rng = np.random.default_rng(L)
    H, d = 2, 128
    q = jnp.asarray(_unit(rng, L, H, d) / np.sqrt(d), jnp.float32)
    k = jnp.asarray(_unit(rng, L, H, d), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, H, d)), jnp.float32)
    sharp = 8.0 if lower == -5.0 else 1.0
    g = jnp.asarray(lower / (1 + np.exp(-sharp - rng.normal(size=(L, H, d)))),
                    jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(L, H)))),
                       jnp.float32)
    real = (jnp.arange(L) < n_valid)[:, None]
    k, v = (jnp.where(real[..., None], t, 0.0) for t in (k, v))
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    want_o, want_s = kda._prefill_reference(q, k, v, g, beta)
    got_o, got_s = kda.kda_prefill(q, k, v, g, beta, n_valid)
    assert float(jnp.abs(got_o[:n_valid] - want_o[:n_valid]).max()) < 1e-5
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5


@pytest.mark.parametrize("path", ["xla", "pallas_interpret"])
def test_kda_decode_continues_the_recurrence(path, request):
    """Decode steps from a prefilled state equal the recurrence run on; an
    inactive slot's state comes back bit for bit and its output is 0."""
    if path == "pallas_interpret":
        request.getfixturevalue("interpret_kernels")
    rng = np.random.default_rng(7)
    L, n, H, d = 24, 4, 8, 128
    q = jnp.asarray(_unit(rng, L + n, H, d) / np.sqrt(d), jnp.float32)
    k = jnp.asarray(_unit(rng, L + n, H, d), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L + n, H, d)), jnp.float32)
    g = jnp.asarray(-5.0 / (1 + np.exp(-rng.normal(size=(L + n, H, d)))),
                    jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(L + n, H)))),
                       jnp.float32)
    want_o, _ = kda._prefill_reference(q, k, v, g, beta)
    _, s = kda._prefill_reference(q[:L], k[:L], v[:L], g[:L], beta[:L])
    state = jnp.stack([s, s * 0 + 3.0])            # slot 1 idle
    active = jnp.asarray([True, False])
    for t in range(L, L + n):
        pick = lambda a: jnp.stack([a[t], a[t]])   # noqa: E731
        o, state = kda.kda_decode(state, pick(q), pick(k), pick(v), pick(g),
                                  pick(beta), active)
        assert float(jnp.abs(o[0] - want_o[t]).max()) < 1e-5, t
        assert not np.asarray(o[1]).any()
    assert bool((state[1] == 3.0).all())


# --------------------------------------------------- (b) the selection -----

def test_selection_matches_the_reference_models(interpret_kernels):
    """The indexer of the tiny model's DSA layer through ``dsa_select``
    against the reference's full score matrix and exact top-k: queries with fewer complete groups than the top (16 groups, the
    first 64 positions) select them all, later ones exactly the top 16; the
    pooled keys are means of four positions."""
    model = _model(3)
    attn = model.layers[2].self_attn
    cfg = model.config
    L = 512
    x = jnp.asarray(np.random.default_rng(0).normal(size=(L, 128)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, _, qi, ki, w = attn._project(x, jnp.arange(L))
        kbar = dsa.pool_keys(ki, L)
        chosen = dsa.select_groups(qi, w, kbar, L, cfg.top_groups)
        weights = {k: v.astype(jnp.float32) for k, v in
                   builder.layer_weights(model, 2).items()}
        want, _ = reference.selection(
            x, weights, n_idx=cfg.index_n_heads, d_idx=cfg.index_head_dim,
            rot=cfg.index_rope_dim, theta=cfg.index_rope_theta,
            top=cfg.top_groups, eps=cfg.rms_norm_eps)
    t = np.arange(L)[:, None]
    got = np.asarray(chosen) > 0.5
    assert (got == np.asarray(want)).all()
    counts = got.sum(1)
    assert (counts == np.minimum(t[:, 0] // 4, cfg.top_groups)).all()
    assert np.allclose(np.asarray(kbar), np.asarray(ki).reshape(-1, 4, 128)
                       .mean(1), atol=1e-6)


def _prefill_case(L, n, H=8, d=128, top=16, seed=1):
    rng = np.random.default_rng(seed)
    chosen = dsa._select_reference(
        jnp.asarray(rng.normal(size=(L, 2, 128)), jnp.float32),
        jnp.asarray(rng.normal(size=(L, 2)), jnp.float32),
        dsa.pool_keys(jnp.asarray(rng.normal(size=(L, 128)), jnp.float32),
                      n), n, top)
    q, k, v = (jnp.asarray(rng.normal(size=(H, L, d)), jnp.float32)
               for _ in range(3))
    return q, k, v, chosen


@pytest.mark.parametrize("L,n", [
    (512, 437),       # one query and one key block, the prompt ending inside
    (2048, 1337),     # four of each; the last query block wholly past it
    (2048, 2048),     # four of each, every block live
])
def test_prefill_attention_kernel_against_its_reference(L, n,
                                                        interpret_kernels):
    """``dsa_prefill_attn`` over a prompt of ``n`` of ``L`` positions, each
    query limited to its top 16 groups and tail, against the XLA mask; and
    its 512-query blocks against 128-query blocks, which add up each row's
    key blocks in the same order: the rows agree to float32's rounding."""
    q, k, v, chosen = _prefill_case(L, n)
    want = dsa._prefill_reference(q, k, v, chosen, n, 0.1)
    got = dsa.sparse_prefill_attention(q, k, v, chosen, n, 0.1)
    assert float(jnp.abs(got[:, :n] - want[:, :n]).max()) < 1e-5
    narrow = dsa._pallas_prefill(q, k, v, chosen, n, 0.1, interpret=True,
                                 bq=128)
    assert float(jnp.abs(got[:, :n] - narrow[:, :n]).max()) < 1e-6
    assert not np.asarray(got[:, -(-n // 512) * 512:]).any()


def test_prefill_attention_programs_name_their_query_tile():
    """``dsa.programs{kernel=dsa_prefill_attn}`` carries the query tile a
    traced program took: 512 rows from the wrapper, what a caller of the
    kernel asks for otherwise; one count a trace, none on XLA's path."""
    def count(bq):
        return obs.registry().counter("dsa.programs", kernel="dsa_prefill_attn",
                                      bq=bq).value

    q, k, v, chosen = _prefill_case(512, 300, H=2)
    before = count(512), count(128)
    attend = jax.jit(functools.partial(dsa.sparse_prefill_attention,
                                       interpret=True), static_argnums=5)
    attend.lower(q, k, v, chosen, 300, 0.1)
    jax.jit(functools.partial(dsa._pallas_prefill, interpret=True, bq=128),
            static_argnums=5).lower(q, k, v, chosen, 300, 0.1)
    dsa.sparse_prefill_attention(q[:, :256], k[:, :256], v[:, :256],
                                 chosen[:256, :64], 200, 0.1)   # XLA's path
    assert (count(512), count(128)) == (before[0] + 1, before[1] + 1)
    assert obs.registry().snapshot()[
        "dsa.programs{bq=512,kernel=dsa_prefill_attn}"]["labels"] == {
            "kernel": "dsa_prefill_attn", "bq": 512}


@pytest.mark.parametrize("path", ["xla", "pallas_interpret"])
def test_sparse_decode_reads_the_selection_and_the_tail(path, request):
    """Three slots: 200 tokens (more complete groups than the top 16), none
    (inactive), 37 (fewer); positions 199 and 36 leave tails of 3 and 0
    earlier tokens in their own group.  The kernel attends exactly those
    tokens: dense softmax over them, gathered by hand, agrees."""
    if path == "pallas_interpret":
        request.getfixturevalue("interpret_kernels")
    rng = np.random.default_rng(2)
    B, H, rank, bs, NB, MB = 3, 8, 256, 32, 40, 8
    pool = jnp.asarray(rng.normal(size=(NB, 2 * bs, rank // 2)), jnp.float32)
    ipool = jnp.asarray(rng.normal(size=(NB, bs // 4, 128)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, NB))[:B * MB].reshape(
        B, MB), jnp.int32)
    lengths = jnp.asarray([200, 0, 37], jnp.int32)
    qi = jnp.asarray(rng.normal(size=(B, 2, 128)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(B, 2)), jnp.float32)
    groups, count, scored = dsa.decode_select(qi, wi, ipool, tbl, lengths, 16)
    assert list(np.asarray(count)) == [17, 0, 10]
    assert list(np.asarray(scored)) == [49, 0, 9]
    ql = jnp.asarray(rng.normal(size=(B, H, rank)), jnp.float32)
    got = dsa.sparse_decode_attention(ql, pool, tbl, lengths, groups, count,
                                      0.1)
    c = np.asarray(dsa.gather_latent(pool, tbl))
    for b in (0, 2):
        pos = int(lengths[b]) - 1
        toks = [t for t in range(pos + 1) if t // 4 == pos // 4] + [
            4 * int(gr) + i for gr in np.asarray(groups[b, 1:count[b]])
            for i in range(4)]
        rows = c[b, sorted(toks)]
        s = np.asarray(ql[b]) @ rows.T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows
        assert np.abs(np.asarray(got[b]) - want).max() < 1e-5, b
    assert not np.asarray(got[1]).any()


def test_pair_counts_in_closed_form():
    for n, top in ((437, 16), (3, 16), (64, 16), (65, 2)):
        sel, scored = dsa.prefill_pairs(n, top)
        assert int(sel) == sum(4 * min(t // 4, top) + t % 4 + 1
                               for t in range(n))
        assert int(scored) == sum(t // 4 for t in range(n))


# ------------------------------------------- (c) mHC, clamp, expert shares --

def test_sinkhorn_is_doubly_stochastic():
    """20 rounds from logits drawn normal(0, 1): every row and column of
    each 4 x 4 mix sums to 1 within 1e-5 (columns within hc_eps, the last
    normalisation's)."""
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(64, 4, 4)),
                         jnp.float32)
    m = np.asarray(sinkhorn(logits, 20, 1e-6))
    assert (m > 0).all()
    assert np.abs(m.sum(-2) - 1).max() < 2e-6
    assert np.abs(m.sum(-1) - 1).max() < 1e-5


@pytest.mark.parametrize("block", [8, 16])
def test_reference_mixes_streams_a_block_at_a_time(block):
    """The reference's hyper-connection a block of positions at a time (how
    a 34k-token check fits beside the weights) is its whole-sequence mix,
    around a sublayer that reads every position.  Float32 on both sides, the
    same products grouped by rows: 1e-5."""
    rng = np.random.default_rng(0)
    s, n, c = 64, 4, 32
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    X = f32(s, n, c)
    w = {"hc.phi": 0.1 * f32(n * c, n * (n + 2)), "hc.alpha": f32(3),
         "hc.bias": f32(2 * n), "hc.res_bias": f32(n * n)}
    mix = f32(c, c)

    def sublayer(h):
        return 0.1 * jnp.cumsum(h @ mix, 0), None

    def run(b):
        return np.asarray(reference.hyper(
            X, w, "hc", n=n, iters=20, eps_hc=1e-6, eps=1e-6,
            sublayer=sublayer, block=b)[0])

    whole = run(s)
    assert np.abs(run(block) - whole).max() < 1e-5 * np.abs(whole).max()


def test_clamped_swiglu():
    x = jnp.asarray([[20.0, -3.0, 0.5, 30.0], [-20.0, 2.0, 12.0, -15.0]])
    got = np.asarray(swiglu(x, limit=10.0))
    gate = np.minimum(np.asarray(x[:, :2]), 10.0)
    up = np.clip(np.asarray(x[:, 2:]), -10.0, 10.0)
    assert np.allclose(got, gate / (1 + np.exp(-gate)) * up, rtol=1e-6)
    assert np.allclose(np.asarray(swiglu(x)),
                       np.asarray(jax.nn.silu(x[:, :2]) * x[:, 2:]))


def test_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The two chips' shares of 8 clamped experts (4 each), the shared
    expert counted once, sum to the layer that holds all 8."""
    def layer(held):
        paddle.seed(21)
        return DroplessMoE(128, 64, 8, 2, num_shared=1, scale=2.5,
                           dtype="float32", held=held, swiglu_limit=0.05)
    full, a, b = layer(None), layer((0, 4)), layer((4, 4))
    b.w_gate_up._data = full.w_gate_up._data[4:]
    b.w_down._data = full.w_down._data[4:]
    a.w_gate_up._data = full.w_gate_up._data[:4]
    a.w_down._data = full.w_down._data[:4]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 9, 128)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, _ = full(x)
        ya, _ = a(x)
        yb, _ = b(x)
        shared = swiglu(x @ full.shared_gate_up._data, limit=0.05) \
            @ full.shared_down._data
    got = np.asarray(ya._data) + np.asarray(yb._data) - np.asarray(shared)
    assert np.abs(got - np.asarray(y._data)).max() < 1e-5


# ------------------------------------- (d) the model, the engine, readings --

@pytest.fixture(scope="module")
def served_forward():
    """The tiny model's logits over a 300-token prompt: the DSA layer's
    selection binds from position 68 on, the KDA states carry across 300
    tokens."""
    model = _model(3)
    ids = np.random.default_rng(3).integers(1, 512, size=300).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda x: model(x)._data)(jnp.asarray(ids[None]))
    return model, ids, np.asarray(got)[0]


def test_forward_matches_reference(served_forward):
    model, ids, got = served_forward
    assert np.abs(got - _reference_logits(model, ids)).max() < TOL


@pytest.mark.parametrize("reading", ["kda_state_bf16", "no_sinkhorn",
                                     "dense_attention"])
def test_a_wrong_reading_fails(reading, served_forward):
    """The comparison sees each of three wrong readings of the equations:
    the KDA state held in bfloat16 between tokens, the residual mix without
    Sinkhorn's normalisation, the DSA layer attending every earlier token
    instead of its selection.  Each moves the logits by far more than the
    tolerance the program meets."""
    model, ids, got = served_forward
    readings = {"kda_state_bf16": {"state_dtype": jnp.bfloat16},
                "no_sinkhorn": {"sinkhorn": False},
                "dense_attention": {"dense": True}}[reading]
    wrong = _reference_logits(model, ids, readings)
    assert np.abs(got - wrong).max() > 20 * TOL


def _serve_with_logits(model, requests, monkeypatch, **engine):
    """Requests one after the other, each alone, with the logits every
    program sampled from (``test_swa_moe.py``'s recorder)."""
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


def test_engine_logits_match_reference(monkeypatch):
    """Prefill into the latent and index pools and the KDA slots, then
    decode chunks through them (blocks of 32 crossed, the selection binding,
    groups completed by decode), against the reference's full forward at
    every emitted position; the counters count what ran."""
    model = _model(7)
    rng = np.random.default_rng(1)
    first = rng.integers(1, 512, size=90).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng, outs, logits = _serve_with_logits(
            model, [("first", first, 13)], monkeypatch, max_batch=2,
            num_blocks=24, block_size=32, prefill_buckets=(128,),
            decode_chunk=4)
    be = eng.backend
    assert isinstance(be, HybridCache) and isinstance(be.pages, LatentKV)
    assert isinstance(be.state, KdaState)
    for rid, prompt in (("first", first),):
        want = _reference_logits(model, np.concatenate(
            [prompt, np.asarray(outs[rid], np.int32)]))[len(prompt) - 1:-1]
        assert logits[rid].shape == want.shape
        assert np.abs(logits[rid] - want).max() < TOL, rid
        assert outs[rid] == list(want.argmax(-1)), rid
    assert be.pages._ref == {} and be.state._live == {}
    snap = obs.registry().snapshot()
    assert snap["kda.prefill_tokens"]["value"] >= 90
    assert snap["dsa.decode_selected_tokens"]["value"] > 0
    assert snap["cache.kda_state_bytes_per_slot"]["value"] == \
        be.state.state_bytes_per_slot


# ------------------------------------------------------------ (e) the caches --

def test_hybrid_cache_of_latent_pages_and_kda_slots():
    """The slot ledger is the recurrent state's, ``seq_bytes`` is the pages'
    (latent and pooled keys) plus the flat per-slot state, and a latent
    model without an indexer keeps no index pool."""
    model = _model(3)
    spec = model.cache_spec()
    be = make_backend(spec, 16, 32, 2)
    assert isinstance(be, HybridCache)
    assert be.state_keys == ("latent", "index", "counters", "kda")
    dev = be.init_device(model)
    assert dev["index"][0].shape == (16, 8, 128)
    assert dev["latent"][0].shape == (16, 64, 128)
    assert dev["kda"][0]["S"].shape == (2, 2, 128, 128)
    be.acquire_slot(0)
    with pytest.raises(RuntimeError):
        be.acquire_slot(0)
    be.release_slot(0)
    with pytest.raises(RuntimeError):
        be.release_slot(0)
    flat = spec["state_bytes_per_slot"]
    per_token = 256 * 4 + 128 * 4 // 4
    assert be.seq_bytes(64) == 2 * 32 * per_token + flat
    assert be.seq_bytes(64 * 8) - be.seq_bytes(64) == 14 * 32 * per_token
    paddle.seed(0)
    kimi = MlaMoeForCausalLM(mla_moe_tiny_config())
    plain = make_backend(kimi.cache_spec(), 16, 32, 2)
    assert isinstance(plain, LatentKV) and "index" not in plain.state_keys
    assert "index" not in plain.init_device(kimi)



# ---------------------------------------------------- (f) the cell's schedule --

@pytest.mark.parametrize("fixed", [True, False])
def test_cell_schedule_across_seeds(fixed):
    """``serve_kda_dsa_sat``'s runner gives two seeds one schedule (the same
    due times and lengths in the same order) and prompts of their own; with
    no ``schedule_seed`` each seed shuffles the same multiset, as the
    generator does."""
    from benchmarks import harness
    from benchmarks.runners import serve_steady

    _, _, traffic = harness.load_cell("serve_kda_dsa_sat")
    assert "schedule_seed" in traffic
    if not fixed:
        del traffic["schedule_seed"]
    a, b = (serve_steady._arrivals(traffic, 512, s, traffic["lead_s"], 32.0)
            for s in (2**31 + 5, 7))
    plan = [[(x.due_s, x.prompt_ids.size, x.max_new_tokens, x.judged)
             for x in r] for r in (a, b)]
    assert 0 < sum(x.judged for x in a) < len(a)
    for k in (1, 2):
        assert sorted(p[k] for p in plan[0]) == sorted(p[k] for p in plan[1])
    assert (plan[0] == plan[1]) is fixed
    assert not any(np.array_equal(x.prompt_ids, y.prompt_ids)
                   for x, y in zip(a, b))
