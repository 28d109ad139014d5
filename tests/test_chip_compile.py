"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
described (not attached) v5e at the widths the ``base`` and ``serve``
presets run: what Mosaic refuses on the chip it refuses here, at no chip
time.  Interpret-mode tests cannot see tiling, VMEM or layout refusals.

Nothing executes and nothing is timed.  The topology is described inside a
module-scoped fixture (never at import: every xdist worker imports this
file, and only one process at a time may load the TPU library), and all
the compiles live in this one file so one worker owns the library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import paddle_tpu.kernels as kernels
from paddle_tpu.kernels import adamw, decode_attention, flash_attention
from paddle_tpu.kernels import rms_norm as rms_norm_mod
from paddle_tpu.kernels import ssd_scan as ssd_mod

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# base preset: batch 3 x seq 2048, hidden 2048, intermediate 5632,
# 16 query / 8 kv heads x 128; serve preset: max_batch 16, 256 blocks of 128
B, S, HID, INTER, H, HK, D = 3, 2048, 2048, 5632, 16, 8, 128
SB, NB, BS, CTX = 16, 256, 128, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_branch(monkeypatch):
    # the wrappers ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)


def _compile(one_chip, fn, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _flash_fwd_bwd(one_chip, b, s):
    def loss(q, k, v):
        o = flash_attention.flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(F32))

    return _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                    ((b, s, H, D), BF16), ((b, s, HK, D), BF16),
                    ((b, s, HK, D), BF16))


def test_flash_fwd_bwd_resident(one_chip, kernel_branch):
    assert flash_attention._resident_ok(S, D, 2)
    text = _flash_fwd_bwd(one_chip, B, S).as_text()
    # the device line of a trace names an op by its instruction: the
    # kernels' names have to be in it (PERF.md's breakdowns read them)
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert f"jvp_{name}_" in text, name


def test_flash_fwd_bwd_streaming(one_chip, kernel_branch):
    # the longctx preset's sequence: K/V page through VMEM
    assert not flash_attention._resident_ok(16384, D, 2)
    _flash_fwd_bwd(one_chip, 1, 16384)


def test_rms_norm_fwd_bwd(one_chip, kernel_branch):
    def loss(x, w):
        return jnp.sum(rms_norm_mod.rms_norm(x, w).astype(F32))

    # the backward is jnp: keep the value, or the forward kernel is dead code
    _compile(one_chip, jax.value_and_grad(loss, argnums=(0, 1)),
             ((B, S, HID), BF16), ((HID,), BF16))


@pytest.mark.parametrize("shape,out_dtype", [
    # the benchmark's leaves (Mistral-7B widths, fp32 parameters) ...
    ((32768, 4096), None), ((4096, 28672), None), ((14336, 4096), None),
    ((4096, 6144), None),
    # ... the base preset's MLP leaf with the bf16 copy written in the pass,
    # and a norm weight, which keeps the flat [rows, 128] view
    ((HID, INTER), "bfloat16"), ((4096,), None),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_adamw_update(one_chip, shape, out_dtype):
    """A leaf the kernel can cut along its own rows goes in and comes out
    with no copy: under the (8, 128) tiling ``[r, c] -> [r*c/128, 128]`` is
    a relayout of the whole array through HBM, seven times a leaf."""
    import math
    import re

    def step(p, g, m, v, lr, t):
        out = adamw.adamw_update(p, g, m, v, lr, t, beta1=0.9, beta2=0.95,
                                 epsilon=1e-8, weight_decay=0.1,
                                 out_dtype=out_dtype)
        return out if out_dtype else out[:3]  # p_out is p_new: one buffer

    # donated as TrainStep donates them: an argument the caller keeps would
    # be copied before the aliased call whatever the kernel's layout
    z = (shape, F32)
    compiled = _compile(one_chip, step, z, z, z, z, ((), F32), ((), I32),
                        donate=(0, 2, 3))
    text = compiled.as_text()
    assert "adamw_fused" in text
    if len(shape) < 2:
        return
    n = math.prod(shape)
    moved = [m.group(0)[:120] for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:reshape|copy)\(.*", text)
        if math.prod(map(int, m.group(1).split(","))) == n]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n


_DENSE = (((SB, 1, H, D), BF16), ((SB, CTX, HK, D), BF16),
          ((SB, CTX, HK, D), BF16), ((SB,), I32))
_PAGED = (((SB, 1, H, D), BF16), ((NB, HK, BS, D), BF16),
          ((NB, HK, BS, D), BF16), ((SB, NB // SB), I32), ((SB,), I32))


@pytest.mark.parametrize("fn,shapes", [
    pytest.param(
        lambda q, k, v, n: decode_attention._pallas_decode(q, k, v, n, 0.1),
        _DENSE, id="decode_mmha"),
    pytest.param(
        lambda q, k, v, n: decode_attention._pallas_decode_fused(
            q, k, v, n, 0.1, block_k=256), _DENSE, id="decode_mmha_fused"),
    pytest.param(
        lambda q, k, v, t, n: decode_attention._pallas_paged_decode(
            q, k, v, t, n, 0.1), _PAGED, id="paged_decode"),
    pytest.param(
        lambda q, k, v, t, n: decode_attention._pallas_paged_decode_fused(
            q, k, v, t, n, 0.1), _PAGED, id="paged_decode_fused"),
])
def test_decode_kernels(one_chip, fn, shapes):
    _compile(one_chip, fn, *shapes)


def test_decode_wrappers_pick_the_fused_kernels(one_chip, kernel_branch):
    # at the serve preset's shapes the public wrappers must reach a kernel
    _compile(one_chip, decode_attention.masked_multihead_attention, *_DENSE)
    _compile(one_chip, decode_attention.paged_decode_attention, *_PAGED)


@pytest.mark.parametrize("k", [1, 4])
def test_decode_chunk_copies_no_pool(one_chip, kernel_branch, k):
    """The engine's k-step decode program at the serving cell's attention
    widths (8 kv heads x 128, blocks of 128, 32 slots): the token write
    leaves the pools in the row-major layout ``paged_decode_fused`` reads,
    so the scan carries them without a copy of a pool, per layer or around
    the loop, and needs less scratch than one pool."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=1024, hidden_size=H * D, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=H, num_key_value_heads=HK,
        max_position_embeddings=1024, dtype="bfloat16"))
    eng = Engine(model, max_batch=32, num_blocks=48, block_size=BS,
                 prefill_buckets=(BS,))
    pool = eng.backend.device["k"][0]
    assert pool.shape == (48, HK, BS, D) and pool.dtype == BF16
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._decode_dummy_args())
    compiled = eng._get_decode_fn(k).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_fused" in text
    dims = ",".join(map(str, pool.shape))
    copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(.*", text)
    assert not copies, copies[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < pool.nbytes


# the latent-attention, sparse-expert cell (Kimi-VL-A3B widths): 16 heads,
# latent 512 + 64 rotated, 64 experts of 1408 over hidden 2048; 32 slots,
# 1,536 blocks of 128 tokens = pool rows of two tokens, 1,152 wide
_LAT = (((32, 16, 512), BF16), ((32, 16, 64), BF16),
        ((1536, 64, 1152), BF16), ((32, 64), I32), ((32,), I32))


def test_latent_decode_and_token_write(one_chip, kernel_branch):
    from paddle_tpu.kernels import mla_attention

    text = _compile(one_chip, lambda ql, qr, p, t, n:
                    mla_attention.latent_decode_attention(ql, qr, p, t, n,
                                                          0.07), *_LAT)
    assert "mla_paged_decode" in text.as_text()
    # the token write updates the donated pool in place: no scratch
    pool, tbl, lens = _LAT[2:]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (pool, tbl, lens, ((32, 576), BF16))]
    write = jax.jit(lambda p, t, n, new: mla_attention.write_latent_token(
        p, t, n, new, 512), donate_argnums=(0,)).lower(*args).compile()
    assert write.memory_analysis().temp_size_in_bytes < 2 ** 20


def _latent_decode_kernel(one_chip, batch, table):
    """``mla_paged_decode``'s Mosaic payload lowered at the cell's widths,
    with a fresh closure: ``(payload, its module as text)``."""
    import base64
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    from paddle_tpu.kernels import mla_attention

    shapes = (((batch, 16, 512), BF16), ((batch, 16, 64), BF16),
              ((1536, 64, 1152), BF16), ((batch, table), I32), ((batch,), I32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(lambda ql, qr, p, t, n: mla_attention.
                   latent_decode_attention(ql, qr, p, t, n, 0.07)).lower(
                       *args).as_text()
    (payload,) = re.findall(r'tpu_custom_call\(.*backend_config = "([^"]*)"',
                            text)
    body = re.search(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", payload).group(1)
    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = str(ir.Module.parse(base64.b64decode(body)))
    return payload, module


def test_latent_decode_kernel_does_not_grow(one_chip, kernel_branch):
    """The kernel's code is the same whatever the batch or the table's
    width: every walk over slots and blocks is a loop in the kernel, none a
    Python loop that the trace unrolls (an unrolled walk is paid again in
    tracing and lowering at every start, warm compile cache or not).  Only
    the sizes differ, so the modules are equal once numbers are masked.  The
    payload is MLIR bytecode with the ops' source locations, whose numbers
    take one to a few bytes by their value and whose equal numbers and
    locations are stored once, so its length moves by a percent or two
    between sizes; one more unrolled block or slot would add a kilobyte."""
    import re

    def masked(module):
        return re.sub(r"\d+", "0", module)

    # the payload holds the caller's line: lower all from one line
    (p37, m37), again, (p74, m74), (p64, m64) = [
        _latent_decode_kernel(one_chip, b, t)
        for b, t in ((32, 37), (32, 37), (32, 74), (64, 37))]
    assert again == (p37, m37)
    assert masked(m37) == masked(m74) == masked(m64)
    for p in (p74, p64):
        assert abs(len(p) - len(p37)) < len(p37) // 20


@pytest.mark.parametrize("b,s", [(1, 256), (4, 4096)])
def test_latent_prefill_attention(one_chip, kernel_branch, b, s):
    from paddle_tpu.kernels import mla_attention

    text = _compile(
        one_chip, lambda qn, qr, kn, kr, v: mla_attention.
        mla_prefill_attention(qn, qr, kn, kr, v, 0.07),
        ((b, s, 16, 128), BF16), ((b, s, 16, 64), BF16),
        ((b, s, 16, 128), BF16), ((b, s, 64), BF16), ((b, s, 16, 128), BF16))
    assert "mla_prefill_attn" in text.as_text()


@pytest.mark.parametrize("rows", [192, 1536, 98304, 6144, 12288],
                         ids=["decode", "prefill_256", "prefill_4x4096",
                              "prefill_1024", "prefill_2048"])
def test_grouped_matmul(one_chip, kernel_branch, rows):
    """Both projections of an expert layer at a decode step's rows (32
    slots x 6), one short prompt's, the widest prefill rung's, and one
    prompt of the 1024 and of the 2048 bucket (the cell's common calls:
    512-row tiles walked in 128-row blocks)."""
    from paddle_tpu.kernels import grouped_matmul

    for k, n in ((2048, 2816), (1408, 2048)):
        text = _compile(one_chip, grouped_matmul.grouped_matmul,
                        ((rows, k), BF16), ((64, k, n), BF16), ((64,), I32))
        assert "moe_grouped_mm" in text.as_text()


def test_latent_decode_chunk_copies_no_pool(one_chip, kernel_branch):
    """The engine's decode program over the latent cache at the cell's
    attention and expert widths (two layers, a small vocabulary): the
    token write leaves the pools as ``mla_paged_decode`` reads them, so no
    pool is copied, and both new kernels are in the program."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = MlaMoeForCausalLM(MlaMoeConfig(
        vocab_size=1024, num_hidden_layers=2, n_routed_experts=8,
        intermediate_size=512, max_position_embeddings=1024))
    eng = Engine(model, max_batch=32, num_blocks=48, block_size=BS,
                 prefill_buckets=(BS,))
    pool = eng.backend.device["latent"][0]
    assert pool.shape == (48, BS // 2, 1152) and pool.dtype == BF16
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._decode_dummy_args())
    compiled = eng._get_decode_fn(4).lower(*args).compile()
    text = compiled.as_text()
    for name in ("mla_paged_decode", "moe_grouped_mm", "moe.experts",
                 "mla.decode_attn"):
        assert name in text, name
    dims = ",".join(map(str, pool.shape))
    copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(.*", text)
    assert not copies, copies[:2]


# 64 query heads over 4 (full) or 8 (window) KV heads, keys 192 and values
# 128 wide; 32 slots, 4,608 blocks of 128 tokens, tables of 256 blocks
@pytest.mark.parametrize("s,lengths", [
    pytest.param(1024, False, id="1024"),
    pytest.param(16384, False, id="16384"),
    pytest.param(8192, True, id="8192-n_valid")])
@pytest.mark.parametrize("window,hk", [(None, 4), (128, 8)],
                         ids=["full", "window"])
def test_gqa_prefill_attention(one_chip, kernel_branch, window, hk, s,
                               lengths):
    """Both layer kinds' prefill at the cell's shortest and longest bucket:
    64 query heads in groups of 16 or 8 a program, the score as a 64-wide and
    a 128-wide product, the window's band of two key blocks; and with the
    prompt's true length as a scalar prefetch the body reads."""
    from paddle_tpu.kernels import gqa_attention

    def attend(q, k, v, b, n=None):
        return gqa_attention.gqa_prefill_attention(
            q, k, v, 0.07, window=window, sinks=b if window else None,
            n_valid=n)

    shapes = [((1, s, 64, 192), BF16), ((1, s, hk, 192), BF16),
              ((1, s, hk, 128), BF16), ((64,), F32)]
    text = _compile(one_chip, attend, *shapes,
                    *([((1,), I32)] if lengths else []))
    assert "gqa_prefill_attn" in text.as_text()


_GQA_POOLS = (((4608, 4, 192, 128), BF16), ((4608, 4, 128, 128), BF16),
              ((32, 256), I32), ((32,), I32))


def test_gqa_paged_decode_and_token_write(one_chip, kernel_branch):
    from paddle_tpu.kernels import gqa_attention

    text = _compile(one_chip, lambda q, kp, vp, t, n:
                    gqa_attention.gqa_paged_decode_attention(q, kp, vp, t, n,
                                                             0.07),
                    ((32, 64, 192), BF16), *_GQA_POOLS)
    assert "gqa_paged_decode" in text.as_text()
    # the token write updates the donated pools in place: no scratch
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (*_GQA_POOLS, ((32, 4, 192), BF16),
                         ((32, 4, 128), BF16))]
    write = jax.jit(gqa_attention.write_kv_token,
                    donate_argnums=(0, 1)).lower(*args).compile()
    assert write.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_window_full_decode_chunk_copies_no_pool(one_chip, kernel_branch):
    """The engine's decode program over pages and rings at the cell's
    attention and expert widths (a full and a window expert layer after the
    dense one, a small vocabulary): the token write leaves the pools as
    ``gqa_paged_decode`` reads them, so no pool is copied, and the kernels
    and scopes are in the program."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.models.swa_moe import SwaMoeConfig, SwaMoeForCausalLM
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = SwaMoeForCausalLM(SwaMoeConfig(
        vocab_size=1024, num_hidden_layers=3, hybrid_layer_pattern=(0, 1, 0),
        moe_layer_freq=(0, 1, 1), intermediate_size=512, n_routed_experts=32,
        experts_held=(0, 2), max_position_embeddings=1024))
    eng = Engine(model, max_batch=32, num_blocks=48, block_size=BS,
                 prefill_buckets=(BS,))
    pool = eng.backend.device["k"][0]
    assert pool.shape == (48, 4, BS * 3 // 2, 128) and pool.dtype == BF16
    assert eng.backend.device["window"][0]["k"].shape == (32, 8, 128, 192)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._decode_dummy_args())
    text = eng._get_decode_fn(4).lower(*args).compile().as_text()
    for name in ("gqa_paged_decode", "moe_grouped_mm", "moe.experts",
                 "attn.full", "attn.window"):
        assert name in text, name
    for p in (pool, eng.backend.device["v"][0]):
        dims = ",".join(map(str, p.shape))
        copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(.*", text)
        assert not copies, copies[:2]


def _kda_dsa_kernels():
    from paddle_tpu.kernels import dsa_attention as dsa
    from paddle_tpu.kernels import kda

    L, G, B, H, d = 1024, 256, 32, 64, 128
    return {
        "kda_decode": (lambda s, q, k, v, g, b, a: kda.kda_decode(
            s, q, k, v, g, b, a), [((B, H, d, d), F32)] + [((B, H, d), BF16)]
            * 3 + [((B, H, d), F32), ((B, H), F32), ((B,), jnp.bool_)]),
        "kda_prefill": (lambda q, k, v, g, b, n: kda.kda_prefill(
            q, k, v, g, b, n), [((L, 8, d), BF16)] * 3
            + [((L, 8, d), F32), ((L, 8), F32), ((), I32)]),
        "dsa_select": (lambda q, w, k, n: dsa.select_groups(q, w, k, n, 128),
                       [((L, 32, d), BF16), ((L, 32), F32), ((G, d), BF16),
                        ((), I32)]),
        "dsa_prefill_attn": (
            lambda q, k, v, c, n: dsa.sparse_prefill_attention(
                q, k, v, c, n, 0.0625),
            [((8, L, 256), BF16)] * 3 + [((L, G), BF16), ((), I32)]),
        "dsa_prefill_attn_32k": (
            lambda q, k, v, c, n: dsa.sparse_prefill_attention(
                q, k, v, c, n, 0.0625),
            [((8, 32768, 256), BF16)] * 3 + [((32768, 8192), BF16),
                                             ((), I32)]),
        "dsa_sparse_decode": (
            lambda q, p, t, n, g, c: dsa.sparse_decode_attention(
                q, p, t, n, g, c, 0.0625),
            [((B, H, 512), BF16), ((512, 256, 256), BF16), ((B, 64), I32),
             ((B,), I32), ((B, 513), I32), ((B,), I32)]),
    }


@pytest.mark.parametrize("name", ["kda_decode", "kda_prefill", "dsa_select",
                                  "dsa_prefill_attn", "dsa_prefill_attn_32k",
                                  "dsa_sparse_decode"])
def test_kda_dsa_kernels(one_chip, kernel_branch, name):
    """The KDA and DSA kernels at the GLM-5.3-Flash cell's widths (64 heads
    of 128 and a float32 state; 64 heads of 256 over a 512-wide latent, an
    indexer of 32 x 128, 513 groups a slot), a 1,024-token prompt, and the
    DSA prefill attention at the cell's largest bucket, 32,768: what the
    chip's compiler refuses (a DMA of 2 rows of a bf16 array, a lane-padded
    operand, a tile past the fast memory) it refuses here."""
    fn, shapes = _kda_dsa_kernels()[name]
    compiled = _compile(one_chip, fn, *shapes)
    assert name.removesuffix("_32k") in compiled.as_text()


def test_ssd_scan(one_chip):
    # ssd_8b_config: 64 heads, state 128, head dim 64, chunk 128; seq 2048
    G, T, P, N, chunk = 64, 2048, 64, 128, 128

    def fn(x, b, c, la):
        return ssd_mod.ssd_scan(x, b, c, la, chunk=chunk)

    _compile(one_chip, fn, ((G, T, P), F32), ((G, T, N), F32),
             ((G, T, N), F32), ((G, T), F32))


def test_emitted_kernels_refused_at_base_widths(topo):
    """kernels/emit.py's backward keeps every operand in one VMEM block and
    its matmuls accumulate in the operand dtype: at the base preset's
    widths (rows 3 x 2048, bf16) the compiler refuses all three sites, and
    the refusal takes the transformer's own fuse-admission-rejected route,
    in the compiler's words, with nothing accepted."""
    from paddle_tpu.analysis.fusion_transform import plan_transform
    from paddle_tpu.kernels import emit
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=32000, hidden_size=HID,
                      intermediate_size=INTER, num_hidden_layers=12,
                      num_attention_heads=H, num_key_value_heads=HK,
                      dtype="bfloat16", param_dtype="float32")
    cands = [{"name": f"region:{site}", "pattern": s.pattern,
              "bytes_saved": 1 << 20, "source": (s.match_sources or ("",))[0],
              "op_hints": list(s.match_hints)}
             for site, s in emit.SITES.items()]
    plan = plan_transform(cands, shapes=emit.llama_site_shapes(cfg, B * S),
                          device=topo.devices[0], verify=False)
    assert not plan.accepted, plan.describe()
    refused = plan.report.by_code("fuse-admission-rejected")
    assert {f.where for f in refused} == set(emit.SITES), plan.describe()
    words = " ".join(f.message for f in refused)
    assert "Expected matmul acc to be 32-bit" in words
    assert "vmem" in words
