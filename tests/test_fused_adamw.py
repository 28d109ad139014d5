"""PR 6 tentpole tests: fused single-pass AdamW kernel (interpret mode) and
the ZeRO-1 sharded weight update (``Optimizer.shard_update``).

Parity contract (what is bit-provable on this backend, and why):

- kernel vs. jitted reference: the m/v moment outputs are bit-exact on EVERY
  shape; the full (p, m, v) tuple is bit-exact on shapes XLA compiles as a
  single fusion.  On large shapes XLA splits the REFERENCE chain into
  several fusions and re-materializes ``v_new`` inside the p-step fusion
  with different FMA contraction than the ``v_new`` it returns — the
  reference is then self-inconsistent at the 1-ulp level, so params are
  compared with a 1-ulp budget there (the kernel is the self-CONSISTENT
  one: it reads the same v it writes).
- sharded vs. unsharded: Adam (wd=0) is bit-exact end-to-end across steps;
  AdamW's decay multiply sits at an fmsub contraction site whose placement
  shifts under GSPMD partitioning, so params carry sub-ulp-of-update noise
  while the m/v state stays bit-exact.  The shard -> replicate all-gather
  itself is lossless (fp32 round-trip exact).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.framework import flags
from paddle_tpu.framework.tensor import Parameter
from paddle_tpu.kernels.adamw import (NATIVE_BLOCK, adamw_reference,
                                      adamw_update, native_view)

HYP = dict(beta1=0.9, beta2=0.999, epsilon=1e-8)
LR = 1e-3
WD = 0.01

EXACT_SHAPES = [(8,), (257,), (33, 7), (8, 128)]
SPLIT_FUSION_SHAPES = [(130, 257), (256, 384), (512, 512)]


def _rand_state(shape, seed):
    rng = np.random.default_rng(seed)
    sign = np.where(rng.random(shape) < 0.5, -1.0, 1.0)
    p = ((0.5 + rng.random(shape)) * sign).astype(np.float32)
    g = rng.standard_normal(shape).astype(np.float32)
    m = (0.1 * rng.standard_normal(shape)).astype(np.float32)
    v = (0.01 * rng.random(shape)).astype(np.float32)
    return (jnp.asarray(p), jnp.asarray(g), jnp.asarray(m), jnp.asarray(v))


def _ulp_diff(a, b):
    """Max distance in fp32 representation steps (monotonic int mapping)."""
    def key(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return int(np.abs(key(a) - key(b)).max()) if np.size(a) else 0


def _ref_jit(**hyp):
    return jax.jit(lambda p, g, m, v, lr, step:
                   adamw_reference(p, g, m, v, lr, step, **hyp))


def _run_both(shape, seed=0, **hyp):
    p, g, m, v = _rand_state(shape, seed)
    lr = jnp.float32(LR)
    step = jnp.int32(3)
    ref = _ref_jit(**hyp)(p, g, m, v, lr, step)
    fused = adamw_update(p, g, m, v, lr, step, interpret=True, **hyp)
    return ref, fused


WD_MODES = [
    pytest.param(dict(weight_decay=0.0), id="no_decay"),
    pytest.param(dict(weight_decay=WD, decoupled=True), id="adamw"),
    pytest.param(dict(weight_decay=WD, decoupled=False), id="adam_l2"),
    pytest.param(dict(weight_decay=WD, decoupled=True, apply_decay=False),
                 id="decay_excluded"),
]


@pytest.mark.parametrize("wd_mode", WD_MODES)
@pytest.mark.parametrize("shape", EXACT_SHAPES, ids=str)
def test_kernel_bit_exact_single_fusion_shapes(shape, wd_mode):
    (rp, rm, rv), (fp, fm, fv, _) = _run_both(shape, seed=hash(shape) % 997,
                                              **HYP, **wd_mode)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))
    np.testing.assert_array_equal(np.asarray(rp), np.asarray(fp))


@pytest.mark.parametrize("shape", SPLIT_FUSION_SHAPES, ids=str)
def test_kernel_moments_exact_params_1ulp_split_fusion_shapes(shape):
    (rp, rm, rv), (fp, fm, fv, _) = _run_both(shape, seed=7, **HYP,
                                              weight_decay=WD)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))
    # the reference's own v_new-as-returned vs v_new-as-consumed split costs
    # 1 ulp here; the kernel is pinned to the consistent value
    assert _ulp_diff(rp, fp) <= 1


# each side of the kernel's choice of layout: leaves it cuts along their own
# rows (last dim a multiple of 128, second-last of 8), and leaves that keep
# the flat [rows, 128] view
NATIVE_SHAPES = [(8, 128), (16, 256), (3, 8, 128), (24, 384)]
FLAT_SHAPES = [(5, 7), (8, 100), (4097,), (12, 128)]


@pytest.mark.parametrize("shape,native",
                         [(s, True) for s in NATIVE_SHAPES]
                         + [(s, False) for s in FLAT_SHAPES], ids=str)
def test_kernel_bit_exact_on_each_side_of_the_layout_choice(shape, native):
    assert (native_view(shape) is not None) == native
    seed = hash(shape) % 997
    (rp, rm, rv), (fp, fm, fv, _) = _run_both(shape, seed=seed, **HYP,
                                              weight_decay=WD)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))
    # the reference's own 1-ulp split (module docstring) shows on some seeds
    assert _ulp_diff(rp, fp) <= 1
    # one body, two ways of cutting: the same leaf handed over flat gives
    # the same bits
    flat = adamw_update(*(x.reshape(-1) for x in _rand_state(shape, seed)),
                        jnp.float32(LR), jnp.int32(3), interpret=True, **HYP,
                        weight_decay=WD)
    for a, b in zip(flat[:3], (fp, fm, fv)):
        np.testing.assert_array_equal(np.asarray(a).reshape(shape),
                                      np.asarray(b))


def test_native_block_that_does_not_divide_the_leaf():
    # both axes end in a ragged block of the kernel's own block size
    rows, cols = NATIVE_BLOCK
    shape = (rows + 8, cols + 128)
    assert native_view(shape) == shape
    (rp, rm, rv), (fp, fm, fv, _) = _run_both(shape, seed=13, **HYP,
                                              weight_decay=WD)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))
    assert _ulp_diff(rp, fp) <= 1


def test_bf16_copy_narrows_the_native_side():
    # the bf16 copy is tiled (16, 128): 8 rows collapse without a copy in
    # fp32 only
    assert native_view((3, 8, 128)) == (24, 128)
    assert native_view((3, 8, 128), "bfloat16") is None
    assert native_view((3, 16, 128), "bfloat16") == (48, 128)


def test_fused_bytes_counted_by_layout():
    from paddle_tpu import obs

    def read():
        return {lay: obs.registry().counter("optimizer.fused_bytes",
                                            layout=lay).value
                for lay in ("native", "flat")}

    before = read()
    lr, step = jnp.float32(LR), jnp.int32(1)

    @jax.jit
    def both(a, b):
        return (adamw_update(*a, lr, step, interpret=True, **HYP),
                adamw_update(*b, lr, step, interpret=True, **HYP))

    a, b = _rand_state((16, 256), seed=1), _rand_state((8, 100), seed=2)
    both(a, b)
    both(a, b)  # a compiled step adds nothing: the count is the trace's
    after = read()
    assert after["native"] - before["native"] == 16 * 256 * 4
    assert after["flat"] - before["flat"] == 8 * 100 * 4


def test_master_weight_cast_written_in_same_pass():
    p, g, m, v = _rand_state((8, 128), seed=11)
    lr, step = jnp.float32(LR), jnp.int32(1)
    ref_p, _, _ = _ref_jit(**HYP, weight_decay=WD)(p, g, m, v, lr, step)
    fp, _, _, p_out = adamw_update(p, g, m, v, lr, step, interpret=True,
                                   out_dtype=jnp.bfloat16, weight_decay=WD,
                                   **HYP)
    assert p_out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(ref_p))
    np.testing.assert_array_equal(
        np.asarray(p_out, np.float32),
        np.asarray(jnp.asarray(fp).astype(jnp.bfloat16), np.float32))


def test_kernel_multi_step_stays_exact():
    p, g, m, v = _rand_state((64, 16), seed=3)
    lr = jnp.float32(LR)
    ref = _ref_jit(**HYP, weight_decay=WD)
    rp, rm, rv = p, m, v
    fp, fm, fv = p, m, v
    rng = np.random.default_rng(5)
    for t in range(1, 4):
        g = jnp.asarray(rng.standard_normal(p.shape).astype(np.float32))
        rp, rm, rv = ref(rp, g, rm, rv, lr, jnp.int32(t))
        fp, fm, fv, _ = adamw_update(fp, g, fm, fv, lr, jnp.int32(t),
                                     interpret=True, weight_decay=WD, **HYP)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(fm))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(fv))
    np.testing.assert_array_equal(np.asarray(rp), np.asarray(fp))


# ---------------------------------------------------------------------------
# optimizer-level: fused path wired through Adam/AdamW
# ---------------------------------------------------------------------------

@pytest.fixture
def interpret_flag():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


def _make_opt(cls, datas, **kw):
    params = [Parameter(np.array(d), name=f"w{i}")
              for i, d in enumerate(datas)]
    opt = cls(learning_rate=LR, parameters=params, **kw)
    return params, opt


def _step_with(params, opt, grads):
    for p, g in zip(params, grads):
        p._grad = jnp.asarray(g)
    opt.step()


def test_optimizer_fused_step_matches_reference(interpret_flag, monkeypatch):
    import paddle_tpu.kernels.adamw as adamw_mod

    calls = []
    real = adamw_mod.adamw_update
    monkeypatch.setattr(adamw_mod, "adamw_update",
                        lambda *a, **k: calls.append(1) or real(*a, **k))

    rng = np.random.default_rng(0)
    datas = [rng.standard_normal((8, 128)).astype(np.float32),
             rng.standard_normal((64,)).astype(np.float32)]
    grads = [rng.standard_normal(d.shape).astype(np.float32) for d in datas]

    p_f, opt_f = _make_opt(paddle.optimizer.AdamW, datas, weight_decay=WD)
    _step_with(p_f, opt_f, grads)
    assert calls, "fused kernel was not invoked under FLAGS_pallas_interpret"

    flags.set_flags({"pallas_interpret": False})
    p_r, opt_r = _make_opt(paddle.optimizer.AdamW, datas, weight_decay=WD)
    _step_with(p_r, opt_r, grads)

    for pf, pr, sf, sr in zip(p_f, p_r, opt_f._state, opt_r._state):
        np.testing.assert_array_equal(np.asarray(sf["m"]), np.asarray(sr["m"]))
        np.testing.assert_array_equal(np.asarray(sf["v"]), np.asarray(sr["v"]))
        assert _ulp_diff(pf._data, pr._data) <= 1


# ---------------------------------------------------------------------------
# ZeRO-1 sharded weight update
# ---------------------------------------------------------------------------

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (fake) CPU devices")


def _mesh8():
    return dist.ProcessMesh(np.arange(8), ["dp"])


def _run_steps(cls, datas, n_steps, mesh=None, **kw):
    params, opt = _make_opt(cls, datas, **kw)
    if mesh is not None:
        opt.shard_update(mesh)
    rng = np.random.default_rng(42)
    for _ in range(n_steps):
        _step_with(params, opt,
                   [rng.standard_normal(d.shape).astype(np.float32)
                    for d in datas])
    return params, opt


@needs_8_devices
def test_sharded_adam_bit_exact_vs_unsharded():
    """wd=0 has no fmsub site: the sharded program must reproduce the
    unsharded params AND slots bitwise over multiple steps (the acceptance
    bar for the all-gather round-trip being lossless)."""
    rng = np.random.default_rng(1)
    datas = [rng.standard_normal((64, 16)).astype(np.float32),
             rng.standard_normal((128,)).astype(np.float32),
             rng.standard_normal((5, 3)).astype(np.float32)]  # not divisible: replicated
    p_s, opt_s = _run_steps(paddle.optimizer.Adam, datas, 3, mesh=_mesh8())
    p_u, opt_u = _run_steps(paddle.optimizer.Adam, datas, 3)
    for ps, pu, ss, su in zip(p_s, p_u, opt_s._state, opt_u._state):
        np.testing.assert_array_equal(np.asarray(ps._data), np.asarray(pu._data))
        np.testing.assert_array_equal(np.asarray(ss["m"]), np.asarray(su["m"]))
        np.testing.assert_array_equal(np.asarray(ss["v"]), np.asarray(su["v"]))


@needs_8_devices
def test_sharded_adamw_slots_exact_params_within_update_noise():
    rng = np.random.default_rng(2)
    datas = [rng.standard_normal((64, 16)).astype(np.float32),
             rng.standard_normal((128,)).astype(np.float32)]
    p_s, opt_s = _run_steps(paddle.optimizer.AdamW, datas, 3, mesh=_mesh8(),
                            weight_decay=WD)
    p_u, opt_u = _run_steps(paddle.optimizer.AdamW, datas, 3, weight_decay=WD)
    for ps, pu, ss, su in zip(p_s, p_u, opt_s._state, opt_u._state):
        np.testing.assert_array_equal(np.asarray(ss["m"]), np.asarray(su["m"]))
        np.testing.assert_array_equal(np.asarray(ss["v"]), np.asarray(su["v"]))
        # decay multiply is an fmsub contraction site that moves under
        # partitioning: params carry at most ~ulp-of-update noise
        np.testing.assert_allclose(np.asarray(ps._data), np.asarray(pu._data),
                                   rtol=1e-6, atol=1e-9)


@needs_8_devices
def test_sharded_state_actually_sharded_params_replicated():
    rng = np.random.default_rng(3)
    datas = [rng.standard_normal((64, 16)).astype(np.float32)]
    params, opt = _run_steps(paddle.optimizer.AdamW, datas, 1, mesh=_mesh8(),
                             weight_decay=WD)
    m = opt._state[0]["m"]
    assert not m.sharding.is_fully_replicated, m.sharding
    # 1/N memory: each device holds one 8th of the slot
    shard = m.addressable_shards[0].data
    assert shard.size * 8 == m.size, (shard.shape, m.shape)
    p = params[0]._data
    assert p.sharding.is_fully_replicated, p.sharding


@needs_8_devices
def test_sharded_plus_fused_interpret_compose(interpret_flag, monkeypatch):
    """Interpret-mode kernel discharges to plain HLO, so GSPMD can partition
    it: fused + sharded must agree with the unsharded reference."""
    import paddle_tpu.kernels.adamw as adamw_mod

    calls = []
    real = adamw_mod.adamw_update
    monkeypatch.setattr(adamw_mod, "adamw_update",
                        lambda *a, **k: calls.append(1) or real(*a, **k))

    rng = np.random.default_rng(4)
    datas = [rng.standard_normal((64, 16)).astype(np.float32)]
    p_s, opt_s = _run_steps(paddle.optimizer.Adam, datas, 2, mesh=_mesh8())
    assert calls, "fused kernel was not invoked in the sharded program"

    flags.set_flags({"pallas_interpret": False})
    p_u, opt_u = _run_steps(paddle.optimizer.Adam, datas, 2)
    for ps, pu, ss, su in zip(p_s, p_u, opt_s._state, opt_u._state):
        np.testing.assert_array_equal(np.asarray(ss["m"]), np.asarray(su["m"]))
        np.testing.assert_array_equal(np.asarray(ss["v"]), np.asarray(su["v"]))
        np.testing.assert_allclose(np.asarray(ps._data), np.asarray(pu._data),
                                   rtol=1e-6, atol=1e-9)


@needs_8_devices
def test_sharded_fused_shard_map_route_bit_exact(interpret_flag, monkeypatch):
    """The PR-6 composition gap, closed: with shard_update on, _fused_leaf
    must route the fused kernel through shard_map (GSPMD cannot partition
    the compiled Mosaic custom call), and the shard_map-routed update must
    reproduce the unsharded fused kernel: the moments bitwise (wd=0 Adam has
    no contraction site and the kernel is elementwise on shard-local data),
    the params to one ulp.  The scalar bias corrections ``1 - beta**t`` are
    computed once inside each program, and XLA's CPU backend fuses — and so
    rounds — them differently inside a shard_map body than outside one:
    from step 2 on they can differ in the last bit, which reaches one
    param in several hundred."""
    from paddle_tpu.framework import shard_map_compat

    routed = []
    real = shard_map_compat.shard_map
    monkeypatch.setattr(shard_map_compat, "shard_map",
                        lambda *a, **k: routed.append(1) or real(*a, **k))

    rng = np.random.default_rng(7)
    datas = [rng.standard_normal((64, 16)).astype(np.float32),
             rng.standard_normal((128,)).astype(np.float32),
             rng.standard_normal((5, 3)).astype(np.float32)]  # replicated: direct kernel
    p_s, opt_s = _run_steps(paddle.optimizer.Adam, datas, 3, mesh=_mesh8())
    assert routed, "fused kernel was not routed through shard_map"

    p_u, opt_u = _run_steps(paddle.optimizer.Adam, datas, 3)
    for ps, pu, ss, su in zip(p_s, p_u, opt_s._state, opt_u._state):
        np.testing.assert_array_equal(np.asarray(ss["m"]), np.asarray(su["m"]))
        np.testing.assert_array_equal(np.asarray(ss["v"]), np.asarray(su["v"]))
        assert _ulp_diff(ps._data, pu._data) <= 1


@needs_8_devices
def test_allgather_roundtrip_bit_exact():
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = _mesh8().jax_mesh
    x = jnp.asarray(np.random.default_rng(6)
                    .standard_normal((64, 16)).astype(np.float32))

    @jax.jit
    def roundtrip(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec("dp")))
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, PartitionSpec()))

    np.testing.assert_array_equal(np.asarray(roundtrip(x)), np.asarray(x))


# ---------------------------------------------------------------------------
# ZeRO-1 gather/compute overlap (PR-13): the post-update all-gather moves
# to the head of the NEXT step, bucketed per layer group, and interleaves
# with the forward — same dataflow, so training must stay bit-identical
# ---------------------------------------------------------------------------


def _overlap_net(depth=4, dim=64, seed=0):
    import paddle_tpu.nn as nn

    class Net(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Linear(dim, dim)
            self.layers = nn.LayerList([nn.Linear(dim, dim)
                                        for _ in range(depth)])
            self.head = nn.Linear(dim, dim)

        def forward(self, x):
            h = self.embed(x)
            for lyr in self.layers:
                h = nn.functional.relu(lyr(h))
            return self.head(h)

    m = Net()
    rng = np.random.default_rng(seed)
    for n, p in m.named_parameters():
        p._data = jnp.asarray(
            rng.standard_normal(p.shape).astype(np.float32) * 0.05)
    return m


def _overlap_train(cls, overlap, n_steps=3, dim=64, buckets=2, **kw):
    from paddle_tpu.jit import TrainStep

    def loss_fn(model, x, y):
        return ((model(x) - y) ** 2).mean()

    model = _overlap_net(dim=dim)
    opt = cls(learning_rate=1e-3, parameters=model.parameters(), **kw)
    opt.shard_update(_mesh8(), overlap_gather=overlap, gather_buckets=buckets)
    step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(99)
    losses = []
    for _ in range(n_steps):
        x = paddle.to_tensor(rng.standard_normal((8, dim)).astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((8, dim)).astype(np.float32))
        losses.append(float(step(x, y)))
    params = {n: np.asarray(a) for n, a in step._params.items()}
    state = jax.tree_util.tree_map(np.asarray, step._opt_state)
    return losses, params, state, step


@needs_8_devices
def test_overlap_gather_adam_bit_identical():
    """Head-of-step bucketed gather vs sequential tail gather: identical
    dataflow per leaf, so losses, params, AND m/v slots must match
    bitwise over multiple steps — the overlap is free or it is wrong."""
    l_s, p_s, s_s, _ = _overlap_train(paddle.optimizer.Adam, overlap=False)
    l_o, p_o, s_o, st = _overlap_train(paddle.optimizer.Adam, overlap=True)
    assert l_s == l_o, (l_s, l_o)
    assert st._gather_plan is not None and len(st._gather_plan) == 2
    for n in p_s:
        np.testing.assert_array_equal(p_s[n], p_o[n], err_msg=n)
    for a, b in zip(jax.tree_util.tree_leaves(s_s),
                    jax.tree_util.tree_leaves(s_o)):
        np.testing.assert_array_equal(a, b)


@needs_8_devices
def test_overlap_gather_adamw_slots_exact_params_close():
    """The weight-decay fmsub is a contraction site the recompiled program
    may fuse differently, so params carry ~ulp-of-update noise per step —
    and unlike the synthetic-grad harness above, grads here flow through
    the forward, so from step 2 the noise reaches m/v too.  Everything
    must stay within a few ulps; wd=0 (the Adam test) is the bit-exact
    bar."""
    l_s, p_s, s_s, _ = _overlap_train(paddle.optimizer.AdamW, overlap=False,
                                      weight_decay=WD)
    l_o, p_o, s_o, _ = _overlap_train(paddle.optimizer.AdamW, overlap=True,
                                      weight_decay=WD)
    np.testing.assert_allclose(l_s, l_o, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s_s),
                    jax.tree_util.tree_leaves(s_o)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-10)
    for n in p_s:
        np.testing.assert_allclose(p_s[n], p_o[n], rtol=1e-5, atol=1e-7,
                                   err_msg=n)


@needs_8_devices
def test_overlap_inject_serialize_disables_overlap(monkeypatch):
    """The gate's defect injection: OVERLAP_GATE_INJECT=serialize makes
    the overlap build silently fall back to the sequential tail gather —
    exactly the regression class overlap_gate.sh must detect."""
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=[])
    opt.shard_update(_mesh8(), overlap_gather=True)
    assert opt._wus_overlap_active()
    monkeypatch.setenv("OVERLAP_GATE_INJECT", "serialize")
    assert not opt._wus_overlap_active()


def test_overlap_gather_plan_buckets_layers():
    """Layer-indexed params split into contiguous groups; non-layer params
    (embed, head) ride in bucket 0 so no gather is orphaned."""
    from paddle_tpu.jit import _overlap_gather_plan

    names = (["embed.weight", "head.weight"]
             + [f"layers.{i}.weight" for i in range(6)])
    plan = _overlap_gather_plan(names, 3)
    assert [sorted(b) for b in plan] == [
        sorted(["embed.weight", "head.weight",
                "layers.0.weight", "layers.1.weight"]),
        ["layers.2.weight", "layers.3.weight"],
        ["layers.4.weight", "layers.5.weight"]]
    # no layer structure at all: one replicated bucket
    assert _overlap_gather_plan(["a", "b"], 4) == [["a", "b"]]
