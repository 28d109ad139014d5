"""Pallas kernel verifier: every ``krn-*`` catalogue code must fire on a
seeded defect, every shipped kernel must lint clean through the registry,
and the admission seam must refuse a defective registered kernel *before*
its first call.  Everything traces abstractly — no kernel executes except
the tiny interpret-mode runs in the admission tests."""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.analysis import pallas_lint
from paddle_tpu.analysis.pallas_lint import (
    BlockUse, KernelSpec, ScratchUse, check_kernel, extract_kernel_specs,
    lint_kernel_spec)
from paddle_tpu.kernels import registry


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


# ---------------------------------------------------------------------------
# seeded defects: one per krn-* code
# ---------------------------------------------------------------------------


def test_seeded_write_race_and_coverage_hole_caught():
    """Every grid point writing block (0, 0) under a 'parallel' axis is both
    a race and a coverage hole (blocks 1..3 keep garbage)."""
    fn, args = registry._build_injected_write_race()
    rep = check_kernel(fn, *args)
    assert rep.by_code("krn-write-race"), rep.report()
    assert rep.by_code("krn-coverage-hole"), rep.report()


def test_seeded_parallel_carry_caught():
    """A scratch accumulator reset only at i == 0 carries across the i axis;
    declaring that axis 'parallel' is the ssd_scan bug class."""
    fn, args = registry._build_injected_parallel_carry()
    rep = check_kernel(fn, *args)
    assert len(rep.by_code("krn-parallel-carry")) == 1, rep.report()
    assert not rep.by_code("krn-write-race"), rep.report()


def test_seeded_oob_block_index_caught():
    """Grid runs to 5 but the input only has 4 blocks — the affine path
    proves the last program reads entirely out of bounds."""
    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(5,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=_sds((40, 128)),
        )(x)

    rep = check_kernel(fn, _sds((32, 128)))
    oob = rep.by_code("krn-oob-read")
    assert oob and any(f.severity == "high" for f in oob), rep.report()


def test_seeded_ragged_overhang_caught():
    """100 rows under 32-row blocks: the last block overhangs by 28 rows of
    padding read unmasked (medium — numerics, not a crash)."""
    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((32, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((32, 128), lambda i: (i, 0)),
            out_shape=_sds((128, 128)),
        )(x)

    rep = check_kernel(fn, _sds((100, 128)))
    oob = rep.by_code("krn-oob-read")
    assert oob and all(f.severity == "medium" for f in oob), rep.report()


def test_seeded_alias_mismatch_caught():
    """pallas refuses mismatched aliases at trace time, so generated specs
    (the ROADMAP-4 seam) are the only way to hit this — build one by hand."""
    spec = KernelSpec(
        name="gen", grid=(4,),
        inputs=[BlockUse((32, 128), jnp.float32, (8, 128), lambda i: (i, 0))],
        outputs=[BlockUse((32, 128), jnp.bfloat16, (8, 128),
                          lambda i: (i, 0))],
        aliases={0: 0})
    rep = lint_kernel_spec(spec)
    assert len(rep.by_code("krn-alias-mismatch")) == 1, rep.report()


def test_seeded_alias_raw_caught():
    """Aliased pair whose index maps disagree: grid point 1 reads the block
    grid point 0 already overwrote through the output side."""
    spec = KernelSpec(
        name="gen", grid=(4,),
        inputs=[BlockUse((32, 128), jnp.float32, (8, 128),
                         lambda i: ((i + 1) % 4, 0))],
        outputs=[BlockUse((32, 128), jnp.float32, (8, 128),
                          lambda i: (i, 0))],
        aliases={0: 0})
    rep = lint_kernel_spec(spec)
    assert len(rep.by_code("krn-alias-raw")) == 1, rep.report()


def test_aligned_alias_is_clean():
    spec = KernelSpec(
        name="gen", grid=(4,),
        inputs=[BlockUse((32, 128), jnp.float32, (8, 128), lambda i: (i, 0))],
        outputs=[BlockUse((32, 128), jnp.float32, (8, 128),
                          lambda i: (i, 0))],
        aliases={0: 0})
    assert not lint_kernel_spec(spec), lint_kernel_spec(spec).report()


def test_seeded_vmem_over_budget_caught():
    """The shipped flash forward models ~0.79 MB resident; a 0.5 MB budget
    must refuse it, and the report must carry the modeled bytes."""
    registry.load_all()
    rep = registry.check("flash_fwd_resident", vmem_budget=512 * 1024)
    assert rep.by_code("krn-vmem-over-budget"), rep.report()
    assert rep.meta["kernel_vmem_bytes"] > 512 * 1024


def test_seeded_dynamic_index_advisory():
    """An index map that loads from the scalar-prefetch ref cannot be
    evaluated statically — advisory finding, footprint checks skipped."""
    def fn(order, x):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i, s: (s[i], 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, s: (i, 0)),
        )
        return pl.pallas_call(
            lambda s_ref, x_ref, o_ref: _copy_kernel(x_ref, o_ref),
            grid_spec=grid_spec, out_shape=_sds((32, 128)))(order, x)

    rep = check_kernel(fn, _sds((4,), jnp.int32), _sds((32, 128)))
    dyn = rep.by_code("krn-dynamic-index")
    assert dyn and all(f.severity == "low" for f in dyn), rep.report()
    assert not rep.by_code("krn-coverage-hole"), rep.report()


def test_untraceable_function_raises():
    # a kernel the verifier cannot trace is a broken kernel, not a finding
    def boom(x):
        raise ValueError("no trace for you")

    with pytest.raises(ValueError, match="no trace for you"):
        check_kernel(boom, _sds((8, 128)))


# ---------------------------------------------------------------------------
# shipped kernels: the registry inventory is clean at the committed baseline
# ---------------------------------------------------------------------------


_EXPECTED_KERNELS = {
    "adamw_fused", "decode_mmha", "decode_mmha_fused", "flash_bwd_stream",
    "flash_fwd_resident", "flash_fwd_stream", "paged_chunk_attention",
    "paged_decode", "paged_decode_fused", "rms_norm", "ssd_scan",
    "write_paged_chunk",
}


def test_registry_inventory_complete():
    registry.load_all()
    assert _EXPECTED_KERNELS <= set(registry.names())


def test_all_registered_kernels_lint_clean():
    registry.load_all()
    reports = registry.check_all()
    dirty = {n: r.report() for n, r in reports.items() if r}
    assert not dirty, dirty
    # VMEM model stays inside the default per-core budget for every kernel
    for name, rep in reports.items():
        assert (rep.meta["kernel_vmem_bytes"]
                <= pallas_lint.DEFAULT_VMEM_BUDGET), name


def test_check_all_preset_filter():
    registry.load_all()
    ssd_only = registry.check_all(presets="ssd")
    assert set(ssd_only) == {"ssd_scan"}


# ---------------------------------------------------------------------------
# ssd_scan regression (the satellite): the state-carry invariant is
# *certified*, not assumed
# ---------------------------------------------------------------------------


def _ssd_spec():
    registry.load_all()
    built = registry.entries()["ssd_scan"].build()
    specs = extract_kernel_specs(built[0], *built[1])
    assert len(specs) == 1
    return specs[0]


def test_ssd_declares_sequential_chunk_axis():
    spec = _ssd_spec()
    assert spec.dimension_semantics == ("parallel", "arbitrary")
    # the verifier independently derives the carry: scratch 0 (the state
    # accumulator) carries across axis 1 (chunks) only — the ci == 0 reset
    # cuts the carry across g
    assert spec.carried_scratch == [(0, frozenset({1}))]
    assert not lint_kernel_spec(spec), lint_kernel_spec(spec).report()


def test_ssd_parallel_chunk_axis_variant_refused():
    """The exact bug the declaration guards against: flipping the chunk axis
    to 'parallel' must be flagged as a carry hazard (and the revisited
    s_final row becomes a write race)."""
    spec = _ssd_spec()
    spec.dimension_semantics = ("parallel", "parallel")
    rep = lint_kernel_spec(spec)
    assert rep.by_code("krn-parallel-carry"), rep.report()
    assert rep.by_code("krn-write-race"), rep.report()


def test_flash_stream_carry_certified():
    """Flash attention's online-softmax scratch (m, l, acc) carries across
    the KV axis (axis 2), which is declared sequential — same invariant,
    independently derived."""
    registry.load_all()
    built = registry.entries()["flash_fwd_stream"].build()
    spec = extract_kernel_specs(built[0], *built[1])[0]
    assert spec.carried_scratch, "expected carried online-softmax scratch"
    for _, axes in spec.carried_scratch:
        assert axes == frozenset({2})
        assert not (axes & spec.parallel_axes())


# ---------------------------------------------------------------------------
# admission: a defective registered kernel is refused before its first call
# ---------------------------------------------------------------------------


@pytest.fixture
def _admission():
    from paddle_tpu.framework import flags

    registry.load_all()
    orig = registry.entries()["ssd_scan"]
    registry.reset_admission_cache()
    try:
        yield flags
    finally:
        registry.register(orig.name, orig.build, presets=orig.presets,
                          description=orig.description)
        flags.set_flags({"kernel_admission": False})
        registry.reset_admission_cache()


def _ssd_args():
    G, T, P, N = 2, 128, 8, 4
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k[0], (G, T, P)),
            jax.random.normal(k[1], (G, T, N)),
            jax.random.normal(k[2], (G, T, N)),
            -0.1 * jnp.ones((G, T)))


def test_admission_refuses_defective_kernel_before_first_call(_admission):
    from paddle_tpu.kernels import ssd_scan as ssd_mod

    _admission.set_flags({"kernel_admission": True})
    # sabotage the registered spec builder: admission must now refuse the
    # public entry point before any pallas_call runs
    registry.register("ssd_scan", registry._build_injected_write_race)
    with pytest.raises(registry.KernelRejected, match="krn-write-race"):
        ssd_mod.ssd_scan(*_ssd_args(), chunk=64, interpret=True)


def test_admission_passes_clean_kernel_and_caches(_admission):
    from paddle_tpu.kernels import ssd_scan as ssd_mod

    _admission.set_flags({"kernel_admission": True})
    y, s = ssd_mod.ssd_scan(*_ssd_args(), chunk=64, interpret=True)
    assert y.shape == (2, 128, 8) and s.shape == (2, 4, 8)
    # second call hits the admission cache (and still works)
    ssd_mod.ssd_scan(*_ssd_args(), chunk=64, interpret=True)


def test_admission_off_is_a_no_op(_admission):
    from paddle_tpu.kernels import ssd_scan as ssd_mod

    # flag off (the default): even a sabotaged registration is not consulted
    registry.register("ssd_scan", registry._build_injected_write_race)
    y, _ = ssd_mod.ssd_scan(*_ssd_args(), chunk=64, interpret=True)
    assert y.shape == (2, 128, 8)


def test_unregistered_name_passes_admission(_admission):
    _admission.set_flags({"kernel_admission": True})
    registry.ensure_admitted("not_a_registered_kernel")  # must not raise
