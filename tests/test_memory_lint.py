"""Memory liveness lint: every ``mem-*`` catalogue code must fire on a
seeded defect, a clean program must stay silent, and the liveness-modeled
peak must agree with XLA's own ``memory_analysis()`` within tolerance on a
battery of program shapes.  Everything compiles toy programs — nothing
larger than a few MB runs — so the suite stays in the non-slow tier."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import analysis
from paddle_tpu.analysis import lint_memory, lint_memory_text
from paddle_tpu.analysis.liveness import analyze_text, xla_peak_bytes
from paddle_tpu.analysis.memory_lint import GATED_MEM_CODES


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile(fn, *args, **jit_kwargs):
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


# a 2 MB elementwise update: big enough for the 1 MiB big-buffer floor
_W = _sds((512, 1024))


def _update(w, g):
    return w - 0.1 * g


# ---------------------------------------------------------------------------
# acceptance: a donated clean program reports nothing gated


def test_clean_donated_update_no_gated_findings():
    compiled = _compile(_update, _W, _W, donate_argnums=(0,))
    rep = lint_memory(compiled)
    gated = [f for f in rep if f.code in GATED_MEM_CODES]
    assert not gated, rep.report()
    assert rep.meta["peak_bytes"] > 0


# ---------------------------------------------------------------------------
# mem-over-budget


def test_over_budget_fires_and_clears():
    compiled = _compile(_update, _W, _W)
    peak = lint_memory(compiled).meta["peak_bytes"]
    over = lint_memory(compiled, hbm_budget=peak - 1).by_code("mem-over-budget")
    assert len(over) == 1
    assert over[0].bytes == 1          # carries the exact overshoot
    assert over[0].severity == "high"
    assert not lint_memory(compiled, hbm_budget=peak).by_code("mem-over-budget")


def test_over_budget_through_check_api():
    rep = analysis.check(_update, (_W, _W), hbm_budget=1024)
    assert rep.by_code("mem-over-budget")


# ---------------------------------------------------------------------------
# mem-donation-would-help


def test_donation_advisor_fires_on_undonated_update():
    compiled = _compile(_update, _W, _W)
    hits = lint_memory(compiled).by_code("mem-donation-would-help")
    assert len(hits) == 1
    # the finding carries the PROVEN delta: re-sweeping with param 0
    # donated must lower the peak by the full parameter size
    assert hits[0].bytes == 512 * 1024 * 4
    assert "donate_argnums" in hits[0].suggestion
    # ...and donating actually clears it
    donated = _compile(_update, _W, _W, donate_argnums=(0,))
    assert not lint_memory(donated).by_code("mem-donation-would-help")


def test_strip_donation_injection_trips_advisor(monkeypatch):
    """The mem_gate defect injection: MEM_GATE_INJECT=strip-donation drops
    the module's input_output_alias header, so an already-donated update
    must re-surface as a donation candidate (this is what drives
    ``scripts/mem_gate.sh`` to rc 1)."""
    compiled = _compile(_update, _W, _W, donate_argnums=(0,))
    clean_peak = lint_memory(compiled).meta["peak_bytes"]
    monkeypatch.setenv("MEM_GATE_INJECT", "strip-donation")
    rep = lint_memory(compiled)
    hits = rep.by_code("mem-donation-would-help")
    assert hits and hits[0].bytes > 0
    assert rep.meta["peak_bytes"] > clean_peak


# ---------------------------------------------------------------------------
# mem-replicated-resident


def test_replicated_resident_fires_on_replicated_param(mesh):
    w, x = _sds((512, 512)), _sds((512, 256))
    global_bytes = 512 * 512 * 4
    rep_w = NamedSharding(mesh, P())
    sh_x = NamedSharding(mesh, P("x"))
    compiled = _compile(lambda w, x: w @ x, w, x,
                        in_shardings=(rep_w, sh_x), out_shardings=sh_x)
    declared = {0: ("w", global_bytes, True)}   # spec CLAIMS w is sharded
    hits = lint_memory(compiled, declared_params=declared).by_code(
        "mem-replicated-resident")
    assert len(hits) == 1
    assert hits[0].bytes == global_bytes        # resident at full global size


def test_replicated_resident_silent_when_actually_sharded(mesh):
    w, x = _sds((512, 512)), _sds((512, 256))
    sh_w = NamedSharding(mesh, P("x"))
    compiled = _compile(lambda w, x: w @ x, w, x,
                        in_shardings=(sh_w, NamedSharding(mesh, P())),
                        out_shardings=NamedSharding(mesh, P("x")))
    declared = {0: ("w", 512 * 512 * 4, True)}
    assert not lint_memory(compiled, declared_params=declared).by_code(
        "mem-replicated-resident")


# ---------------------------------------------------------------------------
# mem-remat-candidate (advisory)


def test_remat_candidate_fires_on_long_lived_activation():
    def f(x, w):
        a = jnp.tanh(x @ w)          # 1 MB activation parked until the end
        y = x
        for _ in range(20):          # 20 dot instructions keep it waiting
            y = jnp.tanh(y @ w)
        return a + y

    x = w = _sds((512, 512))
    rep = lint_memory(_compile(f, x, w))
    hits = rep.by_code("mem-remat-candidate")
    assert hits
    assert all(f.severity == "low" for f in hits)           # advisory only
    assert all(f.code not in GATED_MEM_CODES for f in hits)
    assert any("checkpoint" in f.suggestion for f in hits)


def test_remat_silent_on_short_chain():
    rep = lint_memory(_compile(lambda x, w: jnp.tanh(x @ w) @ w,
                               _sds((512, 512)), _sds((512, 512))))
    assert not rep.by_code("mem-remat-candidate")


# ---------------------------------------------------------------------------
# liveness vs memory_analysis() agreement (the 10% acceptance bound)


def _while_prog(x):
    return jax.lax.fori_loop(0, 8, lambda i, c: jnp.tanh(c) * 0.5 + 1.0, x)


def _scan_prog(x, w):
    def body(c, _):
        return jnp.tanh(c @ w), None
    out, _ = jax.lax.scan(body, x, None, length=4)
    return out


AGREEMENT_CASES = [
    # (label, fn, args, jit kwargs, (lo, hi) ratio bounds)
    ("elementwise-donated", _update, (_W, _W), {"donate_argnums": (0,)},
     (0.9, 1.1)),
    ("elementwise", _update, (_W, _W), {}, (0.9, 1.1)),
    ("matmul-chain", lambda x, w1, w2: jax.nn.relu(x @ w1) @ w2,
     (_sds((256, 512)), _sds((512, 512)), _sds((512, 256))), {}, (0.9, 1.1)),
    # loop bodies: XLA writes the body result in place into the carry
    # buffer, which the per-computation sweep cannot see — it charges the
    # body's fresh result on top of the carry.  The error is strictly a
    # conservative OVERestimate (a lint that never under-reports peak),
    # so the toy bounds are one-sided-loose upward; the bench presets,
    # where loops carry a small share of the peak, stay inside the 10%
    # acceptance bound enforced by scripts/mem_gate.sh.
    ("while-loop", _while_prog, (_sds((256, 1024)),), {}, (1.0, 1.55)),
    ("scan", _scan_prog, (_sds((256, 256)), _sds((256, 256))), {},
     (0.95, 1.3)),
]


@pytest.mark.parametrize("label,fn,args,kw,bounds", AGREEMENT_CASES,
                         ids=[c[0] for c in AGREEMENT_CASES])
def test_liveness_agrees_with_memory_analysis(label, fn, args, kw, bounds):
    compiled = _compile(fn, *args, **kw)
    xp = xla_peak_bytes(compiled)
    assert xp is not None, "memory_analysis() not exposed by this jaxlib"
    res = analyze_text(compiled.as_text())
    ratio = res.peak_bytes / max(xp[0], 1)
    lo, hi = bounds
    assert lo <= ratio <= hi, (
        f"{label}: liveness {res.peak_bytes} vs xla {xp[0]} (ratio {ratio:.4f})")


def test_lint_memory_records_agreement_meta():
    rep = lint_memory(_compile(_update, _W, _W))
    assert rep.meta["xla_peak_bytes"] > 0
    assert abs(rep.meta["peak_agreement"] - 1.0) <= 0.1


def test_spmd_peak_is_per_device(mesh):
    """SPMD text prints per-device shapes: the modeled peak of a 2-way
    sharded update must be about half the unsharded one."""
    sh = NamedSharding(mesh, P("x"))
    full = lint_memory(_compile(_update, _W, _W)).meta["peak_bytes"]
    shard = lint_memory(_compile(
        _update, _W, _W, in_shardings=(sh, sh),
        out_shardings=sh)).meta["peak_bytes"]
    assert shard <= 0.6 * full
