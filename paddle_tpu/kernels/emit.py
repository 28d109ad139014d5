"""Fusion transformer: emit admission-gated Pallas kernels from the audit worklist.

ROADMAP item 4's closing move.  ``profiler.fusion_audit.pallas_candidates()``
*finds* fusible regions (source-region byte model per arXiv:2301.13062); this
module *acts* on them: every :class:`FusionSite` names one model-seam region —
an elementwise chain around a reduction (``fuse_swiglu_mlp``), a norm+matmul
prologue (``fuse_rms_norm_head``: rms_norm feeding the vocab projection), or a
residual+cast epilogue (``fuse_add_rms_norm``) — and the emitter generates a
fused forward/backward Pallas kernel pair for it.

**Bit-identity by construction, verified anyway.**  The emitted forward kernel
body *traces the site's jnp reference* on whole VMEM blocks, and the backward
kernel body traces ``jax.vjp`` of that same reference — the primitive sequence
inside the kernel is byte-for-byte the one the unfused program runs, so the
training loss of a substituted step matches the stock step bit-for-bit.  The
AdamW-kernel discipline still applies on top: :func:`verify_site` replays both
kernels in interpret mode against the references and refuses the site on any
mismatching bit (``fuse-verify-mismatch``).

**Admission before the first call.**  Each emitted kernel (forward and
backward) registers in ``kernels.registry``; ``registry.admit`` /
``FLAGS_kernel_admission`` route it through ``analysis.pallas_lint`` so a bad
emission raises ``KernelRejected`` before any ``pallas_call`` executes.
``KERNEL_GATE_INJECT=emit-race`` (or ``FUSE_GATE_INJECT=emit-race``) seeds a
forced write-race into every emitted forward — the gate leg proving the
admission rail can fail.

Substitution is runtime-scoped: ``analysis.fusion_transform`` plans which
sites win under the audit byte model and :func:`activate`\\ s them; the model
seams (``models/llama.py``) consult :func:`active` and fall back to the stock
jnp path when a site is inactive or rejected.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import registry
from .rms_norm import _largest_divisor

__all__ = [
    "FusionSite", "SITES", "active", "activate", "make_fused", "verify_site",
    "verified_activation", "compile_refusal", "llama_site_shapes",
]

_FUSE_PRESETS = ("tiny", "small", "base", "longctx")


def _race_injected() -> bool:
    return (os.environ.get("KERNEL_GATE_INJECT", "").strip() == "emit-race"
            or os.environ.get("FUSE_GATE_INJECT", "").strip() == "emit-race")


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return bool(interpret)
    from ..framework import flags

    return bool(flags.get_flag("pallas_interpret"))


# ---------------------------------------------------------------------------
# jnp reference regions — the EXACT math of the model seams they replace.
# Any drift between these and the seam's stock path is caught bit-wise by
# tests and by the bench.py --fuse loss-identity check.
# ---------------------------------------------------------------------------

def _rms_rows(x, w, epsilon):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + epsilon)
    out = out * w.astype(jnp.float32)
    return out.astype(x.dtype)


def _swiglu_ref(hidden, w_gate_up, w_down, *, intermediate_size):
    """models/llama.py ``mlp_fn``: fused gate_up matmul -> SwiGLU -> down."""
    gu = hidden @ w_gate_up.astype(hidden.dtype)
    gate, up = jnp.split(gu, [intermediate_size], axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down.astype(hidden.dtype)


def _add_rms_norm_ref(x, h, w, *, epsilon):
    """Residual add + post-attention RMSNorm (+ the f32->compute-dtype cast
    epilogue inside the norm).  Returns (residual stream, normed)."""
    s = jnp.add(x, h)
    return s, _rms_rows(s, w, epsilon)


def _rms_norm_head_ref(hidden, w_norm, w_head, *, epsilon, transpose):
    """Final RMSNorm feeding the vocab projection (norm+matmul prologue)."""
    normed = _rms_rows(hidden, w_norm, epsilon)
    wh = w_head.T if transpose else w_head
    return normed @ wh.astype(normed.dtype)


# ---------------------------------------------------------------------------
# kernel emission machinery
# ---------------------------------------------------------------------------

def _full_spec(pl, shape):
    return pl.BlockSpec(shape, lambda i, _nd=len(shape): (0,) * _nd)


def _row_block_call(ref, row_args, full_args, n_row_outs, interpret,
                    block_cap=256, **static):
    """Emit a forward kernel: ``row_args`` (2D, same leading dim) stream
    through VMEM in row blocks, ``full_args`` (weights) are resident whole,
    and the kernel body traces ``ref`` on the block — the reference's own
    primitive sequence, fused.  Row-independence of every site's math makes
    the blocked result bit-identical to the unblocked reference."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = row_args[0].shape[0]
    br = _largest_divisor(n, block_cap)
    if _race_injected():
        # the seeded race needs more than one writer: shrink the block so
        # the grid has several points even at the small example shapes
        br = _largest_divisor(n, max(1, br // 4))
    grid = (n // br,)
    in_specs = ([pl.BlockSpec((br, a.shape[1]), lambda i: (i, 0))
                 for a in row_args]
                + [_full_spec(pl, a.shape) for a in full_args])
    n_rows = len(row_args)

    def kernel(*refs):
        ins = [r[...] for r in refs[:n_rows + len(full_args)]]
        outs = refs[n_rows + len(full_args):]
        vals = ref(*ins, **static)
        if not isinstance(vals, tuple):
            vals = (vals,)
        for o_ref, v in zip(outs, vals):
            o_ref[...] = v

    abstract = jax.eval_shape(lambda *a: ref(*a, **static),
                              *(row_args + full_args))
    if not isinstance(abstract, tuple):
        abstract = (abstract,)
    out_shape = [jax.ShapeDtypeStruct((n,) + s.shape[1:], s.dtype)
                 for s in abstract]
    out_specs = [pl.BlockSpec((br,) + s.shape[1:], lambda i: (i, 0))
                 for s in abstract]
    kwargs = {}
    if _race_injected():
        # seeded bad emission: every grid point stores to block 0 of output 0
        # along a parallel axis — krn-write-race + krn-coverage-hole; the
        # registry admission rail must refuse this before the first call
        out_specs[0] = pl.BlockSpec((br,) + abstract[0].shape[1:],
                                    lambda i: (0, 0))
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, **kwargs,
    )(*row_args, *full_args)
    return outs if len(out_shape) > 1 else outs[0]


def _single_block_call(body_ref, primals, cotangents, interpret, **static):
    """Emit a backward kernel: one grid point, every operand resident in
    VMEM, body = ``jax.vjp`` of the site reference — the exact primitive
    sequence autodiff runs in the unfused program, with every residual and
    intermediate kept on-chip (recompute-from-primals, the flash-attention
    move)."""
    from jax.experimental import pallas as pl

    n_p, n_c = len(primals), len(cotangents)

    def kernel(*refs):
        p = [r[...] for r in refs[:n_p]]
        c = [r[...] for r in refs[n_p:n_p + n_c]]
        outs = refs[n_p + n_c:]
        _, vjp = jax.vjp(lambda *a: body_ref(*a, **static), *p)
        grads = vjp(tuple(c) if n_c > 1 else c[0])
        for o_ref, g in zip(outs, grads):
            o_ref[...] = g

    ins = list(primals) + list(cotangents)
    out_shape = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in primals]
    return pl.pallas_call(
        kernel, grid=(1,),
        in_specs=[_full_spec(pl, a.shape) for a in ins],
        out_specs=[_full_spec(pl, s.shape) for s in out_shape],
        out_shape=out_shape, interpret=interpret,
    )(*ins)


# ---------------------------------------------------------------------------
# site catalogue
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FusionSite:
    """One emit-able fusion region: the audit pattern it realizes, the jnp
    reference whose math it must reproduce bit-for-bit, and how its audit
    candidates are recognized (source basenames / op_name jit scopes)."""

    name: str                      # registry name of the emitted fwd kernel
    pattern: str                   # audit pattern class this site realizes
    ref: Callable                  # jnp reference region (keyword statics)
    n_row_args: int                # leading args streamed in row blocks
    match_sources: Tuple[str, ...] = ()
    match_hints: Tuple[str, ...] = ()
    example_static: Dict[str, object] = field(default_factory=dict)
    example_shapes: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    description: str = ""

    def example_args(self):
        return tuple(jax.ShapeDtypeStruct(s, jnp.dtype(d))
                     for s, d in self.example_shapes)

    def matches(self, cand: Dict[str, object]) -> bool:
        # pattern agreement first: one source file can spawn regions of
        # different classes (rms_norm.py yields both the norm-prologue body
        # and per-layer cast epilogues) and each must route to the site that
        # realizes its class
        if cand.get("pattern") and cand["pattern"] != self.pattern:
            return False
        if cand.get("source") in self.match_sources:
            return True
        return bool(set(cand.get("op_hints") or ()) & set(self.match_hints))


SITES: Dict[str, FusionSite] = {}


def _add_site(site: FusionSite) -> None:
    SITES[site.name] = site


_add_site(FusionSite(
    name="fuse_swiglu_mlp",
    pattern="elementwise-chain",
    ref=_swiglu_ref,
    n_row_args=1,
    match_hints=("silu",),
    example_static=dict(intermediate_size=384),
    example_shapes=(((64, 128), "float32"), ((128, 768), "float32"),
                    ((384, 128), "float32")),
    description="SwiGLU MLP: gate_up matmul + silu*up chain + down matmul "
                "in one VMEM pass (elementwise chain around the dot)"))

_add_site(FusionSite(
    name="fuse_add_rms_norm",
    pattern="cast-epilogue",
    ref=_add_rms_norm_ref,
    n_row_args=2,
    match_sources=("rms_norm.py",),
    example_static=dict(epsilon=1e-6),
    example_shapes=(((64, 128), "float32"), ((64, 128), "float32"),
                    ((128,), "float32")),
    description="residual add + RMSNorm + dtype-cast epilogue: the residual "
                "stream and its norm leave VMEM exactly once"))

_add_site(FusionSite(
    name="fuse_rms_norm_head",
    pattern="norm-prologue",
    ref=_rms_norm_head_ref,
    n_row_args=1,
    match_sources=("rms_norm.py",),
    match_hints=("lm_head",),
    example_static=dict(epsilon=1e-6, transpose=False),
    example_shapes=(((64, 128), "float32"), ((128,), "float32"),
                    ((128, 512), "float32")),
    description="final RMSNorm feeding the vocab projection: norm+matmul "
                "prologue, row statistics never round-trip HBM"))


# ---------------------------------------------------------------------------
# fused callables (custom_vjp: emitted fwd kernel + emitted bwd kernel)
# ---------------------------------------------------------------------------

def _flatten_rows(arrays, n_row_args):
    """Collapse leading dims of the row-streamed args to 2D (weights pass
    through untouched); returns (flat_arrays, restore)."""
    lead = arrays[0].shape[:-1]
    flat = tuple(a.reshape(-1, a.shape[-1]) if i < n_row_args else a
                 for i, a in enumerate(arrays))

    def restore(v):
        return v.reshape(lead + v.shape[1:])

    return flat, restore


def _fwd_call(site: FusionSite, arrays, interpret, **static):
    flat, restore = _flatten_rows(arrays, site.n_row_args)
    out = _row_block_call(site.ref, list(flat[:site.n_row_args]),
                          list(flat[site.n_row_args:]), 1, interpret, **static)
    if isinstance(out, (tuple, list)):
        return tuple(restore(o) for o in out)
    return restore(out)


def _bwd_call(site: FusionSite, primals, cts, interpret, **static):
    flat, _ = _flatten_rows(primals, site.n_row_args)
    flat_cts = tuple(c.reshape(-1, c.shape[-1]) for c in cts)
    grads = _single_block_call(site.ref, flat, flat_cts, interpret, **static)
    return tuple(g.reshape(p.shape) for g, p in zip(grads, primals))


def make_fused(name: str, interpret: Optional[bool] = None) -> Callable:
    """Build the substituted callable for a site: a ``custom_vjp`` whose
    forward is the emitted row-blocked kernel and whose backward is the
    emitted vjp kernel.  Admission (``registry.ensure_admitted``) runs before
    the first ``pallas_call`` of each."""
    site = SITES[name]

    def call(*arrays, **static):
        itp = _resolve_interpret(interpret)
        registry.ensure_admitted(site.name)
        registry.ensure_admitted(site.name + "_bwd")

        @jax.custom_vjp
        def fused(*a):
            return _fwd_call(site, a, itp, **static)

        def fwd_rule(*a):
            return _fwd_call(site, a, itp, **static), a

        def bwd_rule(res, ct):
            cts = ct if isinstance(ct, tuple) else (ct,)
            return _bwd_call(site, res, cts, itp, **static)

        fused.defvjp(fwd_rule, bwd_rule)
        return fused(*arrays)

    call.site = site
    return call


# ---------------------------------------------------------------------------
# active-substitution table (installed by analysis.fusion_transform)
# ---------------------------------------------------------------------------

_ACTIVE: Dict[str, Callable] = {}


def active(name: str) -> Optional[Callable]:
    """The substituted callable for a site, or None (seam runs stock)."""
    return _ACTIVE.get(name)


@contextlib.contextmanager
def activate(mapping: Dict[str, Callable]):
    """Scope a set of substitutions (site name -> fused callable)."""
    saved = dict(_ACTIVE)
    _ACTIVE.update(mapping)
    try:
        yield
    finally:
        _ACTIVE.clear()
        _ACTIVE.update(saved)


# ---------------------------------------------------------------------------
# verification: interpret-mode bit-identity against the jnp reference
# ---------------------------------------------------------------------------

def _example_concrete(site: FusionSite):
    key = jax.random.PRNGKey(0)
    out = []
    for sds in site.example_args():
        key, sub = jax.random.split(key)
        out.append(jax.random.normal(sub, sds.shape, jnp.float32)
                   .astype(sds.dtype) * 0.1)
    return tuple(out)


def verify_site(name: str, interpret: bool = True):
    """Replay the emitted forward and backward kernels in interpret mode
    against the jnp reference and ``jax.vjp`` of it; every output must match
    BIT-FOR-BIT (the AdamW-kernel discipline).  All comparisons run under
    ``jax.jit`` on both sides — that is the compilation context the training
    step uses, and op-by-op eager dispatch rounds FMA-fusable chains
    differently than one compiled program does.

    Three legs, strictly ordered from local to global:

    1. forward kernel vs reference,
    2. backward kernel vs ``jax.vjp`` of the reference (same cotangents),
    3. end-to-end: ``jax.grad`` through the installed ``custom_vjp`` vs
       ``jax.grad`` through the stock path, under a data-dependent scalar
       loss.  Leg 3 is the one that catches XLA *context* divergence — e.g.
       a purely elementwise site whose stock forward+backward get fused with
       different FMA contraction than any standalone backward graph can
       reproduce.  Static lint cannot see that; this check can, and the
       transform then rejects the site (``fuse-verify-mismatch``).

    Returns an ``analysis.findings.Report`` — empty means the site is
    provably substitutable."""
    from ..analysis.findings import Report

    site = SITES[name]
    rep = Report()
    rep.meta["site"] = name
    args = _example_concrete(site)
    static = dict(site.example_static)

    def ref(*a):
        return site.ref(*a, **static)

    ref_out = jax.jit(ref)(*args)
    got = jax.jit(lambda *a: _fwd_call(site, a, interpret, **static))(*args)
    refs = ref_out if isinstance(ref_out, tuple) else (ref_out,)
    gots = got if isinstance(got, tuple) else (got,)
    for i, (r, g) in enumerate(zip(refs, gots)):
        if r.dtype != g.dtype or r.shape != g.shape or not jnp.array_equal(r, g):
            rep.add("fuse-verify-mismatch", "high",
                    f"emitted forward kernel output {i} diverges from the "
                    f"jnp reference in interpret mode",
                    where=f"{name}[out {i}]", bytes=r.size * r.dtype.itemsize,
                    suggestion="reject the site; seam stays on the stock path")
    # backward kernel vs jax.vjp of the reference, same cotangents
    key = jax.random.PRNGKey(1)
    cts = []
    for r in refs:
        key, sub = jax.random.split(key)
        cts.append(jax.random.normal(sub, r.shape, jnp.float32)
                   .astype(r.dtype) * 0.1)
    ct = tuple(cts) if len(cts) > 1 else cts[0]
    want = jax.jit(lambda a, c: jax.vjp(ref, *a)[1](c))(args, ct)
    have = jax.jit(
        lambda a, c: _bwd_call(site, a, c if isinstance(c, tuple) else (c,),
                               interpret, **static))(args, ct)
    for i, (w, h) in enumerate(zip(want, have)):
        if w.dtype != h.dtype or not jnp.array_equal(w, h):
            rep.add("fuse-verify-mismatch", "high",
                    f"emitted backward kernel grad {i} diverges from jax.vjp "
                    f"of the reference in interpret mode",
                    where=f"{name}[grad {i}]", bytes=w.size * w.dtype.itemsize,
                    suggestion="reject the site; seam stays on the stock path")
    # end-to-end: grads through the custom_vjp wiring vs the stock path,
    # data-dependent cotangents (a constant loss weight would let XLA fold
    # the cotangent into the stock backward and mask context divergence)
    key2 = jax.random.PRNGKey(2)
    weights = []
    for r in refs:
        key2, sub = jax.random.split(key2)
        weights.append(jax.random.normal(sub, r.shape, jnp.float32)
                       .astype(r.dtype))
    fused = make_fused(name, interpret=interpret)

    def scalar(fn, a):
        o = fn(*a)
        o = o if isinstance(o, tuple) else (o,)
        return sum(jnp.sum(x * w) for x, w in zip(o, weights))

    gs = jax.jit(jax.grad(lambda a: scalar(ref, a)))(args)
    gf = jax.jit(jax.grad(
        lambda a: scalar(lambda *x: fused(*x, **static), a)))(args)
    for i, (w, h) in enumerate(zip(gs, gf)):
        if not jnp.array_equal(w, h):
            rep.add("fuse-verify-mismatch", "high",
                    f"end-to-end grad {i} through the substituted site "
                    f"diverges from the stock path (XLA fusion-context "
                    f"rounding the standalone backward cannot reproduce)",
                    where=f"{name}[e2e grad {i}]",
                    bytes=w.size * w.dtype.itemsize,
                    suggestion="reject the site; seam stays on the stock path")
    return rep


def llama_site_shapes(cfg, rows: int) -> Dict[str, Tuple[tuple, dict]]:
    """``site -> (arg shapes, statics)`` at the widths a Llama config calls
    each seam with (``rows`` = batch x seq): what :func:`compile_refusal`
    asks the chip's compiler about."""
    act, par = jnp.dtype(cfg.dtype), jnp.dtype(cfg.pdtype)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    eps = cfg.rms_norm_eps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    tied = cfg.tie_word_embeddings
    return {
        "fuse_swiglu_mlp": (
            (sds((rows, H), act), sds((H, 2 * I), par), sds((I, H), par)),
            dict(intermediate_size=I)),
        "fuse_add_rms_norm": (
            (sds((rows, H), act), sds((rows, H), act), sds((H,), par)),
            dict(epsilon=eps)),
        "fuse_rms_norm_head": (
            (sds((rows, H), act), sds((H,), par),
             sds((V, H) if tied else (H, V), par)),
            dict(epsilon=eps, transpose=tied)),
    }


def compile_refusal(name: str, args, static, device=None) -> Optional[str]:
    """Ask the TPU's compiler for the site's forward and backward kernels at
    the shapes the program will call them with.  Returns the compiler's
    words when it refuses either, else None.

    ``device`` is a TPU device, attached or described
    (``jax.experimental.topologies``); default ``jax.devices()[0]``.  The
    backward holds every operand in one VMEM block, so a site that passes
    the registry's lint at its example shapes can still be refused at real
    widths — nothing but the compiler can say.  On a device that is not a
    TPU there is nothing to ask."""
    from jax.sharding import SingleDeviceSharding

    site = SITES[name]
    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return None
    on = SingleDeviceSharding(device)

    def placed(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on)

    args = tuple(placed(a) for a in args)
    outs = jax.eval_shape(lambda *a: site.ref(*a, **static), *args)
    cts = tuple(placed(o) for o in
                (outs if isinstance(outs, tuple) else (outs,)))
    try:
        jax.jit(lambda *a: _fwd_call(site, a, False, **static)
                ).lower(*args).compile()
        jax.jit(lambda p, c: _bwd_call(site, p, c, False, **static)
                ).lower(args, cts).compile()
    except Exception as e:  # the refusal, whatever its type, is the answer
        words = " ".join(str(e).split())
        return f"{type(e).__name__}: {words[:400]}"
    return None


def verified_activation(interpret: Optional[bool] = None, shapes=None,
                        device=None) -> Dict[str, Callable]:
    """Activation table of every site whose emitted kernels pass registry
    admission AND replay bit-exact (``verify_site``) AND, where ``shapes``
    (see :func:`llama_site_shapes`) names the site, compile for ``device``
    at those shapes — what a ``fuse=auto`` plan substitutes at run time.
    Inadmissible or divergent sites are left on the stock path; the
    reject-and-report findings for them live in
    ``analysis.fusion_transform.plan_transform``."""
    table: Dict[str, Callable] = {}
    for name in SITES:
        try:
            registry.admit(name)
            registry.admit(name + "_bwd")
        except registry.KernelRejected:
            continue
        if name in (shapes or {}) and compile_refusal(
                name, *shapes[name], device=device):
            continue
        if verify_site(name, interpret=True):
            continue
        table[name] = make_fused(name, interpret=interpret)
    return table


# ---------------------------------------------------------------------------
# registry entries: every emitted kernel passes the pallas_lint admission seam
# ---------------------------------------------------------------------------

def _fwd_builder(site: FusionSite):
    def build():
        def fn(*a):
            return _fwd_call(site, a, False, **site.example_static)
        return fn, site.example_args()
    return build


def _bwd_builder(site: FusionSite):
    def build():
        args = site.example_args()
        outs = jax.eval_shape(
            lambda *a: site.ref(*a, **site.example_static), *args)
        outs = outs if isinstance(outs, tuple) else (outs,)
        cts = tuple(jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs)

        def fn(*a):
            return _bwd_call(site, a[:len(args)], a[len(args):], False,
                             **site.example_static)
        return fn, args + cts
    return build


for _site in SITES.values():
    registry.register(_site.name, _fwd_builder(_site), presets=_FUSE_PRESETS,
                      description=f"emitted fusion kernel: {_site.description}")
    registry.register(_site.name + "_bwd", _bwd_builder(_site),
                      presets=_FUSE_PRESETS,
                      description=f"emitted vjp kernel for {_site.name} "
                                  "(recompute-from-primals, residuals in VMEM)")
