"""Fused single-pass AdamW update kernel.

Counterpart of the reference's fused/multi-tensor optimizer kernels
(``phi/kernels/fusion``: fused_adam, multi_tensor_adam).  The update is
bandwidth-bound: with fp32 parameters and moments every element costs 16 B
read (p, g, m, v) and 12 B written (p', m', v'), 28 B, plus 2 B where a
bf16 copy of the new parameter is written in the same pass.  At the
benchmark's ``mistral7b_d2_train`` (704.6M parameters on one v5e) that is
19.7 GB a step, 24.1 ms at 819 GB/s, against a step of 289.5 ms of which
the kernel took 28.7 ms (ledger, PR 28: ``jit_step_fn/adamw_fused`` 0.316 s
of an 11-step window).

Why a kernel when XLA already fuses elementwise chains: with fp32-stored
params as master weights the update is split by XLA into SEVERAL fusions —
the moment updates, the bias-corrected step, the decay multiply and the
bf16 down-cast of the new params land in different fusions whose
intermediates (m', v', p') round-trip HBM between them, and the down-cast
re-reads the fp32 result it just wrote (42 B a parameter against 30).  The
Pallas kernel is ONE pass: each block of (param, grad, m, v) is read into
VMEM once and every output is written from that same residency, p/m/v in
place.  (Whether XLA's update still loses on today's compiler is ROADMAP
S4's open half.)

Two ways of cutting a leaf into blocks, one kernel body, chosen from the
shape the call is handed (under ``kernels.per_shard``: the local shard's):

- **native** (``native_view``): ``ndim >= 2``, last dimension a multiple of
  128 and second-last a multiple of 8 (16 where a bf16 copy is written).
  The leaf is viewed ``[prod(leading), last]`` — a bitcast under the TPU's
  (8, 128) tiling of the last two dimensions — and cut into
  ``NATIVE_BLOCK`` blocks over a 2-D grid.  Nothing is padded, reshaped or
  copied: the compiled program holds the custom call alone
  (``tests/test_chip_compile.py``).  Every matrix of a transformer takes it:
  704.6M of ``mistral7b_d2_train``'s 704.6M parameters.
- **flat**: everything else (1-D norms and biases, last dimensions such as
  3 or 100, ragged shards) is flattened, padded and viewed ``[rows, 128]``
  in blocks of ``FLAT_BLOCK_ROWS`` rows.  On the TPU that view of a tiled
  matrix is NOT a bitcast: ``f32[32768,4096] -> f32[1048576,128]`` relays
  the whole array out through HBM, four times in and three times out a
  leaf.  With every leaf on this view those copies were the second-largest
  device op of the training step, more than the kernel itself
  (``reshape_reshape`` 0.350 s of the same 3.18-s window, 31.8 ms a step:
  ledger, PR 28); for the small leaves that take it they cost
  microseconds.

Bit-parity contract: the kernel reproduces ``optimizer.Optimizer``'s
reference update EXPRESSION-FOR-EXPRESSION (same op order, same fp32
scalar pre-computation), so interpret-mode results are bit-identical to the
jnp path — enforced by ``tests/test_fused_adamw.py`` on both layouts.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import obs
from . import registry

LANE = 128  # TPU lane width: the flat view is [rows, 128]
FLAT_BLOCK_ROWS = 512          # flat view: blocks of (512, 128)
# native view: blocks of the leaf's own rows.  Settled on the v5e in the
# benchmark's training step (PR 31): 29.03 ms of kernel a step against 29.26
# at (128, 1024), 29.46 at (256, 512), 29.48 at (64, 1024); seven
# double-buffered (256, 1024) blocks do not fit the core's VMEM
NATIVE_BLOCK = (64, 2048)


def adamw_reference(p32, g32, m, v, lr, step, *, beta1, beta2, epsilon,
                    weight_decay=0.0, decoupled=True, apply_decay=True):
    """The exact jnp update the kernel must bit-match (the expression order
    of ``Optimizer._build_update_fn`` + ``Adam._update``)."""
    if weight_decay and not decoupled:
        g32 = g32 + weight_decay * p32
    if weight_decay and decoupled and apply_decay:
        p32 = p32 * (1.0 - lr * weight_decay)
    m_new = beta1 * m + (1 - beta1) * g32
    v_new = beta2 * v + (1 - beta2) * jnp.square(g32)
    t = step.astype(jnp.float32)
    m_hat = m_new / (1 - beta1 ** t)
    v_hat = v_new / (1 - beta2 ** t)
    p_new = p32 - lr * m_hat / (jnp.sqrt(v_hat) + epsilon)
    return p_new, m_new, v_new


def _casts(out_dtype) -> bool:
    return out_dtype is not None and jnp.dtype(out_dtype) != jnp.float32


def native_view(shape, out_dtype=None):
    """``(prod(leading), last)`` where collapsing the leading dimensions of
    ``shape`` is a bitcast under the TPU's tiling of the last two, else None
    (the leaf then takes the flat view).  fp32 is tiled (8, 128); a bf16
    copy written in the same pass is tiled (16, 128)."""
    sublanes = 16 if _casts(out_dtype) else 8
    if len(shape) < 2 or shape[-1] % LANE or shape[-2] % sublanes:
        return None
    return math.prod(shape[:-1]), shape[-1]


@functools.partial(jax.jit, static_argnames=(
    "beta1", "beta2", "epsilon", "weight_decay", "decoupled", "apply_decay",
    "out_dtype", "interpret"))
def _adamw_fused_call(p32, g32, m, v, lr, step, *, beta1, beta2,
                      epsilon, weight_decay, decoupled, apply_decay,
                      out_dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # scalar pre-computation INSIDE the jitted module, with the reference's
    # exact expressions: the same HLO scalar ops get the same FMA-contraction
    # treatment from the backend, keeping results bit-identical to the jitted
    # reference chain (computing these eagerly costs 1 ulp on the decay
    # multiply — LLVM contracts 1.0 - lr*wd in-module but not across ops)
    lr = lr.astype(jnp.float32)
    t = step.astype(jnp.float32)
    c1 = 1 - beta1 ** t
    c2 = 1 - beta2 ** t
    if weight_decay and decoupled and apply_decay:
        decay = 1.0 - lr * weight_decay
    else:
        decay = jnp.float32(1.0)

    shape = p32.shape
    n = p32.size
    view = native_view(shape, out_dtype)
    if view is not None:
        # the leaf's own rows: the collapse is a bitcast, ragged edge blocks
        # are masked by Pallas, nothing is padded or copied
        block = tuple(min(b, d) for b, d in zip(NATIVE_BLOCK, view))
        args = [x.reshape(view) for x in (p32, g32, m, v)]

        def unview(x):
            return x.reshape(shape)
    else:
        rows = -(-n // LANE)
        # f32 min tile is (8, 128)
        block = (max(8, min(FLAT_BLOCK_ROWS, rows)), LANE)
        view = (-(-rows // block[0]) * block[0], LANE)
        args = [jnp.pad(x.reshape(-1), (0, view[0] * LANE - n)).reshape(view)
                for x in (p32, g32, m, v)]

        def unview(x):
            return x.reshape(-1)[:n].reshape(shape)
    # traced scalars ride in one prefetched SMEM vector; the static
    # hyperparams (beta1/beta2/eps/coupled-wd) are compile-time constants
    scal = jnp.stack([lr, jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32),
                      jnp.asarray(decay, jnp.float32)])

    cast = _casts(out_dtype)

    def kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
               *maybe_cast_ref):
        lr_s = scal_ref[0]
        c1_s = scal_ref[1]
        c2_s = scal_ref[2]
        decay_s = scal_ref[3]
        p = p_ref[...]
        g = g_ref[...]
        if weight_decay and not decoupled:
            g = g + weight_decay * p
        p = p * decay_s
        m_new = beta1 * m_ref[...] + (1 - beta1) * g
        v_new = beta2 * v_ref[...] + (1 - beta2) * jnp.square(g)
        m_hat = m_new / c1_s
        v_hat = v_new / c2_s
        p_new = p - lr_s * m_hat / (jnp.sqrt(v_hat) + epsilon)
        po_ref[...] = p_new
        mo_ref[...] = m_new
        vo_ref[...] = v_new
        if cast:
            maybe_cast_ref[0][...] = p_new.astype(maybe_cast_ref[0].dtype)

    blk = pl.BlockSpec(block, lambda i, j, *_: (i, j))
    out_shapes = [jax.ShapeDtypeStruct(view, jnp.float32)] * 3
    if cast:
        out_shapes.append(jax.ShapeDtypeStruct(view, out_dtype))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=tuple(pl.cdiv(d, b) for d, b in zip(view, block)),
            in_specs=[blk] * 4,
            out_specs=[blk] * len(out_shapes),
        ),
        out_shape=out_shapes,
        # p/m/v blocks are overwritten in place — the kernel's HBM footprint
        # is the state itself plus the (optional) model-dtype copy
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="adamw_fused",
    )(scal, *args)

    p_new, m_new, v_new = (unview(o) for o in outs[:3])
    p_out = unview(outs[3]) if cast else p_new
    return p_new, m_new, v_new, p_out


def adamw_update(p32, g32, m, v, lr, step, *, beta1, beta2, epsilon,
                 weight_decay=0.0, decoupled=True, apply_decay=True,
                 out_dtype=None, interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass fused AdamW/Adam step over one (param, grad, m, v) tuple.

    All arrays are fp32 with identical shapes; the kernel cuts them into
    blocks of their own rows where that costs no copy (``native_view``) and
    of the flat ``[rows, 128]`` view otherwise.  ``lr`` is a traced fp32
    scalar and ``step`` a traced int32 scalar; ``beta1/beta2/epsilon/
    weight_decay`` are Python floats (compile-time constants, like the
    reference's attrs).

    Returns ``(p_new32, m_new, v_new, p_out)`` where ``p_out`` is the
    ``out_dtype`` copy of ``p_new32`` written in the SAME kernel pass
    (``p_out is p_new32`` when no cast is needed) — the master-weight mode
    costs one extra low-precision write instead of a full read+write pass.
    """
    registry.ensure_admitted("adamw_fused")
    out_dtype = None if out_dtype is None else jnp.dtype(out_dtype).name
    # how often the copy-free layout engages: once a leaf each time a program
    # that holds it is traced (or an eager call made), never in a compiled step
    layout = "flat" if native_view(p32.shape, out_dtype) is None else "native"
    obs.registry().counter("optimizer.fused_bytes", layout=layout).inc(
        p32.size * p32.dtype.itemsize)
    return _adamw_fused_call(
        p32, g32, m, v, jnp.asarray(lr, jnp.float32),
        jnp.asarray(step, jnp.int32),
        beta1=float(beta1), beta2=float(beta2), epsilon=float(epsilon),
        weight_decay=float(weight_decay), decoupled=bool(decoupled),
        apply_decay=bool(apply_decay), out_dtype=out_dtype,
        interpret=bool(interpret))


def _registry_example():
    # one leaf of each layout, two blocks along every grid axis: the
    # verifier's VMEM model and write coverage see both ways of cutting
    sds = jax.ShapeDtypeStruct
    fn = functools.partial(
        _adamw_fused_call, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, decoupled=True, apply_decay=True,
        out_dtype="bfloat16", interpret=False)

    def both(flat, native, lr, step):
        return fn(*flat, lr, step), fn(*native, lr, step)

    rows, cols = NATIVE_BLOCK
    return both, ((sds((2 * FLAT_BLOCK_ROWS * LANE,), jnp.float32),) * 4,
                  (sds((2, rows, 2 * cols), jnp.float32),) * 4,
                  sds((), jnp.float32), sds((), jnp.int32))


registry.register(
    "adamw_fused", _registry_example,
    presets=("tiny", "small", "base", "longctx", "moe", "ocr"),
    description="single-pass fused AdamW: p/m/v aliased in place + bf16 "
                "cast epilogue, blocks of the leaf's own rows or of the "
                "flat [rows, 128] view")


def fused_enabled() -> Tuple[bool, bool]:
    """(enabled, interpret): the fused optimizer kernel runs when Pallas
    kernels are on (TPU) or ``FLAGS_pallas_interpret`` asks for interpret
    mode (CPU tests/parity)."""
    from ..framework import flags

    from . import use_pallas

    interpret = bool(flags.get_flag("pallas_interpret"))
    return (use_pallas() or interpret), interpret
