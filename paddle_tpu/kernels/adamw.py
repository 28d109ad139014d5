"""Fused single-pass AdamW update kernel.

Counterpart of the reference's fused/multi-tensor optimizer kernels
(``phi/kernels/fusion``: fused_adam, multi_tensor_adam) — and the direct
attack on the largest non-matmul slice of the base preset: PERF.md's xplane
breakdown puts **~28% of the train step in AdamW elementwise**, which is
bandwidth-bound (every byte of p/g/m/v crosses HBM once per op in the
unfused chain).

Why a kernel when XLA already fuses elementwise chains: with fp32-stored
params as master weights (the base-preset recipe) the update is split by XLA
into SEVERAL fusions — the moment updates, the bias-corrected step, the
decay multiply and the bf16 down-cast of the new params land in different
fusions whose intermediates (m', v', p') round-trip HBM between them, and
the down-cast re-reads the fp32 result it just wrote.  The Pallas kernel is
ONE pass: each block of (param, grad, m, v) is read into VMEM once and every
output (new param, new m, new v, and the optional model-dtype cast of the
new param) is written from that same residency.

Traffic model per element (fp32 state, bf16 model copy):

    unfused chain (measured fusion split):  read p,g,m,v (16B)
        + write m',v' (8B) + re-read m',v' for the step (8B)
        + write p' (4B) + re-read p' for the cast (4B) + write bf16 (2B)
        = 42 B/param
    fused single pass:                      read p,g,m,v (16B)
        + write p',m',v' (12B) + write bf16 copy (2B)
        = 30 B/param   (1.4x);  with the update SHARDED over N replicas the
          per-chip slice is 30/N + the param all-gather — see
          ``Optimizer.shard_update``.

Bit-parity contract: the kernel reproduces ``optimizer.Optimizer``'s
reference update EXPRESSION-FOR-EXPRESSION (same op order, same fp32
scalar pre-computation), so interpret-mode results are bit-identical to the
jnp path — enforced by ``tests/test_fused_adamw.py``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import registry

LANE = 128  # TPU lane width: flat buffers are viewed as [rows, 128]


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


def _pad_rows(flat, rows, block_rows):
    n = flat.shape[0]
    target = rows * LANE
    if target != n:
        flat = jnp.pad(flat, (0, target - n))
    return flat.reshape(rows, LANE)


@functools.partial(jax.jit, static_argnames=(
    "beta1", "beta2", "epsilon", "weight_decay", "decoupled", "apply_decay",
    "out_dtype", "block_rows", "interpret"))
def _adamw_fused_call(p32, g32, m, v, lr, step, *, beta1, beta2,
                      epsilon, weight_decay, decoupled, apply_decay,
                      out_dtype, block_rows, interpret):
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
    rows = -(-n // LANE)
    block_rows = max(8, min(block_rows, rows))  # f32 min tile is (8, 128)
    nb = -(-rows // block_rows)
    rows = nb * block_rows

    args = [_pad_rows(x.reshape(-1), rows, block_rows)
            for x in (p32, g32, m, v)]
    # traced scalars ride in one prefetched SMEM vector; the static
    # hyperparams (beta1/beta2/eps/coupled-wd) are compile-time constants
    scal = jnp.stack([lr, jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32),
                      jnp.asarray(decay, jnp.float32)])

    cast = out_dtype is not None and jnp.dtype(out_dtype) != jnp.float32

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

    blk = pl.BlockSpec((block_rows, LANE), lambda i, *_: (i, 0))
    out_shapes = [jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * 3
    if cast:
        out_shapes.append(jax.ShapeDtypeStruct((rows, LANE), out_dtype))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
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

    def unpad(x):
        return x.reshape(-1)[:n].reshape(shape)

    p_new, m_new, v_new = (unpad(o) for o in outs[:3])
    p_out = unpad(outs[3]) if cast else p_new
    return p_new, m_new, v_new, p_out


def adamw_update(p32, g32, m, v, lr, step, *, beta1, beta2, epsilon,
                 weight_decay=0.0, decoupled=True, apply_decay=True,
                 out_dtype=None, block_rows: int = 512,
                 interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass fused AdamW/Adam step over one (param, grad, m, v) tuple.

    All arrays are fp32 with identical shapes (flattened internally to the
    lane-major ``[rows, 128]`` view).  ``lr`` is a traced fp32 scalar and
    ``step`` a traced int32 scalar; ``beta1/beta2/epsilon/weight_decay`` are
    Python floats (compile-time constants, like the reference's attrs).

    Returns ``(p_new32, m_new, v_new, p_out)`` where ``p_out`` is the
    ``out_dtype`` copy of ``p_new32`` written in the SAME kernel pass
    (``p_out is p_new32`` when no cast is needed) — the master-weight mode
    costs one extra low-precision write instead of a full read+write pass.
    """
    registry.ensure_admitted("adamw_fused")
    return _adamw_fused_call(
        p32, g32, m, v, jnp.asarray(lr, jnp.float32),
        jnp.asarray(step, jnp.int32),
        beta1=float(beta1), beta2=float(beta2), epsilon=float(epsilon),
        weight_decay=float(weight_decay), decoupled=bool(decoupled),
        apply_decay=bool(apply_decay),
        out_dtype=None if out_dtype is None else jnp.dtype(out_dtype).name,
        block_rows=int(block_rows), interpret=bool(interpret))


def _registry_example():
    sds = jax.ShapeDtypeStruct
    z = sds((2048,), jnp.float32)
    fn = functools.partial(
        _adamw_fused_call, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, decoupled=True, apply_decay=True,
        out_dtype="bfloat16", block_rows=8, interpret=False)
    return fn, (z, z, z, z, sds((), jnp.float32), sds((), jnp.int32))


registry.register(
    "adamw_fused", _registry_example,
    presets=("tiny", "small", "base", "longctx", "moe", "ocr"),
    description="single-pass fused AdamW: p/m/v aliased in place + bf16 "
                "cast epilogue")


def fused_enabled() -> Tuple[bool, bool]:
    """(enabled, interpret): the fused optimizer kernel runs when Pallas
    kernels are on (TPU) or ``FLAGS_pallas_interpret`` asks for interpret
    mode (CPU tests/parity)."""
    from ..framework import flags

    from . import use_pallas

    interpret = bool(flags.get_flag("pallas_interpret"))
    return (use_pallas() or interpret), interpret
