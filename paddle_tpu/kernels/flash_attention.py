"""Flash attention for TPU.

Counterpart of the reference's flash-attention integration
(``phi/kernels/gpu/flash_attn_kernel.cu:587`` ``FlashAttnKernel`` dynloading
``third_party/flashattn``).  This is NOT a port: the TPU kernel is a Pallas
implementation of the memory-efficient attention algorithm (online softmax over
KV blocks), designed around VMEM tiling and the MXU.

Layout convention follows the reference's API (``nn/functional/flash_attention.py``):
``q, k, v: [batch, seq, num_heads, head_dim]``.

TPU tiling note: the softmax statistics (lse, delta) are carried as
``[BH, 1, S]`` so their blocks ``(1, 1, block)`` satisfy Mosaic's trailing-two
-dims rule ((div 8, div 128) or equal-to-array).

The XLA reference path is used on CPU and as the numerics oracle in tests;
``interpret=True`` runs the Pallas kernels on CPU for CI.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import registry

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA reference implementation
# ---------------------------------------------------------------------------

def _attention_reference(q, k, v, causal: bool, mask, sm_scale: float):
    # [B, S, H, D] -> [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * sm_scale
    sq, sk = scores.shape[-2], scores.shape[-1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(causal_mask, scores, NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, NEG_INF)
        else:
            scores = scores + mask.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel (fwd + bwd)
# ---------------------------------------------------------------------------

def _causal_mask(s, qi, ki, block_q, block_k, seq_offset):
    """Mask scores s [block_q, block_k] to q_pos + seq_offset >= k_pos, where
    seq_offset = Sk - Sq aligns the causal diagonal for cross attention."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos + seq_offset >= k_pos, s, NEG_INF)


def _causal_hi(qi, block_q, block_k, seq_offset, n_k):
    """Exclusive upper bound on k-blocks visible to q-block qi."""
    return jnp.minimum(((qi + 1) * block_q + seq_offset + block_k - 1) // block_k, n_k)


def _causal_lo(ki, block_q, block_k, seq_offset):
    """First q-block that can see k-block ki."""
    return jnp.maximum((ki * block_k - seq_offset) // block_q, 0)


def _pallas_flash(q, k, v, causal: bool, sm_scale: float,
                  block_q: int = 128, block_k: int = 128, interpret: bool = False):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    # operate in [B*H, S, D]
    qr = jnp.swapaxes(q, 1, 2).reshape(B * H, Sq, D)
    kr = jnp.swapaxes(k, 1, 2).reshape(B * H, Sk, D)
    vr = jnp.swapaxes(v, 1, 2).reshape(B * H, Sk, D)

    out = _flash_fwd_bh(qr, kr, vr, causal, sm_scale, block_q, block_k, interpret)
    return jnp.swapaxes(out.reshape(B, H, Sq, D), 1, 2).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_fwd_bh(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o


# full-KV (or full-Q) residency budget per kernel instance; beyond it the
# streaming variants page blocks through a third grid dimension instead
# (v5e scoped VMEM is ~16MB; 2 resident streams of Sk*D*2B must fit beside
# the working blocks)
_VMEM_RESIDENT_BYTES = 2 * 1024 * 1024


def _resident_ok(S: int, D: int, itemsize: int) -> bool:
    return S * D * itemsize <= _VMEM_RESIDENT_BYTES


def _replicated(vec, width: int = 128):
    """[n] -> [n, width] lane-replicated (TPU scratch wants 2D tiles)."""
    return jnp.broadcast_to(vec[:, None], (vec.shape[0], width))


def _flash_fwd_stream(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Streaming forward: grid (BH, n_q, n_k) with K/V paged per k-step and
    the online-softmax state carried in VMEM scratch — VMEM use is O(block)
    regardless of sequence length (the resident kernel keeps full K/V in
    VMEM and dies around seq 16k on v5e)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Sq, D = q.shape
    Sk = k.shape[1]
    n_q = Sq // block_q
    n_k = Sk // block_k
    off = Sk - Sq

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        active = (ki * block_k <= qi * block_q + block_q - 1 + off) \
            if causal else (ki >= 0)

        @pl.when(active)
        def _step():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, off)
            m_prev = jnp.max(m_ref[...], axis=1)   # lane-replicated -> [bq]
            l_prev = jnp.max(l_ref[...], axis=1)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = _replicated(alpha * l_prev + jnp.sum(p, axis=1))
            acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[...] = _replicated(m_new)

        @pl.when(ki == n_k - 1)
        def _finalize():
            l_fin = jnp.max(l_ref[...], axis=1)
            m_fin = jnp.max(m_ref[...], axis=1)
            l_safe = jnp.maximum(l_fin, 1e-30)
            o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0] = (m_fin + jnp.log(l_safe)).astype(jnp.float32)

    if causal:
        # clamp the paged K/V index into the active (<= diagonal) range:
        # pl.when skips the COMPUTE of masked steps, but the pipeline would
        # still DMA their blocks — a repeated identical index elides the fetch
        def kv_idx(b, i, j):
            # hi can be negative when Sq > Sk (off < 0): clamp to 0 so early
            # q-blocks never emit a negative (out-of-range) DMA block index —
            # their compute is already masked off by pl.when
            hi = (i * block_q + block_q - 1 + off) // block_k
            return (b, jnp.maximum(jnp.minimum(j, hi), 0), 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)

    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, D), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_stream",
    )(q, k, v)
    return o, lse


def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """q,k,v: [BH, S, D]. Returns (o, lse) with lse: [BH, 1, Sq]."""
    from jax.experimental import pallas as pl

    BH, Sq, D = q.shape
    Sk = k.shape[1]
    if not _resident_ok(Sk, D, q.dtype.itemsize):
        return _flash_fwd_stream(q, k, v, causal, sm_scale, block_q, block_k,
                                 interpret)
    n_q = Sq // block_q
    n_k = Sk // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
        qi = pl.program_id(1)
        qb = q_ref[0].astype(jnp.float32)  # [block_q, D]

        def body(ki, carry):
            acc, m_prev, l_prev = carry
            kb = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, Sk - Sq)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((block_q, D), jnp.float32)
        m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        hi = _causal_hi(qi, block_q, block_k, Sk - Sq, n_k) if causal else n_k
        acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l_safe)).astype(jnp.float32)

    grid = (BH, n_q)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            # stats carried [BH, 1, Sq]: trailing block dims (1, block_q)
            # satisfy Mosaic tiling ((equal-to-array, div 128))
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k, interpret)
    return dq, dk, dv


_flash_fwd_bh.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _flash_bwd_stream(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
                      interpret):
    """Streaming two-pass backward: the opposing operand is paged through a
    third grid dim with accumulators in VMEM scratch (see _flash_fwd_stream)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Sq, D = q.shape
    Sk = k.shape[1]
    n_q = Sq // block_q
    n_k = Sk // block_k
    off = Sk - Sq

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc_ref, dv_acc_ref):
        ki = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

        # causal: q block contributes iff its last row reaches this k block
        active = (qi * block_q + block_q - 1 + off >= ki * block_k) \
            if causal else (qi >= 0)

        @pl.when(active)
        def _step():
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            qb = q_ref[0].astype(jnp.float32)
            dob = do_ref[0].astype(jnp.float32)
            lseb = lse_ref[0, 0]
            deltab = delta_ref[0, 0]
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, off)
            p = jnp.exp(s - lseb[:, None])
            dv_acc_ref[...] += jax.lax.dot_general(
                p, dob, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - deltab[:, None]) * sm_scale
            dk_acc_ref[...] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(qi == n_q - 1)
        def _finalize():
            dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)

    if causal:
        # q-side blocks below the causal lower bound never contribute to this
        # k block; clamping the index avoids their DMA (see fwd kv_idx)
        def q_row(i, j):
            lo = jnp.maximum((i * block_k - off) // block_q, 0)
            return jnp.maximum(j, lo)
    else:
        def q_row(i, j):
            return j

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, q_row(i, j), 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, q_row(i, j), 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, q_row(i, j))),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, q_row(i, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv_stream",
    )(q, k, v, do, lse, delta)

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_acc_ref):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

        active = (ki * block_k <= qi * block_q + block_q - 1 + off) \
            if causal else (ki >= 0)

        @pl.when(active)
        def _step():
            qb = q_ref[0].astype(jnp.float32)
            dob = do_ref[0].astype(jnp.float32)
            lseb = lse_ref[0, 0]
            deltab = delta_ref[0, 0]
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, off)
            p = jnp.exp(s - lseb[:, None])
            dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - deltab[:, None]) * sm_scale
            dq_acc_ref[...] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(ki == n_k - 1)
        def _finalize():
            dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)

    if causal:
        def kv_idx(b, i, j):
            # hi can be negative when Sq > Sk (off < 0): clamp to 0 so early
            # q-blocks never emit a negative (out-of-range) DMA block index —
            # their compute is already masked off by pl.when
            hi = (i * block_q + block_q - 1 + off) // block_k
            return (b, jnp.maximum(jnp.minimum(j, hi), 0), 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_k, D), kv_idx),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq_stream",
    )(q, k, v, do, lse, delta)

    return dq, dk, dv


def _flash_bwd_impl(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k, interpret):
    """Two-pass flash backward: dKV pass (grid over KV blocks) and dQ pass.

    lse: [BH, 1, Sq] (fp32); delta is computed the same shape.
    """
    from jax.experimental import pallas as pl

    BH, Sq, D = q.shape
    Sk = k.shape[1]
    if not (_resident_ok(Sk, D, q.dtype.itemsize)
            and _resident_ok(Sq, D, q.dtype.itemsize)):
        return _flash_bwd_stream(q, k, v, o, lse, do, causal, sm_scale,
                                 block_q, block_k, interpret)
    n_q = Sq // block_q
    n_k = Sk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]  # [BH, 1, Sq]

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref):
        ki = pl.program_id(1)
        kb = k_ref[0].astype(jnp.float32)  # [block_k, D]
        vb = v_ref[0].astype(jnp.float32)

        def body(qi, carry):
            dk_acc, dv_acc = carry
            qb = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
            dob = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
            lseb = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
            deltab = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, Sk - Sq)
            p = jnp.exp(s - lseb[:, None])  # [bq, bk]
            dv_acc = dv_acc + jax.lax.dot_general(p, dob, (((0,), (0,)), ((), ())),
                                                  preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - deltab[:, None]) * sm_scale
            dk_acc = dk_acc + jax.lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                                  preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        lo = _causal_lo(ki, block_q, block_k, Sk - Sq) if causal else 0
        dk_acc0 = jnp.zeros((block_k, D), jnp.float32)
        dv_acc0 = jnp.zeros((block_k, D), jnp.float32)
        dk_acc, dv_acc = jax.lax.fori_loop(lo, n_q, body, (dk_acc0, dv_acc0))
        dk_ref[0] = dk_acc.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc.astype(dv_ref.dtype)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, n_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Sq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), q.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref):
        qi = pl.program_id(1)
        qb = q_ref[0].astype(jnp.float32)
        dob = do_ref[0].astype(jnp.float32)
        lseb = lse_ref[0, 0]
        deltab = delta_ref[0, 0]

        def body(ki, dq_acc):
            kb = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, Sk - Sq)
            p = jnp.exp(s - lseb[:, None])
            dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - deltab[:, None]) * sm_scale
            return dq_acc + jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                                preferred_element_type=jnp.float32)

        hi = _causal_hi(qi, block_q, block_k, Sk - Sq, n_k) if causal else n_k
        dq_acc = jax.lax.fori_loop(0, hi, body, jnp.zeros((block_q, D), jnp.float32))
        dq_ref[0] = dq_acc.astype(dq_ref.dtype)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = False, mask=None, sm_scale: Optional[float] = None,
                    interpret: bool = False, block_q: int = 512, block_k: int = 512,
                    shard=None):
    """Memory-efficient attention. q,k,v: [B, S, H, D] jax arrays.

    ``shard``: ``(jax Mesh, PartitionSpec)`` where q, k and v are laid out
    over several devices (batch and/or heads; sequence and head dim whole) —
    the kernel then runs per shard.

    ``interpret=True`` forces the Pallas kernel in interpreter mode (CPU CI).
    Block sizes are clamped to the sequence lengths; 512x512 measured fastest
    on v5e at seq 2048 (6.8ms vs 11.9ms at 128x128 for one fwd+bwd layer —
    PERF.md).
    """
    from . import per_shard, use_pallas

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if shard is not None and (use_pallas() or interpret):
        # GQA repeats kv heads below: shard first, so each device repeats
        # its own
        return per_shard(
            lambda q, k, v: flash_attention(q, k, v, causal, mask, sm_scale,
                                            interpret, block_q, block_k),
            shard, 3)(q, k, v)

    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hk = k.shape[2]
    if Hk != H and Hk > 0 and H % Hk == 0:
        # grouped-query attention: repeat KV heads
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    kernel_shapes_ok = (
        mask is None
        and D in (64, 128, 256)
        and Sq % block_q == 0
        and Sk % block_k == 0
        and block_q % 128 == 0
        and block_k % 128 == 0
    )
    if interpret and not kernel_shapes_ok:
        raise ValueError(
            "flash_attention(interpret=True) requires kernel-compatible shapes "
            f"(mask=None, D in 64/128/256, S % block == 0); got D={D}, Sq={Sq}, Sk={Sk}")
    pallas_ok = (use_pallas() or interpret) and kernel_shapes_ok
    if pallas_ok:
        registry.ensure_admitted("flash_fwd_resident")
        registry.ensure_admitted("flash_fwd_stream")
        return _pallas_flash(q, k, v, causal, sm_scale,
                             block_q=block_q, block_k=block_k, interpret=interpret)
    return _attention_reference(q, k, v, causal, mask, sm_scale)


def _registry_args():
    sds = jax.ShapeDtypeStruct
    BH, S, D = 2, 256, 128
    return sds((BH, S, D), jnp.float32)


def _registry_fwd_resident():
    z = _registry_args()
    return (lambda q, k, v: _flash_fwd_impl(q, k, v, False, 1.0, 128, 128,
                                            False), (z, z, z))


def _registry_fwd_stream():
    # causal=True exercises the clamped KV index map (the evaluated, non-
    # affine path of the verifier) plus the online-softmax scratch carry
    z = _registry_args()
    return (lambda q, k, v: _flash_fwd_stream(q, k, v, True, 1.0, 128, 128,
                                              False), (z, z, z))


def _registry_bwd_stream():
    z = _registry_args()
    lse = jax.ShapeDtypeStruct((2, 1, 256), jnp.float32)
    return (lambda q, k, v, o, lse, do: _flash_bwd_stream(
        q, k, v, o, lse, do, True, 1.0, 128, 128, False),
        (z, z, z, z, lse, z))


_FLASH_PRESETS = ("tiny", "small", "base", "longctx", "moe", "ocr")
registry.register("flash_fwd_resident", _registry_fwd_resident,
                  presets=_FLASH_PRESETS,
                  description="flash attention forward, full-KV residency")
registry.register("flash_fwd_stream", _registry_fwd_stream,
                  presets=_FLASH_PRESETS,
                  description="streaming flash forward: causal KV paging + "
                              "online-softmax VMEM carry")
registry.register("flash_bwd_stream", _registry_bwd_stream,
                  presets=_FLASH_PRESETS,
                  description="streaming flash backward (dk/dv + dq passes)")
