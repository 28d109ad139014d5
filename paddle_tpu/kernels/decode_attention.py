"""Decode-time attention: KV-cache attention, decode-MHA Pallas kernel, paged attention.

Counterparts of the reference's LLM-inference fused kernels:

- ``masked_multihead_attention`` — decode attention over a dense KV cache
  (``paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu``,
  Python API ``incubate/nn/functional/masked_multihead_attention.py``).
- ``block_multi_head_attention`` — paged KV-cache attention
  (``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``,
  Python API ``incubate/nn/functional/block_multihead_attention.py``).

TPU-native design, not a port:

- The cache is a dense ``[B, capacity, kv_heads, head_dim]`` ring written with
  ``lax.dynamic_update_slice`` (static shapes keep XLA happy; the reference
  grows CUDA buffers instead).
- Prefill attends with an absolute-position causal mask; decode (S=1) is a
  Pallas online-softmax kernel over the cache with a length mask — a GQA GEMV
  that is HBM-bandwidth-bound, so the kernel's job is to stream K/V exactly
  once (the reference's kernel splits over cache chunks the same way).
- The paged layout keeps fixed-size blocks addressed by a per-sequence block
  table; the gather is XLA ``take`` over the block axis (the reference walks
  the table inside the CUDA kernel).
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
# XLA reference paths
# ---------------------------------------------------------------------------

def cached_attention_reference(q, k_cache, v_cache, offset, sm_scale: Optional[float] = None):
    """Attention of a chunk against the (already updated) KV cache.

    q: ``[B, S, H, D]`` at absolute positions ``offset .. offset+S``;
    k_cache/v_cache: ``[B, C, Hk, D]``.  Causal against absolute positions:
    row ``i`` sees cache slots ``j <= offset + i``.  Returns ``[B, S, H, D]``.
    """
    B, S, h, d = q.shape
    C, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32).reshape(B, S, hk, rep, d)
    s = jnp.einsum("bsgrd,bcgd->bgrsc", qf, k_cache.astype(jnp.float32)) * sm_scale
    q_pos = offset + jnp.arange(S)
    mask = jnp.arange(C)[None, :] <= q_pos[:, None]  # [S, C]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrsc,bcgd->bsgrd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, S, h, d).astype(q.dtype)


def _decode_reference(q, k_cache, v_cache, lengths, sm_scale: float):
    """Single-step decode with per-sequence lengths. q: [B, 1, H, D]; lengths: [B]."""
    B, _, h, d = q.shape
    C, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    qf = q.astype(jnp.float32).reshape(B, 1, hk, rep, d)
    s = jnp.einsum("bsgrd,bcgd->bgrsc", qf, k_cache.astype(jnp.float32)) * sm_scale
    mask = jnp.arange(C)[None, :] < lengths[:, None]  # [B, C]
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # the p@v contraction runs over masked positions too (weight 0); V there
    # may be arbitrary pool trash including NaN, and 0*NaN = NaN — zero it
    vf = jnp.where(mask[:, :, None, None], v_cache.astype(jnp.float32), 0.0)
    o = jnp.einsum("bgrsc,bcgd->bsgrd", p, vf)
    return o.reshape(B, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas decode kernel (masked_multihead_attention role)
# ---------------------------------------------------------------------------

def _pallas_decode(q, k_cache, v_cache, lengths, sm_scale: float,
                   block_k: int = 128, interpret: bool = False):
    """q: [B, 1, H, D]; caches [B, C, Hk, D]; lengths: [B] int32.

    Grid over (B * Hk); each program streams that head's cache once, carrying
    online-softmax stats for its ``rep = H/Hk`` query rows.  Only blocks below
    the live length are visited.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, h, d = q.shape
    C, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    n_k = C // block_k

    qr = q.reshape(B, hk, rep, d).reshape(B * hk, rep, d)
    kr = jnp.swapaxes(k_cache, 1, 2).reshape(B * hk, C, d)
    vr = jnp.swapaxes(v_cache, 1, 2).reshape(B * hk, C, d)
    # per-program live length, scalar-prefetched into SMEM (Mosaic rejects
    # sub-(8,128) VMEM blocks; SMEM is where control scalars belong anyway)
    len_r = jnp.broadcast_to(lengths.astype(jnp.int32)[:, None], (B, hk)).reshape(B * hk)

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref):
        qb = q_ref[0].astype(jnp.float32)  # [rep, d]
        L = len_ref[pl.program_id(0)]

        def body(ki, carry):
            acc, m_prev, l_prev = carry
            kb = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (rep, block_k), 1)
            s = jnp.where(k_pos < L, s, NEG_INF)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((rep, d), jnp.float32)
        m0 = jnp.full((rep,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rep,), jnp.float32)
        hi = jnp.minimum((L + block_k - 1) // block_k, n_k)
        acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * hk,),
            in_specs=[
                pl.BlockSpec((1, rep, d), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, C, d), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, C, d), lambda b, *_: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, rep, d), lambda b, *_: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B * hk, rep, d), q.dtype),
        interpret=interpret,
        name="dense_decode",
    )(len_r, qr, kr, vr)
    return out.reshape(B, hk, rep, d).reshape(B, 1, h, d)


def _fused_softmax_block(qb, kb, vb, base_pos, L, sm_scale, carry,
                         heads_axis: int):
    """One online-softmax step shared by the fused decode kernels.

    qb: [hk, rep, d] fp32; kb/vb: VMEM buffers in their NATIVE layout —
    ``heads_axis`` says where the kv-head dim sits ([bk, hk, d] for the
    dense cache, [hk, bs, d] for the paged pool).  Mosaic's batched matmul
    requires the batch dim LEADING on both operands (compile-checked on a
    v5e: ``tpu.matmul`` rejects mixed batch positions with "batch dims must
    be equal"), so a non-leading heads axis is relayouted here — a
    VMEM-local vector shuffle, NOT the per-step full-cache HBM transpose
    this kernel family exists to avoid.  base_pos: absolute position of the
    block's first row.  Returns the updated (acc, m, l).
    """
    acc, m_prev, l_prev = carry
    hk, rep, _ = qb.shape
    if heads_axis != 0:
        kb = jnp.swapaxes(kb, 0, 1)
        vb = jnp.swapaxes(vb, 0, 1)
    kf = kb.astype(jnp.float32)
    vf = vb.astype(jnp.float32)
    bk = kf.shape[1]
    s = jax.lax.dot_general(qb, kf, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    k_pos = base_pos + jax.lax.broadcasted_iota(jnp.int32, (hk, rep, bk), 2)
    s = jnp.where(k_pos < L, s, NEG_INF)
    m_cur = jnp.max(s, axis=2)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=2)
    acc = acc * alpha[..., None] + jax.lax.dot_general(
        p, vf, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return acc, m_new, l_new


def _pallas_decode_fused(q, k_cache, v_cache, lengths, sm_scale: float,
                         block_k: int = 256, interpret: bool = False):
    """Fused-heads decode: grid (B,), caches read in their NATIVE
    ``[B, C, Hk, D]`` layout via double-buffered manual DMA.

    Two costs of :func:`_pallas_decode` die here (PERF.md round-3/4
    diagnosis):

    - the per-step ``swapaxes(1, 2)`` re-materialized the ENTIRE cache in
      ``[B, Hk, C, D]`` layout before every kernel launch — a read+write of
      all cache bytes on top of the kernel's own read, ~3x the compulsory
      HBM traffic (measured 0.53 of the weight-stream bound fits);
    - one program per (batch, kv-head) meant ``Hk`` separate programs
      re-issuing DMAs; one program per batch row streams each cache byte
      exactly once and batches the group matmuls (``[Hk, rep, d]``).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, h, d = q.shape
    C, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    n_k = C // block_k

    qr = q.reshape(B, hk, rep, d)

    def kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems):
        b = pl.program_id(0)
        L = len_ref[b]
        hi = jnp.minimum((L + block_k - 1) // block_k, n_k)
        qb = q_ref[0].astype(jnp.float32)              # [hk, rep, d]

        def start(slot, j):
            sl = pl.ds(j * block_k, block_k)
            pltpu.make_async_copy(k_hbm.at[b, sl], kbuf.at[slot],
                                  sems.at[slot, 0]).start()
            pltpu.make_async_copy(v_hbm.at[b, sl], vbuf.at[slot],
                                  sems.at[slot, 1]).start()

        def wait(slot, j):
            sl = pl.ds(j * block_k, block_k)
            pltpu.make_async_copy(k_hbm.at[b, sl], kbuf.at[slot],
                                  sems.at[slot, 0]).wait()
            pltpu.make_async_copy(v_hbm.at[b, sl], vbuf.at[slot],
                                  sems.at[slot, 1]).wait()

        @pl.when(hi > 0)
        def _prologue():
            start(0, 0)

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < hi)
            def _prefetch():
                start(jax.lax.rem(j + 1, 2), j + 1)

            wait(slot, j)
            return _fused_softmax_block(qb, kbuf[slot], vbuf[slot],
                                        j * block_k, L, sm_scale, carry,
                                        heads_axis=1)

        acc0 = jnp.zeros((hk, rep, d), jnp.float32)
        m0 = jnp.full((hk, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hk, rep), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l_safe[..., None]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, hk, rep, d), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # k cache stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),   # v cache stays in HBM
            ],
            out_specs=pl.BlockSpec((1, hk, rep, d), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_k, hk, d), k_cache.dtype),
                pltpu.VMEM((2, block_k, hk, d), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, hk, rep, d), q.dtype),
        interpret=interpret,
        name="dense_decode_fused",
    )(lengths.astype(jnp.int32), qr, k_cache, v_cache)
    return out.reshape(B, 1, h, d)


def masked_multihead_attention(q, k_cache, v_cache, lengths, sm_scale: Optional[float] = None,
                               interpret: bool = False):
    """Single-token decode attention over a dense KV cache.

    q: ``[B, 1, H, D]``; caches ``[B, C, Hk, D]``; ``lengths`` ``[B]`` int32
    (number of valid cache slots per sequence, INCLUDING the current token,
    which must already be written to the cache).  Reference role:
    ``masked_multihead_attention_kernel.cu``.
    """
    from . import use_pallas

    B, S, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths[None], (B,))
    C = k_cache.shape[1]
    hk = k_cache.shape[2]
    kernel_ok = S == 1 and d in (64, 128, 256) and C % 128 == 0
    if (use_pallas() or interpret) and kernel_ok:
        # fused-heads variant: native-layout cache stream (no per-step
        # transpose), one program per batch row; VMEM buffers must fit
        block_k = 256 if C % 256 == 0 else 128
        vmem_bytes = 4 * block_k * hk * d * jnp.dtype(k_cache.dtype).itemsize
        if vmem_bytes <= 8 * 2 ** 20:
            registry.ensure_admitted("decode_mmha_fused")
            return _pallas_decode_fused(q, k_cache, v_cache, lengths,
                                        sm_scale, block_k=block_k,
                                        interpret=interpret)
        registry.ensure_admitted("decode_mmha")
        return _pallas_decode(q, k_cache, v_cache, lengths, sm_scale, interpret=interpret)
    return _decode_reference(q, k_cache, v_cache, lengths, sm_scale)


# ---------------------------------------------------------------------------
# Paged (block) KV cache — block_multi_head_attention role
# ---------------------------------------------------------------------------

def paged_attention(q, k_blocks, v_blocks, block_table, lengths,
                    sm_scale: Optional[float] = None):
    """Decode attention over a paged KV cache.

    q: ``[B, 1, H, D]``; ``k_blocks/v_blocks``: ``[num_blocks, bs, Hk, D]``
    global block pools; ``block_table``: ``[B, max_blocks]`` int32 (physical
    block id per logical block; unused entries may be any valid id — they are
    masked by ``lengths``); ``lengths``: ``[B]`` valid token count per seq.
    """
    nb, bs, hk, d = k_blocks.shape
    B = q.shape[0]
    # gather each sequence's logical cache: [B, max_blocks, bs, hk, d] -> [B, C, hk, d]
    k = jnp.take(k_blocks, block_table, axis=0).reshape(B, -1, hk, d)
    v = jnp.take(v_blocks, block_table, axis=0).reshape(B, -1, hk, d)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return masked_multihead_attention(q, k, v, lengths, sm_scale=sm_scale)


def write_paged_kv(k_blocks, v_blocks, block_table, lengths, k_new, v_new):
    """Append one token's K/V per sequence into the paged pools.

    k_new/v_new: ``[B, 1, Hk, D]``.  The target physical slot for sequence b is
    block ``block_table[b, lengths[b] // bs]``, offset ``lengths[b] % bs``.
    Returns updated (k_blocks, v_blocks).  Scatter via ``.at[]`` — XLA lowers
    to an in-place dynamic-update when the buffer is donated.
    """
    nb, bs, hk, d = k_blocks.shape
    B = k_new.shape[0]
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None], axis=1)[:, 0]  # [B]
    slot = lengths % bs
    k_blocks = k_blocks.at[phys, slot].set(k_new[:, 0])
    v_blocks = v_blocks.at[phys, slot].set(v_new[:, 0])
    return k_blocks, v_blocks


# ---------------------------------------------------------------------------
# Serving-layout paged KV pools: [num_blocks, kv_heads, block_size, head_dim]
# ---------------------------------------------------------------------------
# This layout makes each (physical block, kv head) a CONTIGUOUS [bs, d] slab,
# so the paged decode kernel can DMA exactly the live blocks straight from
# HBM (the reference's block_multi_head_attention walks its block table the
# same way inside the CUDA kernel). Block 0 is reserved as the trash block
# for inactive slots (serving.Engine convention).


def _paged_pool_reference(q, k_pool, v_pool, block_table, lengths, sm_scale):
    """Gather-based oracle for the serving layout (testing / CPU path).

    q: [B, 1, H, D]; pools [NB, Hk, bs, D]; block_table [B, MAXB] int32;
    lengths [B] int32 (valid tokens INCLUDING the current one)."""
    nb, hk, bs, d = k_pool.shape
    B = q.shape[0]
    # [B, MAXB, Hk, bs, D] -> [B, C, Hk, D]
    k = jnp.swapaxes(jnp.take(k_pool, block_table, axis=0), 2, 3)
    v = jnp.swapaxes(jnp.take(v_pool, block_table, axis=0), 2, 3)
    k = k.reshape(B, -1, hk, d)
    v = v.reshape(B, -1, hk, d)
    out = _decode_reference(q, k, v, lengths, sm_scale)
    # inactive slots (length 0) are all-zero, matching the Pallas kernel
    return out * (lengths > 0).astype(out.dtype)[:, None, None, None]


def _pallas_paged_decode(q, k_pool, v_pool, block_table, lengths, sm_scale,
                         interpret: bool = False):
    """Paged decode attention: grid (B, Hk); per program, double-buffered
    manual DMA of exactly the LIVE physical blocks of this head (block table
    and lengths are scalar-prefetched into SMEM), online-softmax accumulate.

    Unlike the dense kernel (which DMAs the full [C, d] cache row via its
    BlockSpec), HBM traffic here is proportional to the live length — the
    fix for the "full-cache DMA" cost diagnosed in PERF.md round 3.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, d = q.shape
    nb, hk, bs, d2 = k_pool.shape
    assert S == 1 and d == d2
    rep = h // hk
    maxb = block_table.shape[1]

    qr = q.reshape(B, hk, rep, d)

    def kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems):
        b = pl.program_id(0)
        g = pl.program_id(1)
        L = len_ref[b]
        n_live = jnp.minimum((L + bs - 1) // bs, maxb)
        qb = q_ref[0, 0].astype(jnp.float32)  # [rep, d]

        def start(slot, j):
            phys = tbl_ref[b, j]
            pltpu.make_async_copy(k_hbm.at[phys, g], kbuf.at[slot],
                                  sems.at[slot, 0]).start()
            pltpu.make_async_copy(v_hbm.at[phys, g], vbuf.at[slot],
                                  sems.at[slot, 1]).start()

        def wait(slot, j):
            phys = tbl_ref[b, j]
            pltpu.make_async_copy(k_hbm.at[phys, g], kbuf.at[slot],
                                  sems.at[slot, 0]).wait()
            pltpu.make_async_copy(v_hbm.at[phys, g], vbuf.at[slot],
                                  sems.at[slot, 1]).wait()

        @pl.when(n_live > 0)
        def _prologue():
            start(0, 0)

        def body(j, carry):
            acc, m_prev, l_prev = carry
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_live)
            def _prefetch():
                start(jax.lax.rem(j + 1, 2), j + 1)

            wait(slot, j)
            kb = kbuf[slot].astype(jnp.float32)  # [bs, d]
            vb = vbuf[slot].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
            s = jnp.where(k_pos < L, s, NEG_INF)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((rep, d), jnp.float32)
        m0 = jnp.full((rep,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rep,), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, hk),
            in_specs=[
                pl.BlockSpec((1, 1, rep, d), lambda b, g, *_: (b, g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # k pool stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),   # v pool stays in HBM
            ],
            out_specs=pl.BlockSpec((1, 1, rep, d), lambda b, g, *_: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bs, d), k_pool.dtype),
                pltpu.VMEM((2, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, hk, rep, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), qr,
      k_pool, v_pool)
    return out.reshape(B, 1, h, d)


def _pallas_paged_decode_fused(q, k_pool, v_pool, block_table, lengths,
                               sm_scale, interpret: bool = False):
    """Fused-heads paged decode: grid (B,); per live block, ONE DMA moves
    the whole ``[Hk, bs, d]`` physical block (vs one per (head, block) in
    :func:`_pallas_paged_decode`) and the block table is read once per
    block — the round-4 serve-preset overhead diagnosis (VERDICT #7)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, h, d = q.shape
    nb, hk, bs, d2 = k_pool.shape
    assert S == 1 and d == d2
    rep = h // hk
    maxb = block_table.shape[1]

    qr = q.reshape(B, hk, rep, d)

    def kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems):
        b = pl.program_id(0)
        L = len_ref[b]
        n_live = jnp.minimum((L + bs - 1) // bs, maxb)
        qb = q_ref[0].astype(jnp.float32)              # [hk, rep, d]

        def start(slot, j):
            phys = tbl_ref[b, j]
            pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot],
                                  sems.at[slot, 0]).start()
            pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot],
                                  sems.at[slot, 1]).start()

        def wait(slot, j):
            phys = tbl_ref[b, j]
            pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot],
                                  sems.at[slot, 0]).wait()
            pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot],
                                  sems.at[slot, 1]).wait()

        @pl.when(n_live > 0)
        def _prologue():
            start(0, 0)

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_live)
            def _prefetch():
                start(jax.lax.rem(j + 1, 2), j + 1)

            wait(slot, j)
            return _fused_softmax_block(qb, kbuf[slot], vbuf[slot],
                                        j * bs, L, sm_scale, carry,
                                        heads_axis=0)

        acc0 = jnp.zeros((hk, rep, d), jnp.float32)
        m0 = jnp.full((hk, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hk, rep), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l_safe[..., None]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, hk, rep, d), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hk, rep, d), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, hk, bs, d), k_pool.dtype),
                pltpu.VMEM((2, hk, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, hk, rep, d), q.dtype),
        interpret=interpret,
        name="paged_decode_fused",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), qr,
      k_pool, v_pool)
    return out.reshape(B, 1, h, d)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False):
    """Decode attention over serving-layout paged pools.

    q: ``[B, 1, H, D]``; pools ``[NB, Hk, bs, D]``; ``block_table``
    ``[B, MAXB]`` int32; ``lengths`` ``[B]`` int32 (0 = inactive slot, whose
    output is all-zero). Reference role:
    ``block_multi_head_attention_kernel.cu`` — but HBM reads are proportional
    to live tokens, not table capacity."""
    from . import use_pallas

    B, S, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    lengths = jnp.asarray(lengths, jnp.int32)
    bs = k_pool.shape[2]
    hk = k_pool.shape[1]
    kernel_ok = S == 1 and d in (64, 128, 256) and bs % 128 == 0
    if (use_pallas() or interpret) and kernel_ok:
        # fused-heads variant (one DMA per block for all kv heads) when the
        # whole [hk, bs, d] block double-buffers within VMEM budget
        vmem_bytes = 4 * hk * bs * d * jnp.dtype(k_pool.dtype).itemsize
        if vmem_bytes <= 8 * 2 ** 20:
            registry.ensure_admitted("paged_decode_fused")
            return _pallas_paged_decode_fused(q, k_pool, v_pool, block_table,
                                              lengths, sm_scale,
                                              interpret=interpret)
        registry.ensure_admitted("paged_decode")
        return _pallas_paged_decode(q, k_pool, v_pool, block_table, lengths,
                                    sm_scale, interpret=interpret)
    return _paged_pool_reference(q, k_pool, v_pool, block_table, lengths, sm_scale)


def write_paged_token(k_pool, v_pool, block_table, lengths, k_new, v_new):
    """Append one token's K/V per sequence into serving-layout pools.

    k_new/v_new: ``[B, 1, Hk, D]``. Target: block ``table[b, lengths[b]//bs]``
    slot ``lengths[b] % bs``. Inactive slots (length 0, table row pointing at
    the reserved trash block) harmlessly write there.

    The write is a scatter of ``B*Hk`` rows of ``D`` into the pool viewed
    ``[NB*Hk*bs, D]`` (a bitcast of the row-major pool), row
    ``(phys*Hk + head)*bs + slot``.  Written as ``pool.at[phys, :, slot]``
    the scatter's window spans heads and ``D``, for which the TPU compiler
    lays the operand out ``{3,1,2,0}``; the decode kernels read row-major, so
    every layer of every decode step would copy both whole pools."""
    nb, hk, bs, d = k_pool.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None], axis=1)[:, 0]
    rows = ((phys[:, None] * hk + jnp.arange(hk)) * bs
            + (lengths % bs)[:, None]).reshape(-1)                   # [B*Hk]

    def write(pool, new):
        flat = pool.reshape(-1, d).at[rows].set(new.reshape(-1, d))
        return flat.reshape(pool.shape)

    return write(k_pool, k_new), write(v_pool, v_new)


def paged_chunk_attention(q, k_pool, v_pool, block_table, ctx_lengths,
                          sm_scale: Optional[float] = None):
    """Chunk attention over serving-layout paged pools (chunked prefill /
    prefix-cache suffix prefill).

    q: ``[B, S, H, D]`` — an S-token chunk per sequence at absolute positions
    ``ctx_lengths[b] .. ctx_lengths[b]+S-1``; pools ``[NB, Hk, bs, D]``;
    ``block_table`` ``[B, MAXB]``; ``ctx_lengths`` ``[B]`` int32 tokens
    already resident BEFORE this chunk.  The chunk's own K/V must already be
    written into the pools (:func:`write_paged_chunk`); the gather then sees
    context and chunk through one table walk.  Chunk token ``j`` attends
    cache positions ``<= ctx_lengths[b] + j`` — pad-tail rows past the true
    chunk length only ever attend positions the caller later masks or
    overwrites.  Gather-based (XLA) path; there is no streamed Pallas
    variant yet."""
    nb, hk, bs, d = k_pool.shape
    B, S, h, _ = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    rep = h // hk
    # [B, MAXB, Hk, bs, D] -> [B, C, Hk, D]
    k = jnp.swapaxes(jnp.take(k_pool, block_table, axis=0), 2, 3).reshape(B, -1, hk, d)
    v = jnp.swapaxes(jnp.take(v_pool, block_table, axis=0), 2, 3).reshape(B, -1, hk, d)
    C = k.shape[1]
    qf = q.astype(jnp.float32).reshape(B, S, hk, rep, d)
    s = jnp.einsum("bsgrd,bcgd->bgrsc", qf, k.astype(jnp.float32)) * sm_scale
    q_pos = ctx_lengths[:, None] + jnp.arange(S)[None, :]            # [B, S]
    mask = jnp.arange(C)[None, None, :] <= q_pos[:, :, None]         # [B, S, C]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # zero V past every row's reach (union bound ctx+S): those positions are
    # pool trash — possibly NaN — and 0*NaN = NaN in the p@v contraction
    valid = jnp.arange(C)[None, :] < (ctx_lengths + S)[:, None]      # [B, C]
    vf = jnp.where(valid[:, :, None, None], v.astype(jnp.float32), 0.0)
    o = jnp.einsum("bgrsc,bcgd->bsgrd", p, vf)
    return o.reshape(B, S, h, d).astype(q.dtype)


def write_paged_chunk(k_pool, v_pool, block_table, ctx_lengths, k_chunk, v_chunk):
    """Scatter an S-token chunk's K/V into paged pools starting at position
    ``ctx_lengths[b]`` per sequence.

    PRECONDITION: every ``ctx_lengths[b]`` is block-aligned and ``S`` is a
    multiple of ``bs`` (the serving scheduler pads chunks to the block
    ladder; table entries past a sequence's real blocks are 0 = trash, so
    the pad tail lands harmlessly there).  ``k_chunk/v_chunk``:
    ``[B, S, Hk, D]``."""
    nb, hk, bs, d = k_pool.shape
    B, S = k_chunk.shape[0], k_chunk.shape[1]
    ctx_lengths = jnp.asarray(ctx_lengths, jnp.int32)
    start_block = ctx_lengths // bs                                  # [B]
    for i in range(S // bs):
        phys = jnp.take_along_axis(
            block_table, (start_block + i)[:, None], axis=1)[:, 0]   # [B]
        kb = jnp.swapaxes(k_chunk[:, i * bs:(i + 1) * bs], 1, 2)     # [B,Hk,bs,D]
        vb = jnp.swapaxes(v_chunk[:, i * bs:(i + 1) * bs], 1, 2)
        k_pool = k_pool.at[phys].set(kb.astype(k_pool.dtype))
        v_pool = v_pool.at[phys].set(vb.astype(v_pool.dtype))
    return k_pool, v_pool


def write_paged_prefill(k_pool, v_pool, blocks, k_seq, v_seq):
    """Scatter a prefilled sequence's K/V into its allocated blocks.

    ``blocks``: ``[n_blocks]`` int32 physical ids; ``k_seq/v_seq``:
    ``[n_blocks*bs, Hk, D]`` (bucket-padded; the tail past the true length is
    garbage that the length mask never attends)."""
    nb, hk, bs, d = k_pool.shape
    n = blocks.shape[0]
    ks = jnp.swapaxes(k_seq.reshape(n, bs, hk, d), 1, 2)  # [n, Hk, bs, D]
    vs = jnp.swapaxes(v_seq.reshape(n, bs, hk, d), 1, 2)
    return k_pool.at[blocks].set(ks.astype(k_pool.dtype)), \
        v_pool.at[blocks].set(vs.astype(v_pool.dtype))


# ---------------------------------------------------------------------------
# kernel-registry entries (verified by analysis.pallas_lint; see registry.py)
# ---------------------------------------------------------------------------

def _dense_shapes():
    sds = jax.ShapeDtypeStruct
    B, h, hk, d, C = 2, 8, 2, 128, 512
    return (sds((B, 1, h, d), jnp.float32), sds((B, C, hk, d), jnp.float32),
            sds((B, C, hk, d), jnp.float32), sds((B,), jnp.int32))


def _paged_shapes():
    sds = jax.ShapeDtypeStruct
    B, h, hk, d, nb, bs, maxb = 2, 8, 2, 128, 16, 128, 4
    return (sds((B, 1, h, d), jnp.float32), sds((nb, hk, bs, d), jnp.float32),
            sds((nb, hk, bs, d), jnp.float32), sds((B, maxb), jnp.int32),
            sds((B,), jnp.int32))


registry.register(
    "decode_mmha",
    lambda: (lambda q, k, v, ln: _pallas_decode(q, k, v, ln, 1.0), _dense_shapes()),
    presets=("decode", "serve"),
    description="per-(batch, kv-head) dense decode attention")
registry.register(
    "decode_mmha_fused",
    lambda: (lambda q, k, v, ln: _pallas_decode_fused(q, k, v, ln, 1.0,
                                                      block_k=256),
             _dense_shapes()),
    presets=("decode", "serve"),
    description="fused-heads dense decode: ANY-space cache + manual "
                "double-buffered DMA")
registry.register(
    "paged_decode",
    lambda: (lambda q, k, v, bt, ln: _pallas_paged_decode(q, k, v, bt, ln,
                                                          1.0),
             _paged_shapes()),
    presets=("serve",),
    description="paged decode attention, per-(batch, kv-head) programs")
registry.register(
    "paged_decode_fused",
    lambda: (lambda q, k, v, bt, ln: _pallas_paged_decode_fused(
        q, k, v, bt, ln, 1.0), _paged_shapes()),
    presets=("serve",),
    description="fused-heads paged decode: one DMA per live block")


def _chunk_shapes():
    sds = jax.ShapeDtypeStruct
    B, S, h, hk, d, nb, bs, maxb = 2, 128, 8, 2, 128, 16, 128, 4
    return (sds((B, S, h, d), jnp.float32), sds((nb, hk, bs, d), jnp.float32),
            sds((nb, hk, bs, d), jnp.float32), sds((B, maxb), jnp.int32),
            sds((B,), jnp.int32))


registry.register(
    "paged_chunk_attention",
    lambda: (lambda q, k, v, bt, ln: paged_chunk_attention(q, k, v, bt, ln),
             _chunk_shapes()),
    presets=("serve",),
    description="chunked-prefill attention over paged pools (XLA gather "
                "path; certified to contain no unverified pallas_call)")
registry.register(
    "write_paged_chunk",
    lambda: (lambda k, v, bt, ln, kc, vc: write_paged_chunk(k, v, bt, ln,
                                                            kc, vc),
             (jax.ShapeDtypeStruct((16, 2, 128, 128), jnp.float32),
              jax.ShapeDtypeStruct((16, 2, 128, 128), jnp.float32),
              jax.ShapeDtypeStruct((2, 4), jnp.int32),
              jax.ShapeDtypeStruct((2,), jnp.int32),
              jax.ShapeDtypeStruct((2, 128, 2, 128), jnp.float32),
              jax.ShapeDtypeStruct((2, 128, 2, 128), jnp.float32))),
    presets=("serve",),
    description="paged-pool chunk scatter (XLA path; certified "
                "pallas_call-free)")
