"""Latent attention (MLA) for serving: the paged latent cache's writes, the
absorbed decode kernel over it, and a prefill attention whose query/key
width differs from its value width.

The cache of one layer is ONE pool: per token the normed latent ``c``
(``rank`` values) and the rotated shared key ``k_r`` (``rope`` values).  No
heads, no separate k and v: every head reads the same row.  A pool row holds
TWO consecutive tokens, ``[c_even | c_odd | k_r,even | k_r,odd]``, so the
pool is ``[NB, bs / 2, 2 * (rank + rope)]``: with ``rank + rope = 576`` a
token's own row would not be a multiple of the 128 lanes a DMA moves (the
chip's compiler refuses the slice, and its tiled layout would pad every row
to 640), while the pair's 1,152 is, and every part of it starts on a lane
boundary.  Block 0 is the trash block (``serving.Engine``'s convention).

- **Decode** absorbs the up-projection into the query and the output
  (``q_lat = q_nope W_kvb,k^T``): scores are ``q_lat c^T + q_rope k_r^T``, the
  output ``(P c)`` is still in latent space and the caller applies
  ``W_kvb,v``.  The kernel ``mla_paged_decode`` streams exactly the live
  blocks of the batch, one DMA a block for all heads, through one ring of
  buffers that does not drain at a slot's edge.
- **Prefill** expands ``k_nope`` and ``v`` from ``c`` and runs causal
  attention with q/k width ``nope + rope`` and v width ``v_dim``.  The
  kernel ``mla_prefill_attn`` is the streaming flash forward with the score
  split into its two products, so the shared ``k_r`` is never broadcast to
  the heads and the 64-wide part never pads a 192-wide block.
- **Chunk** attention (a prefix hit's suffix, a long prompt's pieces) gathers
  the slot's blocks and attends absorbed, in query tiles (XLA; no streamed
  kernel yet, as for the k/v pools).

Each kernel has an XLA reference of the same signature (CPU tests, oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from . import registry

NEG_INF = -1e30
# latent blocks the decode kernel keeps in VMEM (at least 3): RING - 2 in
# flight while two are in use, one scored and one attended (a block of 128
# tokens is 147 KB in bf16).  Depths 3 to 8 ran alike on the chip: the body,
# not the DMA, bounds a block (PERF.md section 6)
RING = 4


def _flag_interpret() -> bool:
    from ..framework import flags

    return bool(flags.get_flag("pallas_interpret"))


# ---------------------------------------------------------------------------
# the pool's rows, and writes into them in the layout the decode kernel reads
# ---------------------------------------------------------------------------

def pack_rows(seq, rank: int):
    """Token rows ``[..., T, rank + rope]`` (T even) -> pool rows
    ``[..., T / 2, 2 * (rank + rope)]``: ``[c_even | c_odd | kr_even |
    kr_odd]``."""
    *lead, t, w = seq.shape
    pair = seq.reshape(*lead, t // 2, 2, w)
    return jnp.concatenate(
        [pair[..., :rank].reshape(*lead, t // 2, 2 * rank),
         pair[..., rank:].reshape(*lead, t // 2, 2 * (w - rank))], axis=-1)


def unpack_rows(rows, rank: int):
    """Pool rows ``[..., R, 2 * (rank + rope)]`` -> ``(c [..., 2R, rank],
    kr [..., 2R, rope])`` in token order."""
    *lead, r, w2 = rows.shape
    rope = w2 // 2 - rank
    return (rows[..., :2 * rank].reshape(*lead, 2 * r, rank),
            rows[..., 2 * rank:].reshape(*lead, 2 * r, rope))


def init_latent_pool(num_blocks: int, block_size: int, rank: int, rope: int,
                     dtype):
    return jnp.zeros((num_blocks, block_size // 2, 2 * (rank + rope)), dtype)


def write_latent_token(pool, block_table, lengths, new, rank: int):
    """Append one token's latent row per slot.  ``new``: ``[B, rank +
    rope]``; the token's place is block ``table[b, lengths[b] // bs]``, pool
    row ``(lengths[b] % bs) // 2``, the even or the odd half of it.  Inactive
    slots (length 0, table row 0) write into the trash block.  ``B`` pool
    rows are read, their halves replaced, and scattered back into the pool
    viewed ``[NB * bs / 2, 2W]`` (a bitcast), so the donated pool is updated
    in place."""
    nb, half, w2 = pool.shape
    bs, rope = 2 * half, w2 // 2 - rank
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None],
                               axis=1)[:, 0]
    slot = lengths % bs
    rows = phys * half + slot // 2
    flat = pool.reshape(-1, w2)
    new = new.astype(pool.dtype)
    twice = jnp.concatenate([new[:, :rank], new[:, :rank],
                             new[:, rank:], new[:, rank:]], axis=-1)
    lane = jnp.arange(w2)
    odd_lane = jnp.where(lane < 2 * rank, lane >= rank,
                         lane >= 2 * rank + rope)
    mine = odd_lane[None, :] == (slot % 2 == 1)[:, None]
    return flat.at[rows].set(jnp.where(mine, twice, flat[rows])).reshape(
        pool.shape)


def write_latent_chunk(pool, block_table, ctx_lengths, chunk, rank: int):
    """Scatter an S-token chunk ``[B, S, W]`` starting at the block-aligned
    position ``ctx_lengths[b]``; ``S`` is a multiple of ``bs`` and table
    entries past a sequence's blocks are 0, so the pad tail lands in trash."""
    nb, half, w2 = pool.shape
    bs = 2 * half
    B, S, _ = chunk.shape
    start = jnp.asarray(ctx_lengths, jnp.int32) // bs
    idx = start[:, None] + jnp.arange(S // bs)[None, :]              # [B, n]
    phys = jnp.take_along_axis(block_table, idx, axis=1).reshape(-1)
    rows = pack_rows(chunk.reshape(B * (S // bs), bs, -1), rank)
    return pool.at[phys].set(rows.astype(pool.dtype))


def write_latent_prefill(pool, blocks, seq, rank: int):
    """A prefilled sequence's rows ``[n_blocks * bs, W]`` into its blocks
    ``[n_blocks]`` (bucket-padded: freed padding blocks are id 0)."""
    nb, half, w2 = pool.shape
    rows = pack_rows(seq.reshape(blocks.shape[0], 2 * half, -1), rank)
    return pool.at[blocks].set(rows.astype(pool.dtype))


# ---------------------------------------------------------------------------
# decode: absorbed attention over the live blocks
# ---------------------------------------------------------------------------

def _gather_tokens(pool, block_table, rank: int):
    """``(c [B, C, rank], kr [B, C, rope])``: every table entry's tokens."""
    B = block_table.shape[0]
    rows = jnp.take(pool, block_table, axis=0)        # [B, MAXB, bs/2, 2W]
    c, kr = unpack_rows(rows, rank)                   # [B, MAXB, bs, .]
    return c.reshape(B, -1, rank), kr.reshape(B, -1, kr.shape[-1])


def _decode_reference(q_lat, q_rope, pool, block_table, lengths, sm_scale):
    f = jnp.float32
    c, kr = _gather_tokens(pool, block_table, q_lat.shape[-1])
    live = jnp.arange(c.shape[1])[None, :] < lengths[:, None]    # [B, C]
    # rows past the length are pool trash, possibly NaN, and 0 * NaN = NaN
    c = jnp.where(live[:, :, None], c.astype(f), 0.0)
    kr = jnp.where(live[:, :, None], kr.astype(f), 0.0)
    s = (jnp.einsum("bhr,bcr->bhc", q_lat.astype(f), c)
         + jnp.einsum("bhd,bcd->bhc", q_rope.astype(f), kr))
    s = jnp.where(live[:, None, :], s * sm_scale, NEG_INF)
    o = jnp.einsum("bhc,bcr->bhr", jax.nn.softmax(s, axis=-1), c)
    return (o * (lengths > 0)[:, None, None]).astype(q_lat.dtype)


def _work_list(block_table, lengths, bs: int):
    """The call's live blocks as one list, slot after slot, empty slots
    skipped: ``(flat, ends)`` where ``flat [B * MAXB]`` holds the physical
    block ids (entries past the last live block are unused) and ``ends[b]``
    is one past slot ``b``'s last item, so that slot's items are
    ``ends[b] - n_live[b] .. ends[b]``."""
    B, maxb = block_table.shape
    n_live = jnp.minimum((lengths + bs - 1) // bs, maxb)
    ends = jnp.cumsum(n_live).astype(jnp.int32)
    g = jnp.arange(B * maxb, dtype=jnp.int32)
    owner = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1), B - 1)
    j = jnp.clip(g - (ends - n_live)[owner], 0, maxb - 1)
    return block_table[owner, j], ends


def _pallas_decode(q_lat, q_rope, pool, block_table, lengths, sm_scale,
                   interpret=False, ring=None):
    """One program for the whole batch: the call's live blocks are one work
    list (``_work_list``, scalar-prefetched) streamed through a ring of
    ``ring`` VMEM buffers.  Waiting for item ``g`` starts item ``g + ring -
    2`` whatever slot either belongs to, so only the call's first blocks
    wait on HBM.  Per slot, online softmax for all heads at once over a
    block's ``bs`` tokens in one set of products (bf16 operands on the chip,
    float32 sums): even tokens are the block's rows, odd tokens the same
    rows' second half, stacked ``[c_even; c_odd]``; the rotated keys take a
    2 * rope-lane tile masked to each half, so no 64-lane slice is taken.
    A step computes the next block's scores beside this block's softmax and
    value product (a block's own chain, scores -> cross-lane max -> values,
    is mostly MXU and reduction latency: PERF.md section 6).  Every
    loop is a ``fori_loop``: the kernel's code does not grow with the batch
    or the table's width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, rank = q_lat.shape
    rope = q_rope.shape[-1]
    nb, half, w2 = pool.shape
    bs = 2 * half
    maxb = block_table.shape[1]
    ring = ring or RING
    flat, ends = _work_list(block_table, lengths, bs)
    q_two = jnp.concatenate([q_rope, q_rope], -1)          # [B, H, 2 rope]
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32)
    rows_t = (((1,), (1,)), ((), ()))                      # a @ b.T

    def kernel(flat_ref, ends_ref, len_ref, ql_ref, qr_ref, pool_hbm, o_ref,
               buf, sems):
        total = ends_ref[B - 1]

        def copy(g):
            r = jax.lax.rem(g, ring)
            return pltpu.make_async_copy(pool_hbm.at[flat_ref[g]],
                                         buf.at[r], sems.at[r])

        for g in range(ring - 2):                  # the call's one prologue
            @pl.when(g < total)
            def _start():
                copy(g).start()

        # token of each pool lane of a row: 2 row + (1 on an odd-token lane)
        lane = jax.lax.broadcasted_iota(jnp.int32, (half, w2), 1)
        odd = ((lane >= rank) & (lane < 2 * rank)) | (lane >= 2 * rank + rope)
        tok = 2 * jax.lax.broadcasted_iota(jnp.int32, (half, w2), 0) + \
            jnp.where(odd, 1, 0)
        kr_lane = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * rope), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        col_tok = jnp.where(col < half, 2 * col, 2 * (col - half) + 1)

        def latent(r):                                 # [bs, rank]
            return jnp.concatenate(
                [buf[r, :, :rank], buf[r, :, rank:2 * rank]], axis=0)

        def one_slot(b, _):
            L = len_ref[b]
            n_live = jnp.minimum((L + bs - 1) // bs, maxb)
            first = ends_ref[b] - n_live
            ql, qr = ql_ref[b], qr_ref[b]          # [H, rank], [H, 2 rope]

            def arrive(j):
                """Wait for the slot's block j and start the ring's next item
                (into the buffer of block j - 2, already used)."""
                g = first + j

                @pl.when(g + ring - 2 < total)
                def _prefetch():
                    copy(g + ring - 2).start()

                copy(g).wait()

                # the slot's last block: rows past the length are pool trash,
                # possibly NaN, and 0 * NaN = NaN in the value product
                @pl.when((j + 1) * bs > L)
                def _mask():
                    r = jax.lax.rem(g, ring)
                    buf[r] = jnp.where(j * bs + tok < L, buf[r], 0)

            def scores(j):                             # raw, [H, bs]
                r = jax.lax.rem(first + j, ring)
                kr = buf[r, :, 2 * rank:]                     # [bs/2, 2 rope]
                k2 = jnp.concatenate([jnp.where(kr_lane < rope, kr, 0),
                                      jnp.where(kr_lane < rope, 0, kr)],
                                     axis=0)                  # [bs, 2 rope]
                return dot(ql, latent(r), rows_t) + dot(qr, k2, rows_t)

            def block(j, state):
                # block j + 1's scores are computed beside block j's softmax
                # and value product, in one stretch of straight code, so the
                # two chains of MXU and cross-lane latency overlap.  After
                # the slot's last block they read a buffer nobody waited for,
                # and are dropped
                s, acc, m_prev, l_prev = state

                @pl.when(j + 1 < n_live)
                def _next():
                    arrive(j + 1)

                s_next = scores(j + 1)
                s = jnp.where(j * bs + col_tok < L, s * sm_scale, NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                p = jnp.exp(s - m_new[:, None])
                alpha = jnp.exp(m_prev - m_new)
                c = latent(jax.lax.rem(first + j, ring))
                return (s_next, acc * alpha[:, None] + dot(
                    p.astype(c.dtype), c, (((1,), (0,)), ((), ()))),
                    m_new, alpha * l_prev + jnp.sum(p, axis=1))

            @pl.when(n_live > 0)
            def _first():
                arrive(0)

            _, acc, _, l = jax.lax.fori_loop(
                0, n_live, block,
                (scores(0), jnp.zeros((H, rank), jnp.float32),
                 jnp.full((H,), NEG_INF, jnp.float32),
                 jnp.zeros((H,), jnp.float32)))
            o_ref[b] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
                o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, B, one_slot, 0)

    whole = lambda shape: pl.BlockSpec(                          # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole((B, H, rank)), whole((B, H, 2 * rope)),
                      pl.BlockSpec(memory_space=pl.ANY)],  # pool stays in HBM
            out_specs=whole((B, H, rank)),
            scratch_shapes=[pltpu.VMEM((ring, half, w2), pool.dtype),
                            pltpu.SemaphoreType.DMA((ring,))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_decode",
    )(flat, ends, lengths, q_lat, q_two.astype(q_lat.dtype), pool)


# the layers of a program call the kernel with equal shapes: one trace and
# one Mosaic lowering serve them all, where each call would trace and lower
# its own (paid at every start, before the compile cache is consulted)
_shared_decode = jax.jit(_pallas_decode, static_argnums=(5,),
                         static_argnames=("interpret", "ring"))


def latent_decode_attention(q_lat, q_rope, pool, block_table, lengths,
                            sm_scale, interpret=False):
    """Absorbed decode attention over a latent pool.

    ``q_lat`` ``[B, H, rank]`` (the no-position query times ``W_kvb,k^T``),
    ``q_rope`` ``[B, H, rope]`` (rotated), ``pool`` ``[NB, bs / 2, 2 * (rank
    + rope)]``, ``block_table`` ``[B, MAXB]``, ``lengths`` ``[B]`` (tokens to
    attend, the current one included; 0 = inactive slot, whose output is
    zero).  Returns ``[B, H, rank]``: the attention-weighted latent."""
    from . import use_pallas

    interpret = interpret or _flag_interpret()
    lengths = jnp.asarray(lengths, jnp.int32)
    rank, rope = q_lat.shape[-1], q_rope.shape[-1]
    if (use_pallas() or interpret) and rank % 128 == 0 \
            and (2 * rope) % 128 == 0 and pool.shape[1] % 16 == 0:
        registry.ensure_admitted("mla_paged_decode")
        # which ring the programs took: once a call each time a program that
        # holds it is traced (or an eager call made), never in a compiled step
        obs.registry().counter("mla.decode_programs", ring=RING).inc()
        return _shared_decode(q_lat, q_rope, pool, block_table, lengths,
                              sm_scale, interpret=interpret, ring=RING)
    return _decode_reference(q_lat, q_rope, pool, block_table, lengths,
                             sm_scale)


def latent_chunk_attention(q_lat, q_rope, pool, block_table, ctx_lengths,
                           sm_scale, tile: int = 512):
    """Absorbed attention of an S-token chunk per slot at positions
    ``ctx_lengths[b] ..`` over context + chunk (the chunk's rows are already
    in the pool).  ``q_lat`` ``[B, S, H, rank]``, ``q_rope`` ``[B, S, H,
    rope]``; returns ``[B, S, H, rank]``.  One query tile's scores exist at a
    time ([B, H, tile, C] float32)."""
    f = jnp.float32
    B, S, H, rank = q_lat.shape
    c, kr = _gather_tokens(pool, block_table, rank)              # [B, C, .]
    C = c.shape[1]
    valid = (jnp.arange(C)[None, :] < (ctx_lengths + S)[:, None])[:, :, None]
    c, kr = jnp.where(valid, c, 0).astype(f), jnp.where(valid, kr, 0).astype(f)
    tile = min(tile, S)

    def one(args):
        ql, qr, pos = args             # [B, t, H, rank] [B, t, H, rope] [t]
        s = (jnp.einsum("bthr,bcr->bhtc", ql.astype(f), c)
             + jnp.einsum("bthd,bcd->bhtc", qr.astype(f), kr))
        seen = jnp.arange(C)[None, None, :] <= (
            ctx_lengths[:, None] + pos[None, :])[:, :, None]     # [B, t, C]
        p = jax.nn.softmax(jnp.where(seen[:, None], s * sm_scale, NEG_INF),
                           axis=-1)
        return jnp.einsum("bhtc,bcr->bthr", p, c)

    n = S // tile
    split = lambda x: jnp.moveaxis(                                # noqa: E731
        x.reshape(B, n, tile, *x.shape[2:]), 1, 0)
    o = jax.lax.map(one, (split(q_lat), split(q_rope),
                          jnp.arange(S).reshape(n, tile)))
    return jnp.moveaxis(o, 0, 1).reshape(B, S, H, rank).astype(q_lat.dtype)


# ---------------------------------------------------------------------------
# prefill: causal attention, q/k width nope + rope, v width v_dim
# ---------------------------------------------------------------------------

def _prefill_reference(q_nope, q_rope, k_nope, k_rope, v, sm_scale):
    f = jnp.float32
    s = (jnp.einsum("bshd,bthd->bhst", q_nope.astype(f), k_nope.astype(f))
         + jnp.einsum("bshd,btd->bhst", q_rope.astype(f), k_rope.astype(f)))
    S = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * sm_scale, NEG_INF)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v.astype(f))
    return o.astype(v.dtype)


def _pallas_prefill(q_nope, q_rope, k_nope, k_rope, v, sm_scale,
                    block_q=512, block_k=512, interpret=False):
    """Streaming flash forward, grid ``(B * H, n_q, n_k)``: K/V blocks page
    through VMEM (clamped to the causal range, so masked blocks are neither
    fetched nor computed), the online-softmax state lives in scratch.  The
    products take the operands in their own dtype (bf16 on the chip) and
    accumulate in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    block_q, block_k = min(block_q, S), min(block_k, S)
    n_q, n_k = S // block_q, S // block_k
    heads = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, S, x.shape[-1])  # noqa: E731

    def kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, acc_ref, m_ref,
               l_ref):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _step():
            # one MXU pass whatever the process's default precision: the
            # operands are bf16 on the chip, and Mosaic refuses more of them
            dot = functools.partial(jax.lax.dot_general,
                                    precision=jax.lax.Precision.DEFAULT,
                                    preferred_element_type=jnp.float32)
            dims = (((1,), (1,)), ((), ()))
            s = (dot(qn_ref[0], kn_ref[0], dims)
                 + dot(qr_ref[0], kr_ref[0], dims))
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s * sm_scale, NEG_INF)
            m_prev = jnp.max(m_ref[...], axis=1)   # lane-replicated -> [bq]
            l_prev = jnp.max(l_ref[...], axis=1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            rep = lambda x: jnp.broadcast_to(x[:, None], (block_q, 128))  # noqa: E731
            l_ref[...] = rep(alpha * l_prev + jnp.sum(p, axis=1))
            m_ref[...] = rep(m_new)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + dot(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())))

        @pl.when(ki == n_k - 1)
        def _finalize():
            l_fin = jnp.maximum(jnp.max(l_ref[...], axis=1), 1e-30)
            o_ref[0] = (acc_ref[...] / l_fin[:, None]).astype(o_ref.dtype)

    def q_idx(b, i, j):
        return (b, i, 0)

    def kv_idx(b, i, j):
        # a repeated index elides the fetch of a block the mask would skip
        return (b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k), 0)

    def kr_idx(b, i, j):
        return (b // H, jnp.minimum(j, (i * block_q + block_q - 1) // block_k),
                0)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[pl.BlockSpec((1, block_q, dn), q_idx),
                  pl.BlockSpec((1, block_q, dr), q_idx),
                  pl.BlockSpec((1, block_k, dn), kv_idx),
                  pl.BlockSpec((1, block_k, dr), kr_idx),
                  pl.BlockSpec((1, block_k, dv), kv_idx)],
        out_specs=pl.BlockSpec((1, block_q, dv), q_idx),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=interpret,
        name="mla_prefill_attn",
    )(heads(q_nope), heads(q_rope), heads(k_nope), k_rope, heads(v))
    return jnp.swapaxes(out.reshape(B, H, S, dv), 1, 2)


def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, sm_scale,
                          interpret=False):
    """Causal attention with scores ``q_nope k_nope^T + q_rope k_rope^T``.

    ``q_nope``/``k_nope`` ``[B, S, H, nope]``, ``q_rope`` ``[B, S, H, rope]``,
    ``k_rope`` ``[B, S, rope]`` (one rotated key for all heads), ``v``
    ``[B, S, H, v_dim]``.  Returns ``[B, S, H, v_dim]``."""
    from . import use_pallas

    asked = interpret
    interpret = interpret or _flag_interpret()
    S = q_nope.shape[1]
    ok = (S % 128 == 0 and (S <= 512 or S % 512 == 0)
          and q_nope.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
          and q_rope.shape[-1] in (64, 128))
    if asked and not ok:
        raise ValueError(f"mla_prefill_attention(interpret=True): S={S} and "
                         "the head widths must fit the kernel's blocks")
    if (use_pallas() or interpret) and ok:
        registry.ensure_admitted("mla_prefill_attn")
        return _pallas_prefill(q_nope, q_rope, k_nope, k_rope, v, sm_scale,
                               interpret=interpret)
    return _prefill_reference(q_nope, q_rope, k_nope, k_rope, v, sm_scale)


# ---------------------------------------------------------------------------
# kernel-registry entries (verified by analysis.pallas_lint; see registry.py)
# ---------------------------------------------------------------------------

def _decode_shapes():
    sds = jax.ShapeDtypeStruct
    B, H, rank, rope, nb, bs, maxb = 2, 8, 128, 64, 16, 128, 4
    return (sds((B, H, rank), jnp.float32), sds((B, H, rope), jnp.float32),
            sds((nb, bs // 2, 2 * (rank + rope)), jnp.float32),
            sds((B, maxb), jnp.int32), sds((B,), jnp.int32))


def _prefill_shapes():
    sds = jax.ShapeDtypeStruct
    B, S, H = 1, 256, 2
    return (sds((B, S, H, 128), jnp.float32), sds((B, S, H, 64), jnp.float32),
            sds((B, S, H, 128), jnp.float32), sds((B, S, 64), jnp.float32),
            sds((B, S, H, 128), jnp.float32))


registry.register(
    "mla_paged_decode",
    lambda: (lambda ql, qr, pool, bt, ln: _pallas_decode(ql, qr, pool, bt,
                                                         ln, 1.0),
             _decode_shapes()),
    presets=("serve",),
    description="absorbed latent decode attention: one DMA per live block, "
                "all heads")
registry.register(
    "mla_prefill_attn",
    lambda: (lambda qn, qr, kn, kr, v: _pallas_prefill(qn, qr, kn, kr, v, 1.0,
                                                       block_q=128,
                                                       block_k=128),
             _prefill_shapes()),
    presets=("serve",),
    description="streaming causal flash forward, q/k width nope + rope "
                "(two products), v width v_dim")
