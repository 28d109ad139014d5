"""Grouped-query attention whose keys are wider than its values, with a
window bound and a sink in the denominator: the prefill kernel both layer
kinds of a window/full decoder call, the paged decode of its full layers,
and the bounded ring of its window layers (``models/swa_moe.py``).

Why a file of its own and not more arguments of its nearest kin:
``mla_attention.mla_prefill_attn`` has ONE rotated key for all heads and a
program a head, and is the yardstick of an accepted cell;
``decode_attention.paged_decode_fused`` takes one width for K and V.  What
is kept of them: the score as two products over lane-aligned parts, so a
192-wide key never pads a block (the 64-wide part and the 128-wide part are
two operands), the lane-replicated softmax state, and the double-buffered
walk over a slot's live blocks.

**Widths.**  q/k ``D`` wide, v ``dv`` wide, ``H / Hk`` query heads a KV head.
The part of a key that is no multiple of 128 lanes is its *tail*: the first
``D % 128`` values (64 of 192; the rotated ones in the model, though nothing
here knows).  Scores are ``q_tail k_tail^T + q_rest k_rest^T``.

**Prefill** (``gqa_prefill_attn``): causal forward attention.  The query
heads of one KV head ride together: a program's rows are ``rep`` heads x
``block_q`` positions, so a K/V block is fetched once a group, not once a
head.  With ``window=W`` a query block visits only the key blocks that meet
its band ``[i - W + 1, i]`` (the grid's last axis counts band blocks, the
index map starts at the band's first block, blocks past the diagonal are
skipped and their fetch elided): a window layer's cost is flat in ``S``.
The sink enters once, at the end: ``l += exp(b - m)``.  A prompt is padded
to its bucket, and the kernel is told its true length ``n_valid`` (a scalar
prefetch): a query block whose first position is at or past it is *void*,
computes nothing in any of its steps and comes back 0.  ``n_valid`` asks
for no mask of its own: a real row sees only keys at or before itself, all
of them real.  Every step that runs builds the mask from positions and
selects: a body without the select for the steps wholly under the diagonal
was measured slower (the products bound a step, not the vector unit; the
64-wide tail costs the MXU a pass of 128).

**Paged decode of the full layers** (``gqa_paged_decode``): a block of the
K pool holds its ``bs`` tokens' 128-wide parts, then ``bs / 2`` rows of
tails in pairs, ``[tail[i] | tail[i + bs / 2]]``: ``[NB, Hk, bs * 3 / 2,
2 * tail]``.  Every row is whole lanes, a token costs exactly its ``D``
values, and one DMA moves a block's keys for all heads.  (A 192-wide row is
no multiple of the 128 lanes a DMA moves; padding to 256 would cost a third
more key bytes; ``mla_attention`` pairs tokens for the same reason.)  The
kernel walks a slot's live blocks; a block's pairs become one ``[bs, 2 *
tail]`` tile whose rows keep the lanes of their own half, so that one
product with the tail's query on both halves scores the block in token
order.  The V pool is the serving layout ``[NB, Hk, bs, dv]``.

**Window ring** (plain ``jnp``): per slot the last ``W`` positions of a
window layer, position ``p`` at place ``p % W``, ``[slots, Hk, W, D]`` and
``[slots, Hk, W, dv]`` (heads before places: the layout the step's two
products read without a copy).  32 slots are 21 MB a layer, read once a
step.

Each kernel has an XLA reference of the same signature (CPU tests, oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry

NEG_INF = -1e30
_VMEM_LIMIT = 64 * 1024 * 1024


def _flag_interpret() -> bool:
    from ..framework import flags

    return bool(flags.get_flag("pallas_interpret"))


def _parts(d: int):
    """Lane-aligned parts of a ``d``-wide head: the tail, then the rest."""
    lo = d % 128
    return ([(0, lo)] if lo else []) + ([(lo, d)] if d > lo else [])


# ---------------------------------------------------------------------------
# prefill: causal, grouped heads, optional window bound and sink
# ---------------------------------------------------------------------------

def _prefill_reference(q, k, v, sm_scale, window=None, sinks=None,
                       n_valid=None):
    f = jnp.float32
    B, S, H, _ = q.shape
    hk = k.shape[2]
    qg = q.astype(f).reshape(B, S, hk, H // hk, -1)
    s = jnp.einsum("bsgrd,btgd->bgrst", qg, k.astype(f)) * sm_scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i if window is None else (j <= i) & (i - j < window)
    s = jnp.where(seen, s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    if sinks is not None:
        b = sinks.astype(f).reshape(1, hk, H // hk, 1, 1)
        m = jnp.maximum(m, b)
    p = jnp.exp(s - m)
    l = jnp.sum(p, -1, keepdims=True)
    if sinks is not None:
        l = l + jnp.exp(b - m)
    o = jnp.einsum("bgrst,btgd->bsgrd", p / l, v.astype(f))
    o = o.reshape(B, S, H, -1)
    if n_valid is not None:
        real = jnp.arange(S)[None, :] < n_valid[:, None]
        o = jnp.where(real[:, :, None, None], o, 0.0)
    return o.astype(v.dtype)


def _prefill_blocks(S: int, window):
    """``(block_q, block_k)``: a window layer takes blocks of the band's
    size; a full layer 128 positions x ``rep`` heads against 512 keys."""
    if window is not None:
        b = min(S, 128)
        return b, b
    return min(S, 128), min(S, 512)


def _band(i, bq: int, bk: int, window, maximum=max):
    """``(first, last)`` key block query block ``i`` meets: from the band's
    first block (block 0 without a window) to the diagonal's.  Shared by the
    kernel's walk and the count of its steps (ints or arrays)."""
    first = 0 if window is None else maximum(i * bq - (window - 1), 0) // bk
    return first, (i * bq + bq - 1) // bk


def prefill_pairs_run(S: int, window, n_valid):
    """``[B]`` float32: the (query, key) pairs a query head of the grid steps
    the kernel computes for prompts of ``n_valid [B]`` real positions in a
    sequence of ``S``: ``block_q x block_k`` a step, over the band of every
    query block that holds a real position.  The blocks are the kernel's
    for ``S``, whichever path a call takes."""
    bq, bk = _prefill_blocks(S, window)
    i = jnp.arange(-(-S // bq))
    first, last = _band(i, bq, bk, window, jnp.maximum)
    live = (i * bq)[None, :] < jnp.asarray(n_valid, jnp.int32)[:, None]
    steps = jnp.sum(jnp.where(live, last - first + 1, 0), axis=1)
    return steps.astype(jnp.float32) * (bq * bk)


def _pallas_prefill(q, k, v, sm_scale, window=None, sinks=None, n_valid=None,
                    block_q=None, block_k=None, interpret=False):
    """Grid ``(B * Hk, n_q, band blocks)``; the online-softmax state lives in
    scratch, lane-replicated.  The products take the operands in their own
    dtype (bf16 on the chip) and accumulate in float32.  ``n_valid`` is a
    scalar prefetch the body reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    hk, dv = k.shape[2], v.shape[-1]
    rep = H // hk
    bq, bk = _prefill_blocks(S, window)
    bq, bk = block_q or bq, block_k or bk
    n_q = S // bq
    R = rep * bq
    parts = _parts(D)
    if n_valid is None:
        n_valid = jnp.full((B,), S, jnp.int32)

    def band(i, maximum=jnp.maximum):
        return _band(i, bq, bk, window, maximum)

    # key blocks a query block can meet: the whole causal range, or the band
    n_steps = max(last - first + 1
                  for first, last in (band(i, max) for i in range(n_q)))

    # rows of a program: (head of the group, position of the block)
    def rows(x):
        w = x.shape[-1]
        x = x.reshape(B, n_q, bq, hk, rep, w).transpose(0, 3, 1, 4, 2, 5)
        return x.reshape(B * hk, n_q, R, w)

    def heads(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * hk, S, x.shape[-1])

    if sinks is None:
        sinks = jnp.full((H,), NEG_INF, jnp.float32)
    sink_rows = jnp.broadcast_to(
        sinks.astype(jnp.float32).reshape(hk, rep, 1, 1),
        (hk, rep, bq, 128)).reshape(hk, R, 128)

    def kernel(nv_ref, *refs):
        n = len(parts)
        q_refs, k_refs = refs[:n], refs[n:2 * n]
        v_ref, sink_ref, o_ref, acc_ref, m_ref, l_ref = refs[2 * n:]
        g, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        first, last = band(qi)
        kb = first + j
        # a query block past the prompt's end is void: it computes nothing
        live = qi * bq < nv_ref[g // hk]

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(live & (kb <= last))
        def _step():
            # one MXU pass whatever the process's default precision
            dot = functools.partial(jax.lax.dot_general,
                                    precision=jax.lax.Precision.DEFAULT,
                                    preferred_element_type=jnp.float32)
            dims = (((1,), (1,)), ((), ()))
            s = dot(q_refs[0][...], k_refs[0][...], dims)
            for q_ref, k_ref in zip(q_refs[1:], k_refs[1:]):
                s = s + dot(q_ref[...], k_ref[...], dims)
            row = jax.lax.broadcasted_iota(jnp.int32, (R, bk), 0)
            q_pos = qi * bq + jnp.bitwise_and(row, bq - 1)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (R, bk), 1)
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & (q_pos - k_pos < window)
            # a row with no key in this block leaves exp(0) behind; the
            # diagonal block, which comes last and holds the row's own key,
            # scales it away (alpha = 0)
            s = jnp.where(seen, s * sm_scale, NEG_INF)
            m_prev = jnp.max(m_ref[...], axis=1)   # lane-replicated -> [R]
            l_prev = jnp.max(l_ref[...], axis=1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            rep_l = lambda x: jnp.broadcast_to(x[:, None], (R, 128))  # noqa: E731
            l_ref[...] = rep_l(alpha * l_prev + jnp.sum(p, axis=1))
            m_ref[...] = rep_l(m_new)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + dot(
                p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())))

        @pl.when(live & (j == n_steps - 1))
        def _finalize():
            m = jnp.max(m_ref[...], axis=1)
            l = jnp.max(l_ref[...], axis=1)
            b = jnp.max(sink_ref[...], axis=1)
            m_fin = jnp.maximum(m, b)
            keep = jnp.exp(m - m_fin)
            l_fin = jnp.maximum(l * keep + jnp.exp(b - m_fin), 1e-30)
            o_ref[...] = (acc_ref[...] * (keep / l_fin)[:, None]).astype(
                o_ref.dtype)

        # zeros, not what VMEM holds: the next layer multiplies these rows'
        # keys and values by a probability of 0, and 0 x NaN is NaN
        @pl.when(jnp.logical_not(live) & (j == n_steps - 1))
        def _void():
            o_ref[...] = jnp.zeros_like(o_ref)

    # The index maps do not read ``n_valid``: a void step still fetches its
    # blocks.  Maps that repeat the last live block instead cost every step
    # 0.17-0.23 us of scalar work, more than the fetches they save a full
    # layer (PERF.md, PR 38).
    def q_idx(g, i, j, nv_ref):
        return (g, i, 0, 0)

    def kv_idx(g, i, j, nv_ref):
        # a repeated index elides the fetch of a block past the diagonal
        first, last = band(i)
        return (g, jnp.minimum(first + j, last), 0)

    assert bq & (bq - 1) == 0, "block_q must be a power of two"
    q_parts = [rows(q[..., lo:hi]) for lo, hi in parts]
    k_parts = [heads(k[..., lo:hi]) for lo, hi in parts]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * hk, n_q, n_steps),
            in_specs=[pl.BlockSpec((None, None, R, hi - lo), q_idx)
                      for lo, hi in parts]
            + [pl.BlockSpec((None, bk, hi - lo), kv_idx) for lo, hi in parts]
            + [pl.BlockSpec((None, bk, dv), kv_idx),
               pl.BlockSpec((None, R, 128),
                            lambda g, i, j, nv_ref: (g % hk, 0, 0))],
            out_specs=pl.BlockSpec((None, None, R, dv), q_idx),
            scratch_shapes=[pltpu.VMEM((R, dv), jnp.float32),
                            pltpu.VMEM((R, 128), jnp.float32),
                            pltpu.VMEM((R, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B * hk, n_q, R, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gqa_prefill_attn",
    )(jnp.asarray(n_valid, jnp.int32), *q_parts, *k_parts, heads(v),
      sink_rows)
    out = out.reshape(B, hk, n_q, rep, bq, dv).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, S, H, dv)


def _prefill_fits(q, k, v, window) -> bool:
    S, H, D = q.shape[1], q.shape[2], q.shape[3]
    rep = H // k.shape[2]
    return (S % 128 == 0 and (S <= 512 or S % 512 == 0)
            and D % 128 in (0, 64) and D >= 128 and v.shape[-1] % 128 == 0
            and rep & (rep - 1) == 0
            and (window is None or window >= 1))


def gqa_prefill_attention(q, k, v, sm_scale, window=None, sinks=None,
                          interpret=False, n_valid=None):
    """Causal attention of whole sequences from position 0.

    ``q [B, S, H, D]``, ``k [B, S, Hk, D]``, ``v [B, S, Hk, dv]``; query head
    ``g`` reads KV head ``g // (H / Hk)``.  ``window``: a query sees the
    ``window`` latest keys, itself included (None: all before it).  ``sinks
    [H]``: a bias a head that adds ``exp(b)`` to the softmax's denominator
    and nothing to its numerator.  ``n_valid [B]`` (int32; None: ``S``): the
    positions of each sequence that are real, the others its bucket's
    padding.  Returns ``[B, S, H, dv]``.

    The contract of ``n_valid``: rows before it are what they are without
    it, bit for bit.  Rows at or past it are finite (given finite inputs)
    and NOT TO BE READ: the kernel returns 0 for the query blocks that lie
    wholly past it and computes the padded rows of the block it ends in as
    before; the XLA reference returns 0 for every one of them.  A caller
    may rely on "finite", on nothing else.  The padded positions' q, k and v
    must be finite themselves: a padded key or value inside a key block
    that real rows visit is multiplied by a probability of 0.  No step
    carries a mask for ``n_valid``; every step that runs carries the causal
    (and the band's) mask."""
    from . import use_pallas

    asked = interpret
    interpret = interpret or _flag_interpret()
    ok = _prefill_fits(q, k, v, window)
    if asked and not ok:
        raise ValueError(f"gqa_prefill_attention(interpret=True): shapes "
                         f"{q.shape} {k.shape} {v.shape} do not fit the "
                         "kernel's blocks")
    if (use_pallas() or interpret) and ok:
        registry.ensure_admitted("gqa_prefill_attn")
        return _pallas_prefill(q, k, v, sm_scale, window, sinks, n_valid,
                               interpret=interpret)
    return _prefill_reference(q, k, v, sm_scale, window, sinks, n_valid)


# ---------------------------------------------------------------------------
# the full layers' pools: K in whole-lane rows, V in the serving layout
# ---------------------------------------------------------------------------

def _tail(d_k: int) -> int:
    """Width of a key's tail: the pool pairs two tails in a row as wide as
    the key's other part, so the key is three tails wide (192 = 64 + 128)."""
    if d_k % 3:
        raise ValueError(f"key width {d_k} is not tail + 2 x tail")
    return d_k // 3


def init_kv_pools(num_blocks: int, block_size: int, kv_heads: int, d_k: int,
                  d_v: int, dtype):
    t = _tail(d_k)
    return (jnp.zeros((num_blocks, kv_heads, block_size * 3 // 2, 2 * t),
                      dtype),
            jnp.zeros((num_blocks, kv_heads, block_size, d_v), dtype))


def pack_k_blocks(k):
    """Keys in token order ``[..., bs, D]`` -> pool rows ``[..., bs * 3 / 2,
    2 * tail]``: the tokens' other parts, then the tails of tokens ``i`` and
    ``i + bs / 2`` side by side."""
    *lead, bs, d = k.shape
    t = _tail(d)
    tails = k[..., :t].reshape(*lead, 2, bs // 2, t)
    pairs = jnp.concatenate([tails[..., 0, :, :], tails[..., 1, :, :]], -1)
    return jnp.concatenate([k[..., t:], pairs], axis=-2)


def unpack_k_blocks(rows):
    """The inverse of :func:`pack_k_blocks`."""
    *lead, r3, w = rows.shape
    bs, t = r3 * 2 // 3, w // 2
    pairs = rows[..., bs:, :]
    tails = jnp.concatenate([pairs[..., :t], pairs[..., t:]], axis=-2)
    return jnp.concatenate([tails, rows[..., :bs, :]], axis=-1)


def write_kv_prefill(k_pool, v_pool, blocks, k_seq, v_seq):
    """A prefilled sequence's K/V ``[n_blocks * bs, Hk, .]`` into its blocks
    ``[n_blocks]`` (bucket-padded: freed padding blocks are id 0)."""
    nb, hk, bs, dv = v_pool.shape
    n = blocks.shape[0]
    ks = jnp.swapaxes(k_seq.reshape(n, bs, hk, -1), 1, 2)    # [n, Hk, bs, D]
    vs = jnp.swapaxes(v_seq.reshape(n, bs, hk, dv), 1, 2)
    return (k_pool.at[blocks].set(pack_k_blocks(ks).astype(k_pool.dtype)),
            v_pool.at[blocks].set(vs.astype(v_pool.dtype)))


def write_kv_token(k_pool, v_pool, block_table, lengths, k_new, v_new):
    """Append one token's K/V per slot: ``k_new [B, Hk, D]``, ``v_new [B, Hk,
    dv]`` at block ``table[b, lengths[b] // bs]``, place ``lengths[b] % bs``.
    Inactive slots (length 0, table row 0) write into the trash block.  Rows
    are scattered into the pools viewed ``[rows, width]`` (a bitcast of the
    row-major pool), so a donated pool is updated in place and keeps the
    layout the decode kernel reads (``decode_attention.write_paged_token``
    has the reason)."""
    nb, hk, bs, dv = v_pool.shape
    r3, w = k_pool.shape[2:]
    t, half = w // 2, bs // 2
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None],
                               axis=1)[:, 0]
    place = (lengths % bs)[:, None]                                 # [B, 1]
    head = phys[:, None] * hk + jnp.arange(hk)                      # [B, Hk]
    v_flat = v_pool.reshape(-1, dv).at[(head * bs + place).reshape(-1)].set(
        v_new.reshape(-1, dv).astype(v_pool.dtype))
    k_new = k_new.astype(k_pool.dtype)
    k_flat = k_pool.reshape(-1, w).at[(head * r3 + place).reshape(-1)].set(
        k_new[..., t:].reshape(-1, w))
    # the tail's pair row: this token's half replaced, the other kept
    rows = (head * r3 + bs + place % half).reshape(-1)
    twice = jnp.concatenate([k_new[..., :t], k_new[..., :t]], -1)
    mine = (jnp.arange(w) >= t)[None, None, :] == (place >= half)[:, :, None]
    k_flat = k_flat.at[rows].set(jnp.where(
        mine, twice, k_flat[rows].reshape(-1, hk, w)).reshape(-1, w))
    return k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape)


# ---------------------------------------------------------------------------
# paged decode over the full layers' pools
# ---------------------------------------------------------------------------

def _decode_reference(q, k_pool, v_pool, block_table, lengths, sm_scale):
    f = jnp.float32
    B, H, D = q.shape
    hk, dv = v_pool.shape[1], v_pool.shape[3]
    # [B, MAXB, Hk, bs, .] -> [B, C, Hk, .]
    k = unpack_k_blocks(jnp.take(k_pool, block_table, axis=0))
    k = jnp.swapaxes(k, 2, 3).reshape(B, -1, hk, D).astype(f)
    v = jnp.swapaxes(jnp.take(v_pool, block_table, axis=0), 2, 3)
    v = v.reshape(B, -1, hk, dv).astype(f)
    live = jnp.arange(k.shape[1])[None, :] < lengths[:, None]       # [B, C]
    # rows past the length are pool trash, and 0 * NaN = NaN
    k = jnp.where(live[:, :, None, None], k, 0.0)
    v = jnp.where(live[:, :, None, None], v, 0.0)
    s = jnp.einsum("bgrd,bcgd->bgrc", q.astype(f).reshape(B, hk, H // hk, D),
                   k) * sm_scale
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    o = jnp.einsum("bgrc,bcgd->bgrd", jax.nn.softmax(s, axis=-1), v)
    o = o * (lengths > 0)[:, None, None, None]
    return o.reshape(B, H, dv).astype(q.dtype)


def _pallas_paged_decode(q, k_pool, v_pool, block_table, lengths, sm_scale,
                         interpret=False):
    """Grid ``(B,)``; per slot a double-buffered DMA of each LIVE block's
    keys and values for all heads (table and lengths scalar-prefetched),
    one online-softmax step a block: the keys' other parts against their
    query, the tails (each in the lanes of its half of the block, zeros in
    the other half's) against the tail's query repeated on both halves.
    HBM reads are the live tokens' ``D + dv`` values a KV head, once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    nb, hk, r3, w = k_pool.shape
    bs, dv = v_pool.shape[2], v_pool.shape[3]
    t, half, rep = w // 2, bs // 2, H // hk
    maxb = block_table.shape[1]
    qg = q.reshape(B, hk, rep, D)
    # the tail's query on both halves of the pair's lanes: against a tile
    # whose rows keep their own half, one product scores the whole block
    q_tail = jnp.concatenate([qg[..., :t], qg[..., :t]], -1)

    def kernel(tbl_ref, len_ref, qp_ref, qt_ref, k_hbm, v_hbm, o_ref, kbuf,
               vbuf, sems):
        b = pl.program_id(0)
        L = len_ref[b]
        n_live = jnp.minimum((L + bs - 1) // bs, maxb)
        qp = qp_ref[0].astype(kbuf.dtype)                  # [hk, rep, w]
        qt = qt_ref[0].astype(kbuf.dtype)
        first_half = jax.lax.broadcasted_iota(jnp.int32, (1, half, w), 2) < t

        def copies(slot, j):
            phys = tbl_ref[b, j]
            return (pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot],
                                          sems.at[slot, 0]),
                    pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot],
                                          sems.at[slot, 1]))

        def start(slot, j):
            for c in copies(slot, j):
                c.start()

        @pl.when(n_live > 0)
        def _prologue():
            start(0, 0)

        dot = functools.partial(jax.lax.dot_general,
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        qk = (((2,), (2,)), ((0,), (0,)))          # batch dim (heads) leading
        pv = (((2,), (1,)), ((0,), (0,)))

        def body(j, carry):
            acc, m_prev, l_prev = carry
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_live)
            def _prefetch():
                start(jax.lax.rem(j + 1, 2), j + 1)

            for c in copies(slot, j):
                c.wait()
            kb, vb = kbuf[slot], vbuf[slot]        # [hk, r3, w] [hk, bs, dv]
            pairs = kb[:, bs:, :]                  # [tail i | tail i + bs/2]
            none = jnp.zeros_like(pairs)
            # token i's tail in the lanes of its half, zeros in the other's
            tails = jnp.concatenate([jnp.where(first_half, pairs, none),
                                     jnp.where(first_half, none, pairs)],
                                    axis=1)                # [hk, bs, w]
            s = dot(qp, kb[:, :bs, :], qk) + dot(qt, tails, qk)
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                    (hk, rep, bs), 2)
            s = jnp.where(pos < L, s * sm_scale, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            return (acc * alpha[..., None] + dot(p.astype(vb.dtype), vb, pv),
                    m_new, alpha * l_prev + jnp.sum(p, axis=2))

        acc, _, l = jax.lax.fori_loop(
            0, n_live, body,
            (jnp.zeros((hk, rep, dv), jnp.float32),
             jnp.full((hk, rep), NEG_INF, jnp.float32),
             jnp.zeros((hk, rep), jnp.float32)))
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(
            o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, hk, rep, w), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, hk, rep, w), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),    # the pools stay in HBM
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hk, rep, dv),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, hk, r3, w), k_pool.dtype),
                            pltpu.VMEM((2, hk, bs, dv), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, hk, rep, dv), q.dtype),
        interpret=interpret,
        name="gqa_paged_decode",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg[..., t:], q_tail, k_pool, v_pool)
    return out.reshape(B, H, dv)


def gqa_paged_decode_attention(q, k_pool, v_pool, block_table, lengths,
                               sm_scale, interpret=False):
    """One query a slot over its live blocks.  ``q [B, H, D]``; ``k_pool
    [NB, Hk, bs * 3 / 2, 2 * tail]`` (:func:`pack_k_blocks`), ``v_pool [NB,
    Hk, bs, dv]``; ``block_table [B, MAXB]``; ``lengths [B]`` (tokens to
    attend, the current one included; 0 = inactive slot, whose output is
    zero).  Returns ``[B, H, dv]``."""
    from . import use_pallas

    interpret = interpret or _flag_interpret()
    lengths = jnp.asarray(lengths, jnp.int32)
    bs, dv = v_pool.shape[2], v_pool.shape[3]
    if (use_pallas() or interpret) and k_pool.shape[-1] % 128 == 0 \
            and dv % 128 == 0 and bs % 32 == 0:
        registry.ensure_admitted("gqa_paged_decode")
        return _pallas_paged_decode(q, k_pool, v_pool, block_table, lengths,
                                    sm_scale, interpret=interpret)
    return _decode_reference(q, k_pool, v_pool, block_table, lengths,
                             sm_scale)


# ---------------------------------------------------------------------------
# the window layers' ring: the last W positions of every slot
# ---------------------------------------------------------------------------

def init_ring(max_slots: int, window: int, kv_heads: int, d_k: int, d_v: int,
              dtype):
    return {"k": jnp.zeros((max_slots, kv_heads, window, d_k), dtype),
            "v": jnp.zeros((max_slots, kv_heads, window, d_v), dtype)}


def ring_rows(seq, n_valid, window: int):
    """What a prompt leaves in its ring: of ``seq [n, S, Hk, d]`` (positions
    0 .. S-1, of which ``n_valid [n]`` are real) the latest real position
    of every place, ``[n, Hk, window, d]``; a place no real position has
    reached yet holds position 0's row and is never attended."""
    j = jnp.arange(window)[None, :]
    last = n_valid.astype(jnp.int32)[:, None] - 1
    pos = jnp.maximum(j + window * ((last - j) // window), 0)
    return jnp.swapaxes(
        jnp.take_along_axis(seq, pos[:, :, None, None], axis=1), 1, 2)


def write_ring_token(ring, lengths, k_new, v_new):
    """Position ``lengths[b]`` of every live slot into place ``lengths[b] %
    W``; an inactive slot (length 0) keeps its ring.  Rows are scattered
    into the ring viewed ``[slots * Hk * W, d]`` (a bitcast), as the pools'
    token write is: a scatter whose window spans heads and width makes the
    chip's compiler copy the whole ring into another layout and back, every
    layer of every step."""
    slots, hk, window = ring["k"].shape[:3]
    lengths = jnp.asarray(lengths, jnp.int32)
    rows = ((jnp.arange(slots)[:, None] * hk + jnp.arange(hk)) * window
            + (lengths % window)[:, None]).reshape(-1)          # [slots * Hk]
    live = jnp.repeat(lengths > 0, hk)[:, None]

    def write(buf, new):
        flat = buf.reshape(-1, buf.shape[-1])
        new = new.reshape(-1, buf.shape[-1]).astype(buf.dtype)
        return flat.at[rows].set(jnp.where(live, new, flat[rows])).reshape(
            buf.shape)

    return {"k": write(ring["k"], k_new), "v": write(ring["v"], v_new)}


def ring_decode_attention(q, ring, lengths, sm_scale, sinks=None):
    """One query a slot over its ring.  ``q [B, H, D]``; ``lengths [B]``:
    positions the sequence holds, the current one (already written)
    included; 0 = inactive slot, whose output is zero.  Every place a real
    position has reached is inside the window by construction.  Returns
    ``[B, H, dv]``."""
    f = jnp.float32
    B, H, D = q.shape
    k, v = ring["k"].astype(f), ring["v"].astype(f)
    hk, window = k.shape[1], k.shape[2]
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jnp.arange(window)[None, :] < lengths[:, None]          # [B, W]
    s = jnp.einsum("bgrd,bgwd->bgrw", q.astype(f).reshape(B, hk, H // hk, D),
                   k) * sm_scale
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    if sinks is not None:
        b = sinks.astype(f).reshape(1, hk, H // hk, 1)
        m = jnp.maximum(m, b)
    p = jnp.where(live[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    if sinks is not None:
        l = l + jnp.exp(b - m)
    o = jnp.einsum("bgrw,bgwd->bgrd", p / jnp.maximum(l, 1e-30),
                   jnp.where(live[:, None, :, None], v, 0.0))
    return o.reshape(B, H, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-registry entries (verified by analysis.pallas_lint; see registry.py)
# ---------------------------------------------------------------------------

def _prefill_shapes():
    sds = jax.ShapeDtypeStruct
    B, S, H, hk = 1, 256, 4, 2
    return (sds((B, S, H, 192), jnp.float32), sds((B, S, hk, 192), jnp.float32),
            sds((B, S, hk, 128), jnp.float32), sds((H,), jnp.float32),
            sds((B,), jnp.int32))


def _decode_shapes():
    sds = jax.ShapeDtypeStruct
    B, H, hk, nb, bs, maxb = 2, 8, 2, 16, 128, 4
    return (sds((B, H, 192), jnp.float32),
            sds((nb, hk, bs * 3 // 2, 128), jnp.float32),
            sds((nb, hk, bs, 128), jnp.float32),
            sds((B, maxb), jnp.int32), sds((B,), jnp.int32))


registry.register(
    "gqa_prefill_attn",
    lambda: (lambda q, k, v, b, n: _pallas_prefill(q, k, v, 1.0, window=128,
                                                   sinks=b, n_valid=n),
             _prefill_shapes()),
    presets=("serve",),
    description="causal flash forward, grouped heads a program, q/k wider "
                "than v (two products), window band and sink; the query "
                "blocks past the true length skipped")
registry.register(
    "gqa_paged_decode",
    lambda: (lambda q, k, v, bt, ln: _pallas_paged_decode(q, k, v, bt, ln,
                                                          1.0),
             _decode_shapes()),
    presets=("serve",),
    description="paged decode, 192-wide keys in whole-lane rows and "
                "128-wide values: one DMA pair per live block, all heads")
