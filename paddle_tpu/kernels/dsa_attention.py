"""Learned sparse attention over a latent cache (DeepSeek-V3.2's lightning
indexer with pooled keys) for serving: the indexer's scores and its top-k,
the prefill attention limited to each query's selection, the decode
attention that reads only the selected rows, and the writes of the pooled
indexer keys (``models/kda_dsa_moe.py``).

**Selection.**  Positions are pooled in groups of ``POOL`` (4): group ``g``
is positions ``4g .. 4g + 3`` and its key ``kbar_g`` is the mean of their
indexer keys.  A query at position ``t`` scores every *complete* group
before its own (``g < t // 4``): ``I_tg = sum_j w_tj ReLU(q_tj . kbar_g)``
over the indexer's heads ``j``; it attends to the tokens of the ``top``
groups of highest score (all of them where there are fewer) and to its own
group's positions up to itself, the *tail*, always.

- ``select_groups`` (kernel ``dsa_select``): a block of a prompt's queries
  scores every group (one indexer head's product at a time, the weighted
  ReLU summed in float32, in VMEM) and keeps its top, exactly: the
  ``top``-th largest score by a bisection over the 32 bits of an
  order-preserving integer image of the float, ties at it to the lower
  group as ``lax.top_k`` breaks them (a row with fewer complete groups
  keeps them all).  Out comes a ``[L, L / 4]`` bf16 mask of 0 and 1: the
  float32 scores of a 32k prompt (1 GB) are never stored.
- ``sparse_prefill_attention`` (kernel ``dsa_prefill_attn``): causal
  attention of the expanded heads (keys and values rebuilt from the
  latent), a (query block, key block) step masked to each query's
  selection and tail.  The work is that of dense causal attention; the mask
  is what makes it sparse, so the kernel's roofline share of the selected
  pairs reads what block skipping would save.  A step is 512 queries of 8
  heads against 512 keys: each key and value block fetched serves 512
  queries (512 FLOP a byte, over the chip's ridge), so the step waits on
  the MXU and not on its DMAs.  A query block wholly past the prompt
  fetches nothing.
- ``sparse_decode_attention`` (kernel ``dsa_sparse_decode``): absorbed
  attention (``q_lat = q_nope W_k^T``, the output in latent space) of one
  token a slot over a list of 4-token groups a slot, scalar-prefetched: the
  kernel copies each listed group's two pool rows into VMEM and attends
  over them alone.

The latent pool is ``[NB, 2 bs, rank / 2]``: a token's ``rank`` values are
two rows of ``rank / 2`` lanes, so a group is 8 rows, the least the chip's
DMA moves along a bf16 array's rows (``mla_attention``'s pair layout would
put a group in 2).  The pooled-key pool is ``[NB, bs / 4, width]``, one row
a group, in the blocks of the latent pool.  A decode step adds a quarter of
its token's key into its group's row (the first token of a group sets it),
so a row is the mean once its group is complete.

Each kernel has an XLA reference of the same signature (CPU path, oracle).
``dsa.programs{kernel}`` (``paddle_tpu.obs``) counts the calls traced;
the prefill attention's also carry the query tile it took, ``bq``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs

POOL = 4
NEG_INF = float("-inf")
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def _flag_interpret() -> bool:
    from ..framework import flags

    return bool(flags.get_flag("pallas_interpret"))


def _count(kernel: str, **labels) -> None:
    obs.registry().counter("dsa.programs", kernel=kernel, **labels).inc()


def _pallas_ok(interpret: bool) -> bool:
    from . import use_pallas

    return use_pallas() or interpret


# ---------------------------------------------------------------------------
# the pools and their writes
# ---------------------------------------------------------------------------

def init_latent_pool(num_blocks: int, block_size: int, rank: int, dtype):
    return jnp.zeros((num_blocks, 2 * block_size, rank // 2), dtype)


def write_latent_prefill(pool, blocks, seq, rank: int = None):
    """A prefilled sequence's latent rows ``[n_blocks * bs, rank]`` into its
    blocks ``[n_blocks]`` (bucket-padded: freed padding blocks are id 0)."""
    return pool.at[blocks].set(
        seq.reshape(blocks.shape[0], *pool.shape[1:]).astype(pool.dtype))


def write_latent_token(pool, block_table, lengths, new):
    """One token's latent ``[B, rank]`` a slot at position ``lengths[b]``
    (block ``table[b, pos // bs]``); inactive slots write into the trash
    block.  Rows are scattered into the pool viewed ``[rows, rank / 2]``, so
    a donated pool is updated in place."""
    nb, r2, half = pool.shape
    bs = r2 // 2
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None],
                               axis=1)[:, 0]
    first = phys * r2 + 2 * (lengths % bs)
    rows = jnp.stack([first, first + 1], axis=1).reshape(-1)
    flat = pool.reshape(-1, half)
    return flat.at[rows].set(new.reshape(-1, half).astype(pool.dtype)) \
        .reshape(pool.shape)


def gather_latent(pool, block_table):
    """``[B, MAXB * bs, rank]``: every table entry's tokens."""
    B = block_table.shape[0]
    return jnp.take(pool, block_table, axis=0).reshape(B, -1,
                                                       2 * pool.shape[-1])


def init_index_pool(num_blocks: int, block_size: int, width: int, dtype):
    return jnp.zeros((num_blocks, block_size // POOL, width), dtype)


def pool_keys(k, n_valid):
    """``k [L, width]`` -> ``[ceil(L / 4), width]``: each group's mean over its
    positions before ``n_valid`` (a group the prompt ends inside holds the
    quarter-sums of its real positions, as decode leaves it)."""
    L, w = k.shape
    real = (jnp.arange(L) < n_valid)[:, None]
    k = jnp.where(real, k.astype(F32), 0.0)
    k = jnp.pad(k, ((0, -L % POOL), (0, 0)))
    return jnp.sum(k.reshape(-1, POOL, w), axis=1) / POOL


def write_index_prefill(pool, blocks, rows):
    """A prefilled sequence's pooled rows ``[n_blocks * bs / 4, width]``
    into its blocks ``[n_blocks]``."""
    return pool.at[blocks].set(
        rows.reshape(blocks.shape[0], pool.shape[1], -1).astype(pool.dtype))


def write_index_token(pool, block_table, lengths, key):
    """Add one token's indexer key ``[B, width]`` a slot into its group's
    row: position ``lengths[b]``, block ``table[b, pos // bs]``, row ``(pos %
    bs) // 4``; the first position of a group replaces the row.  Inactive
    slots write into the trash block."""
    nb, per, w = pool.shape
    bs = per * POOL
    lengths = jnp.asarray(lengths, jnp.int32)
    phys = jnp.take_along_axis(block_table, (lengths // bs)[:, None],
                               axis=1)[:, 0]
    rows = phys * per + (lengths % bs) // POOL
    flat = pool.reshape(-1, w)
    old = jnp.where((lengths % POOL == 0)[:, None], 0.0,
                    flat[rows].astype(F32))
    new = old + key.astype(F32) / POOL
    return flat.at[rows].set(new.astype(pool.dtype)).reshape(pool.shape)


# ---------------------------------------------------------------------------
# prefill: the indexer's scores and each row's threshold
# ---------------------------------------------------------------------------

def _causal_groups(L: int, G: int, n_valid):
    t = jnp.arange(L)[:, None]
    g = jnp.arange(G)[None, :]
    return (g < t // POOL) & (t < n_valid)


def _index_reference(q, w, kbar, n_valid):
    s = jnp.einsum("ljd,gd->ljg", q.astype(F32), kbar.astype(F32),
                   precision=_HI)
    s = jnp.einsum("ljg,lj->lg", jax.nn.relu(s), w.astype(F32),
                   precision=_HI)
    return jnp.where(_causal_groups(q.shape[0], kbar.shape[0], n_valid), s,
                     NEG_INF)


def _select_reference(q, w, kbar, n_valid, top: int):
    G = kbar.shape[0]
    scores = _index_reference(q, w, kbar, n_valid)
    top = min(top, G)
    vals, idx = jax.lax.top_k(scores, top)
    rows = jnp.arange(q.shape[0])[:, None]
    return jnp.zeros(scores.shape, jnp.bfloat16).at[rows, idx].set(
        jnp.where(vals > NEG_INF, 1.0, 0.0).astype(jnp.bfloat16))


def _order_key(x):
    """Float32 -> int32 whose signed order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ (jax.lax.shift_right_arithmetic(b, 31) & 0x7FFFFFFF)


def _pallas_select(q, w, kbar, n_valid, top: int, interpret=False,
                   br=128):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, nh, d = q.shape
    G = kbar.shape[0]
    br = min(br, L)
    sign = -2 ** 31
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=F32)

    def kernel(nv_ref, q_ref, w_ref, k_ref, o_ref):
        i = pl.program_id(0)
        live = i * br < nv_ref[0]

        @pl.when(live)
        def _select():
            kb, wv = k_ref[...], w_ref[...]
            acc = jnp.zeros((br, G), F32)
            for h in range(nh):
                s = dot(q_ref[h], kb, (((1,), (1,)), ((), ())))
                acc = acc + wv[:, h:h + 1] * jnp.maximum(s, 0.0)
            t = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, G), 0)
            g = jax.lax.broadcasted_iota(jnp.int32, (br, G), 1)
            complete = (g < t // POOL) & (t < nv_ref[0])
            key = _order_key(jnp.where(complete, acc, NEG_INF))
            # the top-th largest key, bit by bit from the top, in the
            # unsigned image u = key ^ sign (compared as signed after
            # flipping back)
            k = jnp.zeros((br, 1), jnp.int32)
            for bit in range(31, -1, -1):
                cand = k | (sign if bit == 31 else 1 << bit)
                n = jnp.sum(jnp.where(key >= (cand ^ sign), 1, 0), axis=1,
                            keepdims=True)
                k = jnp.where(n >= top, cand, k)
            k = k ^ sign
            # the ties at it that the top still takes, lowest group first:
            # the largest c with fewer of them strictly before it than that
            need = top - jnp.sum(jnp.where(key > k, 1, 0), axis=1,
                                 keepdims=True)
            tie = key == k
            c = jnp.zeros((br, 1), jnp.int32)
            for bit in range(max(G - 1, 1).bit_length() - 1, -1, -1):
                cand = c | (1 << bit)
                n = jnp.sum(jnp.where(tie & (g < cand), 1, 0), axis=1,
                            keepdims=True)
                c = jnp.where(n < need, cand, c)
            chosen = complete & ((key > k) | (tie & (g <= c)))
            o_ref[...] = jnp.where(chosen, 1.0, 0.0).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(live))
        def _none():
            o_ref[...] = jnp.zeros_like(o_ref)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L // br,),
            in_specs=[pl.BlockSpec((nh, br, d), lambda i, nv: (0, i, 0)),
                      pl.BlockSpec((br, nh), lambda i, nv: (i, 0)),
                      pl.BlockSpec((G, d), lambda i, nv: (0, 0))],
            out_specs=pl.BlockSpec((br, G), lambda i, nv: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((L, G), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select",
    )(jnp.reshape(jnp.asarray(n_valid, jnp.int32), (1,)),
      jnp.swapaxes(q, 0, 1), w.astype(F32), kbar.astype(q.dtype))


def select_groups(q, w, kbar, n_valid, top: int, interpret=False):
    """The indexer of a prompt: ``q [L, heads, d]`` (its queries), ``w [L,
    heads]``, ``kbar [L / 4, d]`` (the pooled keys), ``n_valid`` (scalar).
    Returns ``[L, L / 4]`` bfloat16, 1 where query ``t`` attends complete
    group ``g`` (``g < t // 4``): its ``top`` best, exactly, ties to the
    lower group as ``lax.top_k`` breaks them; 0 for a query past
    ``n_valid``."""
    interpret = interpret or _flag_interpret()
    L, G = q.shape[0], kbar.shape[0]
    if _pallas_ok(interpret) and L % 128 == 0 and G % 128 == 0 \
            and q.shape[-1] % 128 == 0 and top < G:
        _count("dsa_select")
        return _pallas_select(q, w, kbar, n_valid, top, interpret=interpret)
    return _select_reference(q, w, kbar, n_valid, top)


# ---------------------------------------------------------------------------
# prefill attention limited to the selection
# ---------------------------------------------------------------------------

def _selection_mask(qpos, kpos, sel_groups):
    """Whether query ``qpos`` sees key ``kpos``: a selected complete group
    before its own, or its own group up to itself."""
    qg, kg = qpos // POOL, kpos // POOL
    return (sel_groups & (kg < qg)) | ((kg == qg) & (kpos <= qpos))


def _prefill_reference(q, k, v, chosen, n_valid, sm_scale):
    """``q``, ``k``, ``v`` ``[H, L, d]``; ``chosen [L, L / 4]`` from
    :func:`select_groups`.  Rows past ``n_valid`` come back 0."""
    L = q.shape[1]
    sel = jnp.repeat(chosen > 0.5, POOL, axis=1)[:, :L]            # [L, L]
    t = jnp.arange(L)
    seen = _selection_mask(t[:, None], t[None, :], sel)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(F32), k.astype(F32),
                   precision=_HI) * sm_scale
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", p, v.astype(F32), precision=_HI)
    return jnp.where((t < n_valid)[None, :, None], o, 0.0).astype(v.dtype)


def _pallas_prefill(q, k, v, chosen, n_valid, sm_scale, interpret=False,
                    bq=512, bk=512, hb=8):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, L, d = q.shape
    dv = v.shape[-1]
    bq, bk, hb = min(bq, L), min(bk, L), min(hb, H)
    n_q, n_k = L // bq, L // bk
    _count("dsa_prefill_attn", bq=bq)
    bg = bk // POOL
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=F32)

    def last_block(i):
        return (i * bq + bq - 1) // bk

    def kernel(nv_ref, q_ref, k_ref, v_ref, c_ref, o_ref, acc_ref, m_ref,
               l_ref):
        i, j = pl.program_id(1), pl.program_id(2)
        live = i * bq < nv_ref[0]

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(live & (j <= last_block(i)))
        def _step():
            grp = jax.lax.broadcasted_iota(jnp.int32, (bg, bk), 0)
            tok = jax.lax.broadcasted_iota(jnp.int32, (bg, bk), 1)
            spread = jnp.where(tok // POOL == grp, 1.0, 0.0)
            sel = dot(c_ref[...], spread.astype(c_ref.dtype),
                      (((1,), (0,)), ((), ()))) > 0.5               # [bq, bk]
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            seen = _selection_mask(qpos, kpos, sel)
            for h in range(hb):
                s = dot(q_ref[h], k_ref[h], (((1,), (1,)), ((), ())))
                s = jnp.where(seen, s * sm_scale, -1e30)
                m_prev = jnp.max(m_ref[h], axis=1)
                l_prev = jnp.max(l_ref[h], axis=1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                p = jnp.exp(s - m_new[:, None])
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = jnp.broadcast_to(
                    (alpha * l_prev + jnp.sum(p, axis=1))[:, None], (bq, 128))
                m_ref[h] = jnp.broadcast_to(m_new[:, None], (bq, 128))
                acc_ref[h] = acc_ref[h] * alpha[:, None] + dot(
                    p.astype(v_ref.dtype), v_ref[h], (((1,), (0,)), ((), ())))

        @pl.when(j == n_k - 1)
        def _finalize():
            for h in range(hb):
                l = jnp.maximum(jnp.max(l_ref[h], axis=1), 1e-30)
                o_ref[h] = jnp.where(live, acc_ref[h] / l[:, None],
                                     0.0).astype(o_ref.dtype)

    def blocks(i, j, nv):
        """The (query block, key block) a step reads.  A repeated index
        elides the fetch: past the diagonal a step reads its row's last
        block, and a query block wholly past the prompt the last block its
        last live row read."""
        row = jnp.minimum(i, jnp.maximum(nv[0] - 1, 0) // bq)
        return row, jnp.where(i > row, last_block(row),
                              jnp.minimum(j, last_block(row)))

    def o_idx(g, i, j, nv):
        return (g, i, 0)

    def q_idx(g, i, j, nv):
        return (g, blocks(i, j, nv)[0], 0)

    def kv_idx(g, i, j, nv):
        return (g, blocks(i, j, nv)[1], 0)

    def c_idx(g, i, j, nv):
        return blocks(i, j, nv)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, n_q, n_k),
            in_specs=[pl.BlockSpec((hb, bq, d), q_idx),
                      pl.BlockSpec((hb, bk, d), kv_idx),
                      pl.BlockSpec((hb, bk, dv), kv_idx),
                      pl.BlockSpec((bq, bg), c_idx)],
            out_specs=pl.BlockSpec((hb, bq, dv), o_idx),
            scratch_shapes=[pltpu.VMEM((hb, bq, dv), F32),
                            pltpu.VMEM((hb, bq, 128), F32),
                            pltpu.VMEM((hb, bq, 128), F32)]),
        out_shape=jax.ShapeDtypeStruct((H, L, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_prefill_attn",
    )(jnp.reshape(jnp.asarray(n_valid, jnp.int32), (1,)), q, k, v,
      chosen.astype(jnp.bfloat16))


def sparse_prefill_attention(q, k, v, chosen, n_valid, sm_scale,
                             interpret=False):
    """Attention of a prompt's queries over their selections.  ``q``, ``k``
    ``[H, L, d]``, ``v [H, L, dv]`` (head-major), ``chosen [L, L / 4]`` the
    groups each query attends (:func:`select_groups`), ``n_valid``
    (scalar).
    Returns ``[H, L, dv]``; rows past ``n_valid`` are 0 (the kernel's query
    blocks wholly past it) or finite, and not to be read."""
    interpret = interpret or _flag_interpret()
    H, L, d = q.shape
    if _pallas_ok(interpret) and L % 512 == 0 and d % 128 == 0 \
            and H % min(8, H) == 0:
        return _pallas_prefill(q, k, v, chosen, n_valid, sm_scale,
                               interpret=interpret)
    return _prefill_reference(q, k, v, chosen, n_valid, sm_scale)


def prefill_pairs(n_valid, top: int):
    """``(selected, scored)`` int32: the (query, key) pairs a prompt of
    ``n_valid`` tokens attends (each query's selected groups' tokens and its
    tail) and the (query, group) pairs its indexer scored.  Query ``t`` has
    ``c = t // 4`` complete groups, selects ``4 min(c, top)`` tokens, has a
    tail of ``t % 4 + 1`` and scores ``c`` groups; summed per group of four
    queries in closed form."""
    n = jnp.asarray(n_valid, jnp.int32)
    full, rest = n // POOL, n % POOL
    m = jnp.minimum(full, top)
    # sum over q < full of min(q, top)
    under = m * (m - 1) // 2 + (full - m) * top
    selected = (POOL * POOL * under + 10 * full
                + rest * POOL * jnp.minimum(full, top) + rest * (rest + 1) // 2)
    scored = POOL * full * (full - 1) // 2 + rest * full
    return selected, scored


# ---------------------------------------------------------------------------
# decode: absorbed attention over a list of groups a slot
# ---------------------------------------------------------------------------

def decode_select(qi, w, index_pool, block_table, lengths, top: int):
    """The indexer of one decode step (XLA).  ``qi [B, heads, d]``, ``w [B,
    heads]``; ``lengths [B]`` the positions attended, the current one
    included (0: inactive).  Returns ``(groups [B, 1 + top] int32, count
    [B] int32, scored [B] int32)``: the query's own group first, then the
    selected complete groups (unsorted beyond ``count``), and how many
    complete groups were scored."""
    B = qi.shape[0]
    nb, per, width = index_pool.shape
    kbar = jnp.take(index_pool, block_table, axis=0).reshape(B, -1, width)
    G = kbar.shape[1]
    # bf16 products are exact in float32: no upcast copy of the keys
    s = jnp.einsum("bjd,bgd->bjg", qi.astype(kbar.dtype), kbar,
                   precision=_HI, preferred_element_type=F32)
    s = jnp.einsum("bjg,bj->bg", jax.nn.relu(s), w.astype(F32),
                   precision=_HI)
    pos = jnp.maximum(lengths - 1, 0)
    complete = pos // POOL                                   # groups scored
    s = jnp.where(jnp.arange(G)[None, :] < complete[:, None], s, NEG_INF)
    top = min(top, G)
    _, idx = jax.lax.top_k(s, top)
    n_sel = jnp.minimum(complete, top)
    live = lengths > 0
    groups = jnp.concatenate([complete[:, None], idx], axis=1)
    count = jnp.where(live, 1 + n_sel, 0)
    return groups.astype(jnp.int32), count.astype(jnp.int32), \
        jnp.where(live, complete, 0).astype(jnp.int32)


def _decode_reference(q_lat, pool, block_table, lengths, groups, count,
                      sm_scale):
    """``q_lat [B, H, rank]``; the tokens of the listed groups (the first is
    the query's own, cut at its position)."""
    B, n = groups.shape
    c = gather_latent(pool, block_table)                         # [B, C, r]
    C = c.shape[1]
    tok = (POOL * groups[:, :, None]
           + jnp.arange(POOL)[None, None, :]).reshape(B, n * POOL)
    entry = jnp.repeat(jnp.arange(n), POOL)[None, :]
    pos = (lengths - 1)[:, None]
    ok = (entry < count[:, None]) & (tok <= pos) & (tok < C)
    rows = jnp.take_along_axis(c, jnp.clip(tok, 0, C - 1)[:, :, None],
                               axis=1).astype(F32)
    rows = jnp.where(ok[:, :, None], rows, 0.0)
    s = jnp.einsum("bhr,bcr->bhc", q_lat.astype(F32), rows, precision=_HI)
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s * sm_scale, -1e30), -1)
    o = jnp.einsum("bhc,bcr->bhr", p, rows, precision=_HI)
    return jnp.where((count > 0)[:, None, None], o, 0.0).astype(q_lat.dtype)


def _pallas_decode(q_lat, pool, block_table, lengths, groups, count,
                   sm_scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, rank = q_lat.shape
    nb, r2, half = pool.shape
    bs = r2 // 2
    n = groups.shape[1]
    n_pad = -(-n // 64) * 64                # list entries a slot, padded
    unit = 2 * POOL                         # pool rows a group
    R = unit * n_pad
    per = bs // POOL
    # each entry's first pool row, in the pool viewed [NB * 2 bs, rank / 2]
    blk = jnp.take_along_axis(block_table, groups * POOL // bs, axis=1)
    rows = (blk * r2 + (groups % per) * unit).astype(jnp.int32)
    rows = jnp.pad(rows, ((0, 0), (0, n_pad - n))).reshape(-1)
    flat = pool.reshape(nb * r2, half)
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=F32)
    rows_t = (((1,), (1,)), ((), ()))
    plain = (((1,), (0,)), ((), ()))

    def kernel(rows_ref, cnt_ref, len_ref, q_ref, pool_hbm, o_ref, buf,
               sems):
        def copy(b, e, into):
            return pltpu.make_async_copy(
                pool_hbm.at[pl.ds(pl.multiple_of(rows_ref[b * n_pad + e],
                                                  unit), unit)],
                buf.at[into, pl.ds(pl.multiple_of(unit * e, unit), unit)],
                sems.at[into])

        def start(b):
            def one(e, _):
                copy(b, e, b % 2).start()
                return 0
            jax.lax.fori_loop(0, cnt_ref[b], one, 0)

        buf[...] = jnp.zeros_like(buf)       # unlisted rows stay finite
        start(0)
        c = jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)
        entry, tok = c // unit, (c % unit) // 2
        first_half = c % 2 == 0              # a token's score sits there

        def one_slot(b, _):
            @pl.when(b + 1 < B)
            def _next():
                start(b + 1)

            def wait(e, _):
                copy(b, 0, b % 2).wait()
                return 0
            jax.lax.fori_loop(0, cnt_ref[b], wait, 0)
            cur = buf[b % 2]                                  # [R, rank / 2]
            pos = len_ref[b] - 1
            ql = q_ref[b]                                     # [H, rank]
            own = pos - pos % POOL                # first position of the tail
            ok = first_half & (entry < cnt_ref[b]) & (
                (entry > 0) | (own + tok <= pos))
            # a token's two rows: its first half's product at its first row
            # plus its second half's, one row on, rolled back onto it
            s = dot(ql[:, :half], cur, rows_t) + pltpu.roll(
                dot(ql[:, half:], cur, rows_t), R - 1, 1)
            s = jnp.where(ok, s * sm_scale, -1e30)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.where(ok, jnp.exp(s - m), 0.0)
            l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
            o = jnp.concatenate(
                [dot(p.astype(cur.dtype), cur, plain),
                 dot(pltpu.roll(p, 1, 1).astype(cur.dtype), cur, plain)],
                axis=1)
            o_ref[b] = jnp.where(cnt_ref[b] > 0, o / l, 0.0).astype(
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
            in_specs=[whole((B, H, rank)),
                      pl.BlockSpec(memory_space=pl.ANY)],   # pool stays in HBM
            out_specs=whole((B, H, rank)),
            scratch_shapes=[pltpu.VMEM((2, R, half), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_sparse_decode",
    )(rows, count, lengths, q_lat, flat)


def sparse_decode_attention(q_lat, pool, block_table, lengths, groups, count,
                            sm_scale, interpret=False):
    """Absorbed attention of one token a slot over its listed groups.
    ``q_lat [B, H, rank]``; ``pool`` the latent pool; ``lengths [B]`` the positions attended, the current
    one included (0: inactive, output 0); ``groups [B, n]`` and ``count
    [B]`` from :func:`decode_select` (entry 0 the query's own group, cut at
    its position).  Returns ``[B, H, rank]``."""
    interpret = interpret or _flag_interpret()
    lengths = jnp.asarray(lengths, jnp.int32)
    rank = q_lat.shape[-1]
    if _pallas_ok(interpret) and rank % 256 == 0 \
            and 2 * pool.shape[-1] == rank:
        _count("dsa_sparse_decode")
        return _pallas_decode(q_lat, pool, block_table, lengths, groups,
                              count, sm_scale, interpret=interpret)
    return _decode_reference(q_lat, pool, block_table, lengths, groups,
                             count, sm_scale)
