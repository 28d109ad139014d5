"""Grouped matrix product: rows sorted by group, one weight matrix a group.

``out[r] = lhs[r] @ rhs[g(r)]`` for ``lhs [M, K]`` whose rows are sorted by
group, ``rhs [G, K, N]`` and ``group_sizes [G]`` (rows past their sum belong
to no group and are not computed).  The dropless expert layer's three
projections are this product; there is no capacity and no padding of a
group to a fixed size.

The Pallas kernel ``moe_grouped_mm`` tiles the rows (``tm``) and walks the
(row tile, group) pairs that share rows, in row order (the megablocks
schedule): a pair costs one DMA of the group's ``[K, tn]`` weight tile and
one ``[tb, K] x [K, tn]`` product for each ``tb``-row block of the tile that
holds rows of the group (``tb`` 128, the MXU's granule; a block that holds
none is skipped), masked to the group's rows.  So a pair pays for the rows
the group owns, to the granule, not for the tile: one prompt of 1,024
tokens is 6,144 rows but about 96 an expert, and a whole 512-row product a
pair computed five masked rows for every useful one (PERF.md section 6,
PR 36).  A group with no rows is never visited, so its weights are never
read.  At the decode shape (a few rows an expert) and for one or two
prompts (tens to hundreds of rows an expert) the kernel streams the touched
experts' weights once and is bound by those bytes; at the widest prefill
call (1,536 rows an expert) every weight tile is reused by its rows and the
kernel is bound by the products.  ``K`` is never split, so a row's result
does not depend on the tile or block it rode in.
``moe.grouped_mm_programs{tm}`` (``paddle_tpu.obs``) counts the calls traced
under each tile.

The kernel is NOT in ``kernels.registry``: its block index maps read the
schedule from scalar-prefetch data, which the static verifier cannot
evaluate (it would report ``krn-dynamic-index`` for every operand and prove
nothing about races or coverage).  What stands in: a loop oracle in
interpret mode over skewed, empty and full groups (``tests/test_mla_moe.py``)
and the chip's own compiler at the published widths
(``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs

# rows of one tile by the number of rows: fewer, larger tiles are fewer grid
# steps and the next group's weight DMA runs behind more of this one's work;
# a decode step's few rows keep the tile they always had.  What a pair
# computes does not follow the tile: it is the _BLOCK-row blocks of it that
# hold the group's rows (chip table by tile and block: PERF.md section 6,
# PR 36)
_TM_LADDER = ((4096, 512), (1024, 256), (256, 128), (0, 64))
_BLOCK = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def _reference(lhs, rhs, group_sizes):
    """XLA's own ragged product: the oracle and the CPU path."""
    out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                             preferred_element_type=jnp.float32)
    done = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(done[:, None], out, 0).astype(lhs.dtype)


def _schedule(group_sizes, m: int, tm: int):
    """The (row tile, group) pairs in row order.  Returns ``(offsets [G+1],
    pair_group [P], pair_tile [P], n_pairs)`` with ``P = tiles + G - 1`` the
    most there can be; entries past ``n_pairs`` repeat the last pair."""
    g = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    last = jnp.where(group_sizes > 0, (ends - 1) // tm, first - 1)
    per_group = jnp.maximum(last - first + 1, 0)                  # tiles a group
    n_pairs = jnp.sum(per_group)
    p_max = tiles + g - 1
    pair_group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), per_group,
                            total_repeat_length=p_max)
    before = jnp.cumsum(per_group) - per_group                    # pairs before g
    pair_tile = first[pair_group] + (jnp.arange(p_max, dtype=jnp.int32)
                                     - before[pair_group])
    live = jnp.arange(p_max) < n_pairs
    last_i = jnp.maximum(n_pairs - 1, 0)
    pair_group = jnp.where(live, pair_group, pair_group[last_i])
    pair_tile = jnp.clip(jnp.where(live, pair_tile, pair_tile[last_i]),
                         0, tiles - 1)
    return offsets, pair_group, pair_tile.astype(jnp.int32), n_pairs


def _tile_n(n: int, cap: int = 1408) -> int:
    """The widest multiple of 128 that divides ``n`` within ``cap``."""
    for tn in range(min(cap, n) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


def _pallas_gmm(lhs, rhs, group_sizes, tm: int, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    g, _, n = rhs.shape
    tn, tb = _tile_n(n), min(tm, _BLOCK)
    offsets, pair_group, pair_tile, n_pairs = _schedule(group_sizes, m, tm)

    def kernel(off_ref, grp_ref, tile_ref, np_ref, lhs_ref, rhs_ref, o_ref):
        p = pl.program_id(1)
        grp = grp_ref[p]
        lo, hi = off_ref[grp], off_ref[grp + 1]
        for b in range(tm // tb):
            first = tile_ref[p] * tm + b * tb

            # a live pair's blocks that hold rows of the group
            @pl.when((p < np_ref[0]) & (first < hi) & (first + tb > lo))
            def _block():
                rows = slice(b * tb, (b + 1) * tb)
                row = first + jax.lax.broadcasted_iota(jnp.int32, (tb, tn), 0)
                res = jax.lax.dot_general(
                    lhs_ref[rows, :], rhs_ref[...].astype(lhs_ref.dtype),
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                # the block's other rows are another pair's: kept as they are
                o_ref[rows, :] = jnp.where((row >= lo) & (row < hi),
                                           res.astype(o_ref.dtype),
                                           o_ref[rows, :])

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, pair_group.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, p, off, grp, tile, np_:
                             (tile[p], 0)),
                pl.BlockSpec((None, k, tn), lambda j, p, off, grp, tile, np_:
                             (grp[p], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, p, off, grp, tile, np_:
                                   (tile[p], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_mm",
    )(offsets, pair_group, pair_tile, n_pairs.reshape(1).astype(jnp.int32),
      lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """``lhs [M, K]`` (rows sorted by group) times ``rhs [G, K, N]`` by
    ``group_sizes [G]`` -> ``[M, N]`` in ``lhs``'s dtype, accumulated in
    float32.  Rows past ``sum(group_sizes)`` come back as zeros.  The kernel
    runs on the chip, or in the interpreter where ``interpret`` or
    ``FLAGS_pallas_interpret`` asks; XLA's ragged product otherwise."""
    from . import use_pallas
    from ..framework import flags

    interpret = interpret or bool(flags.get_flag("pallas_interpret"))
    m, k = lhs.shape
    n = rhs.shape[-1]
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    if not ((use_pallas() or interpret) and k % 128 == 0 and n % 128 == 0):
        return _reference(lhs, rhs, group_sizes)
    tm = next(t for floor, t in _TM_LADDER if m >= floor)
    # which tile the programs took: once a call each time a program that
    # holds it is traced (or an eager call made), never in a compiled step
    obs.registry().counter("moe.grouped_mm_programs", tm=tm).inc()
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _pallas_gmm(lhs, rhs, group_sizes, tm, interpret=interpret)[:m]
    # a tile no pair visits is never written
    done = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(done[:, None], out, 0)
