"""SSD (state-space duality) chunked-scan kernel.

The training-time form of the Mamba-2-style selective state-space layer
(``models/ssd.py``): a linear recurrence

    S_t = a_t * S_{t-1} + B_t x_t^T        (state [N, P], decay a_t in (0,1])
    y_t = C_t^T S_t

computed in *chunks* of ``L`` tokens so the per-chunk work is two MXU-native
matmuls (the duality: a masked [L, L] @ [L, P] "attention" form within the
chunk) plus one rank-L state update, with the [N, P] state carried
sequentially chunk-to-chunk.  Per-token cost and cache size are constant in
sequence length — the counterfactual to attention's linear KV growth that the
``RecurrentState`` cache backend serves.

Layout: the caller flattens (batch, heads) into one leading ``G`` axis —
every head owns an independent recurrence, so the grid parallelizes over
``G`` and runs chunks sequentially within each ``g`` (the Pallas kernel
carries the state in a VMEM scratch accumulator across grid steps, the same
pattern flash attention uses for its running softmax).

Bit-parity contract (the fused-AdamW methodology): the kernel evaluates the
SAME jnp chunk expressions as :func:`ssd_scan_reference` —
:func:`ssd_chunk_outputs` / :func:`ssd_chunk_state` are literally shared —
so interpret-mode results are bit-identical to the reference, enforced by
``tests/test_ssd.py``.  The sequential :func:`ssd_recurrence_reference` is
the semantic oracle; chunked-vs-recurrent equality is a float-reassociation
question (matmul form re-orders the sums), checked to tight tolerance.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import registry

LANE = 128  # TPU lane width; N and P should be multiples of it on real TPUs


# ---------------------------------------------------------------------------
# shared chunk math (reference AND kernel body — the bit-parity seam)
# ---------------------------------------------------------------------------

def ssd_chunk_outputs(s, x, b, c, la):
    """Outputs of one chunk given the inbound state ``s``.

    ``s``: [N, P] state at the chunk start; ``x``: [L, P] inputs;
    ``b``/``c``: [L, N] input/output projections; ``la``: [L] log-decay
    (``log a_t``, <= 0).  Returns y [L, P] where

        y_t = sum_{s<=t} (prod_{u=s+1..t} a_u) (C_t . B_s) x_s
              + (prod_{u<=t} a_u) C_t^T S_in

    Rows with ``x = b = 0, la = 0`` are exact no-ops on every OTHER row's
    output (their matmul contributions are +/-0.0 and 0.0 is the additive
    identity), which is what makes zero-padded partial chunks — and the
    decode path's zero-initialized intra-chunk buffers — bit-identical to
    the full-sequence computation (``models/ssd.py`` leans on this for its
    decode-from-state parity).
    """
    L = x.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = si <= ti
    cum = _cumsum_col(la, mask)                       # [L, 1], inclusive
    cum_t = jnp.broadcast_to(cum, (L, L))             # [t, s] = cum[t]
    # log prod_{u=s+1..t} a_u; clamp masked entries BEFORE exp so the upper
    # triangle (positive log-sums) can't overflow into inf*0 = nan grads
    seg = jnp.where(mask, cum_t - cum_t.T, 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [L, L]
    m = jnp.where(mask, cb * jnp.exp(seg), 0.0)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [L, P]
    inter = jax.lax.dot_general(c, s, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    return y + jnp.exp(cum) * inter


def _cumsum_col(la, mask):
    """Inclusive cumulative sum of ``la`` ([L] or [1, L]) as a column
    [L, 1], by a masked row sum: Mosaic lowers neither ``cumsum`` nor
    1-D vectors, and at one chunk the [L, L] pass is noise beside the
    matmuls."""
    L = mask.shape[0]
    row = jnp.broadcast_to(la.reshape(1, L), (L, L))  # [t, s] = la[s]
    return jnp.sum(jnp.where(mask, row, 0.0), axis=1, keepdims=True)


def ssd_chunk_state(s, x, b, la):
    """State after one chunk:  S' = (prod a) S + sum_s (prod_{u>s} a_u) B_s x_s^T."""
    L = x.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    cum = _cumsum_col(la, si <= ti)                   # [L, 1]
    total = cum[L - 1:L]                              # [1, 1]
    bw = b * jnp.exp(total - cum)                     # [L, N]
    ds = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [N, P]
    return jnp.exp(total) * s + ds


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def ssd_scan_reference(x, b, c, la, chunk: int):
    """Pure-jnp chunked scan: the expression the kernel must bit-match.

    ``x``: [G, T, P]; ``b``/``c``: [G, T, N]; ``la``: [G, T]; ``T % chunk
    == 0`` (callers zero-pad — exact, see :func:`ssd_chunk_outputs`).
    Returns ``(y [G, T, P], s_final [G, N, P])``.  The per-``g`` work is a
    ``lax.scan`` over chunks calling the shared chunk helpers on UNBATCHED
    [L, ...] operands — the same shapes the kernel issues, so both lower to
    the same dots.
    """
    G, T, P = x.shape
    N = b.shape[-1]
    nc = T // chunk

    def per_g(_, inp):
        xg, bg, cg, lg = inp

        def step(s, ci):
            xc, bc, cc, lc = ci
            y = ssd_chunk_outputs(s, xc, bc, cc, lc)
            return ssd_chunk_state(s, xc, bc, lc), y

        s_f, ys = jax.lax.scan(
            step, jnp.zeros((N, P), jnp.float32),
            (xg.reshape(nc, chunk, P), bg.reshape(nc, chunk, N),
             cg.reshape(nc, chunk, N), lg.reshape(nc, chunk)))
        return _, (ys.reshape(T, P), s_f)

    _, (y, s) = jax.lax.scan(per_g, 0, (x, b, c, la))
    return y, s


def ssd_recurrence_reference(x, b, c, la):
    """Token-by-token recurrence — the semantic oracle the chunked form is
    dual to (equal up to float reassociation, NOT bitwise)."""
    G, T, P = x.shape
    N = b.shape[-1]

    def per_g(_, inp):
        xg, bg, cg, lg = inp

        def step(s, ti):
            xt, bt, ct, lt = ti
            s = jnp.exp(lt) * s + bt[:, None] * xt[None, :]
            y = jax.lax.dot_general(ct[None, :], s, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)[0]
            return s, y

        s_f, ys = jax.lax.scan(step, jnp.zeros((N, P), jnp.float32),
                               (xg, bg, cg, lg))
        return _, (ys, s_f)

    _, (y, s) = jax.lax.scan(per_g, 0, (x, b, c, la))
    return y, s


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan_call(x, b, c, la, *, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, T, P = x.shape
    N = b.shape[-1]
    nc = T // chunk

    def kernel(x_ref, b_ref, c_ref, la_ref, y_ref, s_ref, s_acc):
        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _init():
            s_acc[...] = jnp.zeros_like(s_acc)

        s = s_acc[...]
        xc = x_ref[0]
        bc = b_ref[0]
        cc = c_ref[0]
        lc = la_ref[0]                                # [1, chunk]
        y_ref[0] = ssd_chunk_outputs(s, xc, bc, cc, lc)
        s_new = ssd_chunk_state(s, xc, bc, lc)
        s_acc[...] = s_new
        # every chunk overwrites the g-row; the last (sequential) one wins
        s_ref[0] = s_new

    y, s = pl.pallas_call(
        kernel,
        grid=(G, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda g, ci: (g, ci, 0)),
            # la rides as a [G, 1, T] view: a (1, chunk) block of [G, T]
            # has a second-to-last dim of 1, which the TPU lowering refuses
            pl.BlockSpec((1, 1, chunk), lambda g, ci: (g, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, N, P), lambda g, ci: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, T, P), jnp.float32),
            jax.ShapeDtypeStruct((G, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        # the state accumulator carries across the chunk axis (reset at
        # ci == 0), so that axis MUST run sequentially; g-rows are
        # independent recurrences and may run in any order.  pallas_lint's
        # scratch-carry check certifies exactly this declaration
        # (tests/test_pallas_lint.py proves the ("parallel", "parallel")
        # variant is refused).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, b, c, la[:, None, :])
    return y, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd_scan_diff(x, b, c, la, chunk, interpret):
    return _ssd_scan_call(x, b, c, la, chunk=chunk, interpret=interpret)


def _ssd_scan_diff_fwd(x, b, c, la, chunk, interpret):
    return (_ssd_scan_call(x, b, c, la, chunk=chunk, interpret=interpret),
            (x, b, c, la))


def _ssd_scan_diff_bwd(chunk, interpret, res, ct):
    # backward recomputes through the jnp reference (bit-identical forward,
    # so the VJP is exact for the kernel too); no backward kernel needed
    x, b, c, la = res
    _, vjp = jax.vjp(lambda *a: ssd_scan_reference(*a, chunk=chunk),
                     x, b, c, la)
    return vjp(ct)


_ssd_scan_diff.defvjp(_ssd_scan_diff_fwd, _ssd_scan_diff_bwd)


def ssd_scan(x, b, c, la, *, chunk: int = 64,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan over ``G`` independent (batch*head) recurrences.

    ``x`` [G, T, P] fp32 inputs, ``b``/``c`` [G, T, N] fp32 input/output
    projections, ``la`` [G, T] fp32 log-decay; ``T`` must be a multiple of
    ``chunk``.  Returns ``(y [G, T, P], s_final [G, N, P])`` — bit-identical
    to :func:`ssd_scan_reference` (interpret mode is the CPU proof).

    On the chip ``chunk`` must be a multiple of 128 (or equal ``T``): the
    log-decay block is ``(1, 1, chunk)`` of a ``[G, 1, T]`` view and the
    lowering wants its last dim lane-aligned.  The v5e's compiler takes
    128, 256 and 512 at the 8B shape (G 64, T 2048, P 64, N 128) and
    refuses 64 (block shape) and 2048 (VMEM); ``tests/test_chip_compile.py``
    keeps the 128 case.  Smaller chunks run in interpret mode only.
    """
    if x.shape[1] % chunk:
        raise ValueError(f"T={x.shape[1]} not a multiple of chunk={chunk}")
    registry.ensure_admitted("ssd_scan")
    return _ssd_scan_diff(
        jnp.asarray(x, jnp.float32), jnp.asarray(b, jnp.float32),
        jnp.asarray(c, jnp.float32), jnp.asarray(la, jnp.float32),
        int(chunk), bool(interpret))


def _registry_example():
    G, T, P, N, chunk = 2, 128, 8, 4, 64
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    return (functools.partial(_ssd_scan_call, chunk=chunk, interpret=False),
            (sds((G, T, P), f32), sds((G, T, N), f32),
             sds((G, T, N), f32), sds((G, T), f32)))


registry.register(
    "ssd_scan", _registry_example, presets=("ssd",),
    description="chunked SSD scan: VMEM state carried across the "
                "sequential chunk axis")


def fused_enabled() -> Tuple[bool, bool]:
    """(enabled, interpret): the Pallas scan runs on TPU, or in interpret
    mode when ``FLAGS_pallas_interpret`` asks for the CPU parity path."""
    from ..framework import flags

    from . import use_pallas

    interpret = bool(flags.get_flag("pallas_interpret"))
    return (use_pallas() or interpret), interpret
