"""Dropless mixture of experts: sigmoid router with a selection bias, the
top-k of ALL experts, no capacity, shared experts beside the routed ones
(the DeepSeek-V3 expert layer; ``moe/__init__.py``'s ``MoELayer`` is the
older capacity-factor layer and stays as it is).

``s = sigmoid(x W_g)`` in float32; the ``k`` largest of ``s + b`` are chosen
(``b`` the selection bias, ``e_score_correction_bias``: it moves the choice
and never the weight); weights ``s_i / sum_chosen s`` (``norm_topk_prob``)
times ``routed_scaling_factor``; ``y = sum_i w_i E_i(x) + S(x)``.

Rows (token, choice) are sorted by expert, each projection is ONE grouped
matrix product over the experts that got rows
(``kernels.grouped_matmul``), and the weighted rows are gathered back into
token order.  Nothing pads an expert to a fixed size and nothing is
dropped: an expert with no row is never read, one with most rows just has
more row tiles.  Tokens marked invalid (a prefill bucket's padding, an
inactive decode slot) are routed nowhere and cost no product.

**A share of the experts** (``held=(first, count)``): under expert
parallelism a chip holds ``count`` of the layer's experts.  The router and
its bias keep every expert's output and the top-k is of all of them; the
stacked weights are the held experts' only; a row whose expert lives
elsewhere sorts past all groups exactly as an invalid row does, costs no
product, and the layer returns the held experts' part of ``sum_i w_i
E_i(x)``.  Nothing stands in for the other chips or their traffic.  With
most rows elsewhere the sorted rows are computed a segment at a time
(``_segment``): the workspace follows the rows this chip owns, not all the
rows routed.

Serving path only: there is no backward here (the training path's
gradients and the optimizer's share of expert state are ROADMAP's).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ... import obs
from ...framework.tensor import Tensor
from ...kernels.grouped_matmul import grouped_matmul
from ...kernels.swiglu import swiglu
from ...nn.initializer import Constant, Normal
from ...nn.layers import Layer

__all__ = ["DroplessMoE", "sigmoid_topk_route", "dropless_experts", "scope"]


@contextlib.contextmanager
def scope(name: str, x):
    """``name`` as a named scope of the program being traced (the device
    trace shows it) and, where ``x`` is a value and not a tracer, as a host
    span too."""
    span = (contextlib.nullcontext() if isinstance(x, jax.core.Tracer)
            else obs.span(name, cat="model"))
    with span, jax.named_scope(name):
        yield


def sigmoid_topk_route(x, w_router, bias, top_k: int, scale: float = 1.0,
                       norm_topk: bool = True):
    """``x [T, hidden]`` -> ``(idx [T, k] int32, weights [T, k] float32)``.
    The scores are float32 at the highest matmul precision whatever ``x``'s
    dtype: a choice must not depend on the MXU's pass count."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


# rows (token, choice) of one call from which a share's sorted rows are
# computed a segment at a time, and the room a segment leaves over the
# share's expected rows
_SEGMENT_FLOOR = 8192
_SEGMENT_ROOM = 2


def _segment(rows: int, share: float) -> int:
    """Rows of one segment of a call's ``rows`` sorted rows, of which the
    held experts expect ``share``: the whole call where every expert is held
    or the call is small, else the power-of-two fraction (an eighth at
    least) that holds ``_SEGMENT_ROOM`` times the expected rows."""
    if share >= 1.0 or rows < _SEGMENT_FLOOR:
        return rows
    parts = 1
    while parts < 8 and _SEGMENT_ROOM * share * 2 * parts <= 1.0 \
            and rows % (2 * parts) == 0:
        parts *= 2
    return rows // parts


def dropless_experts(x, idx, weights, w_gate_up, w_down, valid=None,
                     first: int = 0, num_experts=None,
                     interpret: bool = False):
    """``sum_j weights[t, j] * E_idx[t, j](x[t])`` over the HELD experts:
    SwiGLU experts ``first .. first + E`` of ``num_experts`` (default: all
    are held), stacked ``w_gate_up [E, hidden, 2 * width]`` (gate first) and
    ``w_down [E, width, hidden]``.  ``valid [T]`` bool: tokens to compute at
    all.  Returns the output ``[T, hidden]`` in ``x``'s dtype and ``[rows,
    experts touched, largest expert's rows]`` (float32) of the valid tokens
    and the held experts; a share of the experts counts a fourth, the rows
    routed elsewhere."""
    T, k = idx.shape
    E = w_gate_up.shape[0]
    flat = idx.reshape(-1)
    live = None if valid is None else jnp.repeat(valid, k)
    elsewhere = []
    if num_experts is not None and (first, E) != (0, num_experts):
        flat = flat - first
        mine = (flat >= 0) & (flat < E)
        elsewhere = [jnp.sum(~mine if live is None else ~mine & live)]
        live = mine if live is None else mine & live
    if live is not None:
        flat = jnp.where(live, flat, E)                     # sorts past all
    order = jnp.argsort(flat, stable=True)                  # rows by expert
    sizes = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
    seg = _segment(T * k, E / (num_experts or E))

    def product(rows, seg_sizes):
        hidden = swiglu(grouped_matmul(rows, w_gate_up, seg_sizes,
                                       interpret=interpret))
        return grouped_matmul(hidden, w_down, seg_sizes, interpret=interpret)

    if seg == T * k:
        back = product(x[order // k], sizes)[jnp.argsort(order)]
        out = jnp.sum(back.reshape(T, k, -1).astype(jnp.float32)
                      * weights[..., None], axis=1)
    else:
        # the held rows are the first sum(sizes) of the sorted rows: only
        # the segments that hold some of them run
        ends = jnp.cumsum(sizes)
        starts, w_flat = ends - sizes, weights.reshape(-1)

        def one(s, out):
            lo = s * seg
            mine = jax.lax.dynamic_slice(order, (lo,), (seg,))
            got = product(x[mine // k], jnp.clip(ends, lo, lo + seg)
                          - jnp.clip(starts, lo, lo + seg))
            return out.at[mine // k].add(got.astype(jnp.float32)
                                         * w_flat[mine][:, None])

        out = jax.lax.fori_loop(0, (ends[-1] + seg - 1) // seg, one,
                                jnp.zeros(x.shape, jnp.float32))
    if valid is not None:
        out = jnp.where(valid[:, None], out, 0.0)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes),
                       *elsewhere]).astype(jnp.float32)
    return out.astype(x.dtype), stats


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class DroplessMoE(Layer):
    """The expert feed-forward of one decoder layer: ``num_experts`` routed
    SwiGLU experts of ``d_hidden``, ``top_k`` a token, and one shared SwiGLU
    of ``num_shared * d_hidden`` that every token passes.  ``held = (first,
    count)``: the experts whose weights live here (default: all); the router
    keeps ``num_experts`` outputs and the layer returns the held experts'
    part of the routed sum (the shared expert, which every chip of the
    deployment computes alike, is added whole)."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int, num_shared: int = 0, scale: float = 1.0,
                 norm_topk: bool = True, dtype=None,
                 initializer_range: float = 0.02, held=None):
        super().__init__()
        self.top_k, self.scale, self.norm_topk = top_k, scale, norm_topk
        self.num_experts = num_experts
        self.first, n_held = held if held is not None else (0, num_experts)
        if not 0 <= self.first <= self.first + n_held <= num_experts:
            raise ValueError(f"held={held} of {num_experts} experts")
        init = Normal(0.0, initializer_range)
        # router and selection bias stay float32 (routing numerics)
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], dtype="float32", default_initializer=init)
        self.e_score_correction_bias = self.create_parameter(
            [num_experts], dtype="float32", default_initializer=Constant(0.0))
        self.w_gate_up = self.create_parameter(
            [n_held, d_model, 2 * d_hidden], dtype=dtype,
            default_initializer=init)
        self.w_down = self.create_parameter(
            [n_held, d_hidden, d_model], dtype=dtype,
            default_initializer=init)
        self.num_shared = num_shared
        if num_shared:
            self.shared_gate_up = self.create_parameter(
                [d_model, 2 * num_shared * d_hidden], dtype=dtype,
                default_initializer=init)
            self.shared_down = self.create_parameter(
                [num_shared * d_hidden, d_model], dtype=dtype,
                default_initializer=init)

    def forward(self, x, valid=None):
        """``x [..., d_model]`` -> ``(y, stats)``; see
        :func:`dropless_experts` for ``valid`` (``x``'s leading shape) and
        ``stats``."""
        h = _raw(x)
        tokens = h.reshape(-1, h.shape[-1])
        if valid is not None:
            valid = _raw(valid).reshape(-1)
        with scope("moe.route", h):
            idx, w = sigmoid_topk_route(
                tokens, _raw(self.gate_weight),
                _raw(self.e_score_correction_bias), self.top_k, self.scale,
                self.norm_topk)
        with scope("moe.experts", h):
            y, stats = dropless_experts(
                tokens, idx, w, _raw(self.w_gate_up), _raw(self.w_down),
                valid=valid, first=self.first, num_experts=self.num_experts)
            if self.num_shared:
                y = y + swiglu(tokens @ _raw(self.shared_gate_up).astype(
                    tokens.dtype)) @ _raw(self.shared_down).astype(
                        tokens.dtype)
        return Tensor(y.reshape(h.shape)), stats
