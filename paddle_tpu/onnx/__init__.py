"""``paddle.onnx`` — export models to ONNX.

The reference hook (``python/paddle/onnx/export.py``) shells out to the
paddle2onnx wheel; this environment has no onnx package at all, so the
exporter here is self-contained: the layer's forward is traced to a jaxpr
(the framework's single IR), each primitive is mapped to an ONNX operator,
and the ModelProto is serialized with the wire-format writer in
``onnx/proto.py``.  ``load_graph`` reads a model back (tests round-trip and
numerically re-execute exported graphs against the live model).

Supported primitive set covers the inference graphs of the nn layer library
(matmul/conv/normalizations/activations/softmax/pooling reductions);
training-only or TPU-kernel ops (Pallas calls, collectives) are rejected
with a clear error — export the plain XLA path (``use_flash_attention=False``)
for interchange.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import proto

__all__ = ["export", "load_graph"]


def _np_of(x):
    import jax

    return np.asarray(jax.device_get(x))


class _Converter:
    def __init__(self):
        self.nodes: List[bytes] = []
        self.initializers: List[bytes] = []
        self.names: Dict = {}
        self._ctr = itertools.count()
        self.has_baked_reshape = False  # Reshape targets are traced constants

    def fresh(self, prefix: str = "v") -> str:
        return f"{prefix}{next(self._ctr)}"

    def add_init(self, arr: np.ndarray, prefix: str = "w") -> str:
        nm = self.fresh(prefix)
        self.initializers.append(proto.tensor_proto(nm, np.asarray(arr)))
        return nm

    def name_of(self, var) -> str:
        from jax.extend import core

        if isinstance(var, core.Literal):
            return self.add_init(np.asarray(var.val), "lit")
        return self.names[var]

    def emit(self, op: str, ins: Sequence[str], n_out: int = 1, **attrs) -> List[str]:
        outs = [self.fresh() for _ in range(n_out)]
        self.nodes.append(proto.node(op, ins, outs, name=self.fresh("n"),
                                     attrs=attrs or None))
        if op == "Reshape":
            # every emitted Reshape target is a traced-shape constant; the
            # dynamic-axes warning in export() keys off this
            self.has_baked_reshape = True
        return outs

    # -- per-equation dispatch ------------------------------------------------

    def convert_eqn(self, eqn):
        prim = eqn.primitive.name
        handler = getattr(self, f"_op_{prim.replace('-', '_')}", None)
        if handler is None:
            raise NotImplementedError(
                f"ONNX export: primitive {prim!r} is not supported (export the "
                "plain XLA path: use_flash_attention=False, eval mode)")
        handler(eqn)

    def _bind1(self, eqn, op, **attrs):
        ins = [self.name_of(v) for v in eqn.invars]
        (out,) = self.emit(op, ins, **attrs)
        self.names[eqn.outvars[0]] = out

    def _op_add(self, eqn):
        self._bind1(eqn, "Add")

    def _op_sub(self, eqn):
        self._bind1(eqn, "Sub")

    def _op_mul(self, eqn):
        self._bind1(eqn, "Mul")

    def _op_div(self, eqn):
        self._bind1(eqn, "Div")

    def _op_max(self, eqn):
        self._bind1(eqn, "Max")

    def _op_min(self, eqn):
        self._bind1(eqn, "Min")

    def _op_pow(self, eqn):
        self._bind1(eqn, "Pow")

    def _op_neg(self, eqn):
        self._bind1(eqn, "Neg")

    def _op_exp(self, eqn):
        self._bind1(eqn, "Exp")

    def _op_log(self, eqn):
        self._bind1(eqn, "Log")

    def _op_tanh(self, eqn):
        self._bind1(eqn, "Tanh")

    def _op_logistic(self, eqn):
        self._bind1(eqn, "Sigmoid")

    def _op_sqrt(self, eqn):
        self._bind1(eqn, "Sqrt")

    def _op_abs(self, eqn):
        self._bind1(eqn, "Abs")

    def _op_erf(self, eqn):
        self._bind1(eqn, "Erf")

    def _op_erfc(self, eqn):
        # erfc(x) = 1 - erf(x)
        x = self.name_of(eqn.invars[0])
        (e,) = self.emit("Erf", [x])
        one = self.add_init(np.asarray(1.0, np.dtype(eqn.invars[0].aval.dtype)))
        (out,) = self.emit("Sub", [one, e])
        self.names[eqn.outvars[0]] = out

    def _op_sign(self, eqn):
        self._bind1(eqn, "Sign")

    def _op_floor(self, eqn):
        self._bind1(eqn, "Floor")

    def _op_ceil(self, eqn):
        self._bind1(eqn, "Ceil")

    def _op_is_finite(self, eqn):
        # Not(Or(IsNaN, IsInf))
        x = self.name_of(eqn.invars[0])
        (nan_,) = self.emit("IsNaN", [x])
        (inf_,) = self.emit("IsInf", [x])
        (or_,) = self.emit("Or", [nan_, inf_])
        (out,) = self.emit("Not", [or_])
        self.names[eqn.outvars[0]] = out

    def _op_rsqrt(self, eqn):
        x = self.name_of(eqn.invars[0])
        (s,) = self.emit("Sqrt", [x])
        (out,) = self.emit("Reciprocal", [s])
        self.names[eqn.outvars[0]] = out

    def _op_integer_pow(self, eqn):
        x = self.name_of(eqn.invars[0])
        y = int(eqn.params["y"])
        dt = np.dtype(eqn.invars[0].aval.dtype)
        e = self.add_init(np.asarray(y, dt if dt.kind == "f" else np.int64))
        (out,) = self.emit("Pow", [x, e])
        self.names[eqn.outvars[0]] = out

    def _op_stop_gradient(self, eqn):
        self._bind1(eqn, "Identity")

    def _op_copy(self, eqn):
        self._bind1(eqn, "Identity")

    def _op_convert_element_type(self, eqn):
        to = proto.onnx_dtype(eqn.params["new_dtype"])
        self._bind1(eqn, "Cast", to=to)

    def _op_transpose(self, eqn):
        self._bind1(eqn, "Transpose", perm=list(map(int, eqn.params["permutation"])))

    def _op_reshape(self, eqn):
        x = self.name_of(eqn.invars[0])
        shp = self.add_init(np.asarray(eqn.params["new_sizes"], np.int64), "shape")
        (out,) = self.emit("Reshape", [x, shp])
        self.names[eqn.outvars[0]] = out
        self.has_baked_reshape = True

    def _op_squeeze(self, eqn):
        x = self.name_of(eqn.invars[0])
        shp = self.add_init(
            np.asarray(eqn.outvars[0].aval.shape, np.int64), "shape")
        (out,) = self.emit("Reshape", [x, shp])
        self.names[eqn.outvars[0]] = out

    def _op_broadcast_in_dim(self, eqn):
        x = self.name_of(eqn.invars[0])
        out_shape = list(map(int, eqn.params["shape"]))
        bdims = list(map(int, eqn.params["broadcast_dimensions"]))
        # Reshape to rank(out) with 1s off the broadcast dims, then Expand
        mid = [1] * len(out_shape)
        for src_axis, dst_axis in enumerate(bdims):
            mid[dst_axis] = int(eqn.invars[0].aval.shape[src_axis])
        shp_mid = self.add_init(np.asarray(mid, np.int64), "shape")
        (r,) = self.emit("Reshape", [x, shp_mid])
        shp_out = self.add_init(np.asarray(out_shape, np.int64), "shape")
        (out,) = self.emit("Expand", [r, shp_out])
        self.names[eqn.outvars[0]] = out

    def _op_concatenate(self, eqn):
        ins = [self.name_of(v) for v in eqn.invars]
        (out,) = self.emit("Concat", ins, axis=int(eqn.params["dimension"]))
        self.names[eqn.outvars[0]] = out

    def _op_slice(self, eqn):
        x = self.name_of(eqn.invars[0])
        starts = np.asarray(eqn.params["start_indices"], np.int64)
        ends = np.asarray(eqn.params["limit_indices"], np.int64)
        strides = eqn.params.get("strides")
        axes = np.arange(len(starts), dtype=np.int64)
        ins = [x, self.add_init(starts, "starts"), self.add_init(ends, "ends"),
               self.add_init(axes, "axes")]
        if strides is not None:
            ins.append(self.add_init(np.asarray(strides, np.int64), "steps"))
        (out,) = self.emit("Slice", ins)
        self.names[eqn.outvars[0]] = out

    def _op_select_n(self, eqn):
        if len(eqn.invars) != 3 or eqn.invars[0].aval.dtype != np.bool_:
            raise NotImplementedError(
                "ONNX export: select_n supported only with a boolean predicate "
                "and two cases (jnp.where); integer/multi-way select has no "
                "single ONNX op")
        pred, x0, x1 = (self.name_of(v) for v in eqn.invars)
        # select_n(c, x_false, x_true); Where(cond, A, B) = A where cond
        (out,) = self.emit("Where", [pred, x1, x0])
        self.names[eqn.outvars[0]] = out

    def _op_split(self, eqn):
        x = self.name_of(eqn.invars[0])
        sizes = list(map(int, eqn.params["sizes"]))
        axis = int(eqn.params["axis"])
        outs = self.emit("Split", [x, self.add_init(
            np.asarray(sizes, np.int64), "split")], n_out=len(sizes),
            axis=axis)
        for ov, nm in zip(eqn.outvars, outs):
            self.names[ov] = nm

    def _op_square(self, eqn):
        x = self.name_of(eqn.invars[0])
        (out,) = self.emit("Mul", [x, x])
        self.names[eqn.outvars[0]] = out

    def _op_sin(self, eqn):
        self._bind1(eqn, "Sin")

    def _op_cos(self, eqn):
        self._bind1(eqn, "Cos")

    def _op_iota(self, eqn):
        # static shape at trace time -> a baked constant (np.arange broadcast)
        p = eqn.params
        shape = tuple(map(int, p["shape"]))
        dim = int(p["dimension"])
        ar = np.arange(shape[dim], dtype=np.dtype(p["dtype"]))
        view = [1] * len(shape)
        view[dim] = shape[dim]
        self.names[eqn.outvars[0]] = self.add_init(
            np.broadcast_to(ar.reshape(view), shape).copy(), "iota")
        self.has_baked_reshape = True  # traced-shape constant (same hazard)

    def _op_rev(self, eqn):
        x = self.name_of(eqn.invars[0])
        dims = list(map(int, eqn.params["dimensions"]))
        shape = eqn.invars[0].aval.shape
        ins = [x,
               self.add_init(np.asarray([shape[d] - 1 for d in dims], np.int64), "starts"),
               self.add_init(np.asarray([np.iinfo(np.int64).min] * len(dims), np.int64), "ends"),
               self.add_init(np.asarray(dims, np.int64), "axes"),
               self.add_init(np.asarray([-1] * len(dims), np.int64), "steps")]
        (out,) = self.emit("Slice", ins)
        self.names[eqn.outvars[0]] = out

    def _op_pad(self, eqn):
        p = eqn.params["padding_config"]
        if any(interior for _, _, interior in p):
            raise NotImplementedError(
                "ONNX export: interior (dilation) padding has no Pad mapping")
        x = self.name_of(eqn.invars[0])
        val = self.name_of(eqn.invars[1])
        pads = [int(lo) for lo, _, _ in p] + [int(hi) for _, hi, _ in p]
        (out,) = self.emit("Pad", [x, self.add_init(np.asarray(pads, np.int64), "pads"), val])
        self.names[eqn.outvars[0]] = out

    def _op_dynamic_slice(self, eqn):
        # constant start indices (the common traced case) -> Slice
        from jax.extend import core

        starts = []
        for v in eqn.invars[1:]:
            if not isinstance(v, core.Literal):
                raise NotImplementedError(
                    "ONNX export: dynamic_slice with non-constant starts")
            starts.append(int(v.val))
        sizes = list(map(int, eqn.params["slice_sizes"]))
        op_shape = eqn.invars[0].aval.shape
        # jax clamps out-of-bounds starts to dim - size; bake the same
        starts = [max(0, min(s, int(dim) - z))
                  for s, z, dim in zip(starts, sizes, op_shape)]
        x = self.name_of(eqn.invars[0])
        ins = [x, self.add_init(np.asarray(starts, np.int64), "starts"),
               self.add_init(np.asarray([s + z for s, z in zip(starts, sizes)], np.int64), "ends"),
               self.add_init(np.arange(len(starts), dtype=np.int64), "axes")]
        (out,) = self.emit("Slice", ins)
        self.names[eqn.outvars[0]] = out

    def _op_gather(self, eqn):
        """Two common patterns: embedding-style lookup -> Gather(axis);
        take_along_axis -> GatherElements."""
        d = eqn.params["dimension_numbers"]
        operand, indices = eqn.invars
        op_shape = tuple(operand.aval.shape)
        idx_shape = tuple(indices.aval.shape)
        slice_sizes = tuple(map(int, eqn.params["slice_sizes"]))
        x = self.name_of(operand)
        idx = self.name_of(indices)
        start_dims = tuple(map(int, d.start_index_map))
        # pattern A: single indexed axis, full slices elsewhere -> Gather
        # jnp.take(x, idx, axis=ax) == ONNX Gather(axis=ax): output is
        # operand[:ax] + idx_batch + operand[ax+1:], so the offset dims must
        # sit at exactly the non-index positions of that layout
        ax0 = start_dims[0] if start_dims else 0
        nb = len(idx_shape) - 1
        canon_off = tuple(i for i in range(len(op_shape) - 1 + nb)
                          if not (ax0 <= i < ax0 + nb))
        if (len(start_dims) == 1 and d.collapsed_slice_dims == start_dims
                and not d.operand_batching_dims
                and tuple(d.offset_dims) == canon_off
                and all(slice_sizes[i] == op_shape[i]
                        for i in range(len(op_shape)) if i != start_dims[0])
                and slice_sizes[start_dims[0]] == 1
                and idx_shape and idx_shape[-1] == 1):
            (flat_idx,) = self.emit("Reshape", [idx, self.add_init(
                np.asarray(idx_shape[:-1] or (1,), np.int64), "shape")])
            (out,) = self.emit("Gather", [x, flat_idx], axis=int(start_dims[0]))
            # jax lays out batch dims then offset dims; for axis-0 lookup with
            # leading batch dims that matches Gather's output directly
            out_shape = tuple(eqn.outvars[0].aval.shape)
            (out,) = self.emit("Reshape", [out, self.add_init(
                np.asarray(out_shape, np.int64), "shape")])
            self.names[eqn.outvars[0]] = out
            return
        # pattern B: take_along_axis (one indexed dim, batch dims elsewhere,
        # index rank == operand rank with trailing 1) -> GatherElements
        if (len(start_dims) == 1 and len(idx_shape) == len(op_shape) + 1
                and idx_shape[-1] == 1 and not d.offset_dims
                and all(s == 1 for s in slice_sizes)
                and tuple(eqn.outvars[0].aval.shape) == idx_shape[:-1]):
            ax = int(start_dims[0])
            (flat_idx,) = self.emit("Reshape", [idx, self.add_init(
                np.asarray(idx_shape[:-1], np.int64), "shape")])
            (out,) = self.emit("GatherElements", [x, flat_idx], axis=ax)
            out_shape = tuple(eqn.outvars[0].aval.shape)
            (out,) = self.emit("Reshape", [out, self.add_init(
                np.asarray(out_shape, np.int64), "shape")])
            self.names[eqn.outvars[0]] = out
            return
        # pattern C: dynamic_slice as gather (scalar start vector, no
        # collapsed dims, all dims offset) -> Slice with runtime starts
        if (not d.collapsed_slice_dims and len(idx_shape) == 1
                and idx_shape[0] == len(start_dims)
                and tuple(d.offset_dims) == tuple(range(len(op_shape)))):
            (idx64,) = self.emit("Cast", [idx], to=proto.onnx_dtype(np.int64))
            pieces = []
            for dim in range(len(op_shape)):
                if dim in start_dims:
                    j = start_dims.index(dim)
                    (piece,) = self.emit("Slice", [
                        idx64,
                        self.add_init(np.asarray([j], np.int64), "starts"),
                        self.add_init(np.asarray([j + 1], np.int64), "ends"),
                        self.add_init(np.asarray([0], np.int64), "axes")])
                    pieces.append(piece)
                else:
                    pieces.append(self.add_init(np.asarray([0], np.int64), "z"))
            (starts,) = self.emit("Concat", pieces, axis=0)
            sizes = self.add_init(np.asarray(slice_sizes, np.int64), "sizes")
            (ends,) = self.emit("Add", [starts, sizes])
            (out,) = self.emit("Slice", [
                x, starts, ends,
                self.add_init(np.arange(len(op_shape), dtype=np.int64), "axes")])
            self.names[eqn.outvars[0]] = out
            return
        raise NotImplementedError(
            "ONNX export: gather pattern beyond embedding lookup / "
            "take_along_axis / dynamic_slice is unsupported")

    def _op_reduce_window_max(self, eqn):
        self._pool(eqn, "MaxPool")

    def _op_reduce_window_sum(self, eqn):
        # jax avg_pool = reduce_window_sum / count; export the sum as
        # AveragePool(count_include_pad) * window_size
        outs = self._pool(eqn, "AveragePool", bind=False)
        p = eqn.params
        win = int(np.prod([w for w in p["window_dimensions"]]))
        dt = np.dtype(eqn.outvars[0].aval.dtype)
        c = self.add_init(np.asarray(win, dt))
        (out,) = self.emit("Mul", [outs[0], c])
        self.names[eqn.outvars[0]] = out

    def _pool(self, eqn, op, bind=True):
        p = eqn.params
        wd = list(map(int, p["window_dimensions"]))
        ws = list(map(int, p["window_strides"]))
        pads_pairs = list(p["padding"])
        if wd[0] != 1 or wd[1] != 1 or ws[0] != 1 or ws[1] != 1:
            raise NotImplementedError(
                "ONNX export: pooling windows over batch/channel dims")
        if any(d != 1 for d in p.get("window_dilation", []) or []):
            raise NotImplementedError("ONNX export: dilated pooling")
        if any(d != 1 for d in p.get("base_dilation", []) or []):
            raise NotImplementedError("ONNX export: base-dilated pooling")
        kwargs = dict(
            kernel_shape=wd[2:],
            strides=ws[2:],
            pads=[int(lo) for lo, _ in pads_pairs[2:]] +
                 [int(hi) for _, hi in pads_pairs[2:]])
        if op == "AveragePool":
            kwargs["count_include_pad"] = 1
        x = self.name_of(eqn.invars[0])
        outs = self.emit(op, [x], **kwargs)
        if bind:
            self.names[eqn.outvars[0]] = outs[0]
        return outs

    def _op_reduce_sum(self, eqn):
        x = self.name_of(eqn.invars[0])
        axes = self.add_init(np.asarray(eqn.params["axes"], np.int64), "axes")
        (out,) = self.emit("ReduceSum", [x, axes], keepdims=0)
        self.names[eqn.outvars[0]] = out

    def _op_reduce_max(self, eqn):
        self._bind1(eqn, "ReduceMax", axes=list(map(int, eqn.params["axes"])),
                    keepdims=0)

    def _op_reduce_min(self, eqn):
        self._bind1(eqn, "ReduceMin", axes=list(map(int, eqn.params["axes"])),
                    keepdims=0)

    def _op_dot_general(self, eqn):
        """Any dot_general: canonicalize both sides to [batch..., M, K] /
        [batch..., K, N] with Transpose+Reshape, one MatMul, reshape to the
        jax output layout (batch + lhs_free + rhs_free)."""
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars
        ls, rs = tuple(lhs.aval.shape), tuple(rhs.aval.shape)
        a, b = self.name_of(lhs), self.name_of(rhs)
        l_rank = len(ls)
        # fast path: already a plain (possibly stacked) matmul — both sides
        # must be exactly [batch..., M, K] / [batch..., K, N] (extra free
        # dims would hit ONNX MatMul's right-aligned broadcasting, which
        # differs from jax's batch+free layout)
        if (list(lb) == list(rb) == list(range(len(lb))) and
                len(lc) == 1 and len(rc) == 1 and
                lc[0] == l_rank - 1 and rc[0] == len(lb) and
                len(ls) == len(lb) + 2 and len(rs) == len(lb) + 2):
            (out,) = self.emit("MatMul", [a, b])
            self.names[eqn.outvars[0]] = out
            return
        lfree = [d for d in range(len(ls)) if d not in lb and d not in lc]
        rfree = [d for d in range(len(rs)) if d not in rb and d not in rc]
        perm_l = list(lb) + lfree + list(lc)
        perm_r = list(rb) + list(rc) + rfree
        batch = [ls[d] for d in lb]
        m = int(np.prod([ls[d] for d in lfree])) if lfree else 1
        k = int(np.prod([ls[d] for d in lc])) if lc else 1
        n = int(np.prod([rs[d] for d in rfree])) if rfree else 1
        (ta,) = self.emit("Transpose", [a], perm=perm_l)
        (tb,) = self.emit("Transpose", [b], perm=perm_r)
        (ra,) = self.emit("Reshape", [ta, self.add_init(
            np.asarray(batch + [m, k], np.int64), "shape")])
        (rb_,) = self.emit("Reshape", [tb, self.add_init(
            np.asarray(batch + [k, n], np.int64), "shape")])
        (mm,) = self.emit("MatMul", [ra, rb_])
        out_shape = tuple(eqn.outvars[0].aval.shape)
        (out,) = self.emit("Reshape", [mm, self.add_init(
            np.asarray(out_shape, np.int64), "shape")])
        self.names[eqn.outvars[0]] = out

    def _op_conv_general_dilated(self, eqn):
        p = eqn.params
        dn = p["dimension_numbers"]
        spec = (dn.lhs_spec, dn.rhs_spec, dn.out_spec)
        ndim = len(p["window_strides"]) + 2
        nchw = (tuple(range(ndim)),) * 3  # NCHW / OIHW / NCHW
        if spec != nchw:
            raise NotImplementedError(
                "ONNX export: conv supported only in NCHW/OIHW layout")
        x, w = (self.name_of(v) for v in eqn.invars)
        if any(d != 1 for d in p["lhs_dilation"]):
            # transposed conv: jax zero-stuffs the input then runs a plain
            # conv.  Translate mechanically — Reshape/Pad/Reshape/Slice stuff
            # zeros between elements, then Conv — exact for any kernel
            x = self._zero_stuff(x, eqn.invars[0].aval.shape,
                                 list(map(int, p["lhs_dilation"])),
                                 np.dtype(eqn.invars[0].aval.dtype))
        pads_pairs = list(p["padding"])
        pads = [int(lo) for lo, _ in pads_pairs] + [int(hi) for _, hi in pads_pairs]
        (out,) = self.emit(
            "Conv", [x, w],
            strides=list(map(int, p["window_strides"])),
            pads=pads,
            dilations=list(map(int, p["rhs_dilation"])),
            group=int(p["feature_group_count"]))
        self.names[eqn.outvars[0]] = out

    def _zero_stuff(self, x: str, shape, dilation, dt=np.dtype("float32")):
        """Insert ``d-1`` zeros between spatial elements (lhs_dilation):
        [B,C,H,W] -> [B,C,H,1,W,1] -> Pad trailing unit axes to d -> reshape
        [B,C,H*d,W*d] -> Slice to (H-1)*d+1."""
        b, c = int(shape[0]), int(shape[1])
        spatial = [int(s) for s in shape[2:]]
        mid = [b, c]
        for s in spatial:
            mid += [s, 1]
        (r,) = self.emit("Reshape", [x, self.add_init(
            np.asarray(mid, np.int64), "shape")])
        pads = [0] * len(mid) + [0] * len(mid)
        for i, d in enumerate(dilation):
            pads[len(mid) + 3 + 2 * i] = d - 1      # end-pad each unit axis
        (padded,) = self.emit("Pad", [
            r, self.add_init(np.asarray(pads, np.int64), "pads"),
            self.add_init(np.zeros((), dt))])
        stuffed = [b, c] + [s * d for s, d in zip(spatial, dilation)]
        (r2,) = self.emit("Reshape", [padded, self.add_init(
            np.asarray(stuffed, np.int64), "shape")])
        axes = list(range(2, 2 + len(spatial)))
        (out,) = self.emit("Slice", [
            r2,
            self.add_init(np.zeros(len(spatial), np.int64), "starts"),
            self.add_init(np.asarray([(s - 1) * d + 1 for s, d in
                                      zip(spatial, dilation)], np.int64), "ends"),
            self.add_init(np.asarray(axes, np.int64), "axes")])
        return out

    # comparison ops (emit bool outputs)
    def _op_gt(self, eqn):
        self._bind1(eqn, "Greater")

    def _op_lt(self, eqn):
        self._bind1(eqn, "Less")

    def _op_ge(self, eqn):
        self._bind1(eqn, "GreaterOrEqual")

    def _op_le(self, eqn):
        self._bind1(eqn, "LessOrEqual")

    def _op_eq(self, eqn):
        self._bind1(eqn, "Equal")

    # call primitives: inline the inner jaxpr with shared naming
    def _inline(self, eqn, closed):
        inner = closed.jaxpr
        for outer, innerv in zip(eqn.invars, inner.invars):
            self.names[innerv] = self.name_of(outer)
        for cv, cval in zip(inner.constvars, closed.consts):
            self.names[cv] = self.add_init(_np_of(cval), "c")
        self.convert_jaxpr_body(inner)
        from jax.extend import core

        for outer, innerv in zip(eqn.outvars, inner.outvars):
            if isinstance(innerv, core.Literal):
                self.names[outer] = self.add_init(np.asarray(innerv.val), "lit")
            else:
                self.names[outer] = self.names[innerv]

    def _op_pjit(self, eqn):
        self._inline(eqn, eqn.params["jaxpr"])

    _op_jit = _op_pjit  # newer jax names the pjit primitive 'jit'

    def _op_closed_call(self, eqn):
        self._inline(eqn, eqn.params["call_jaxpr"])

    def _op_custom_jvp_call(self, eqn):
        self._inline(eqn, eqn.params["call_jaxpr"])

    def _op_custom_vjp_call(self, eqn):
        self._inline(eqn, eqn.params["call_jaxpr"])

    def _op_remat(self, eqn):
        from jax.extend import core

        closed = core.ClosedJaxpr(eqn.params["jaxpr"], ())
        self._inline(eqn, closed)

    _op_checkpoint = _op_remat

    def _op_scan(self, eqn):
        """lax.scan (RNN layers): UNROLLED — the trip count is static at
        trace time, so each step inlines the body jaxpr on a Slice of the
        stacked inputs; ys re-stack with Concat.  (The alternative — ONNX
        Loop — trades graph size for a subgraph encoding few runtimes
        optimize; unrolling keeps the exporter self-contained.)"""
        from jax.extend import core

        p = eqn.params
        closed = p["jaxpr"]
        inner = closed.jaxpr
        n_c, n_carry = int(p["num_consts"]), int(p["num_carry"])
        length, reverse = int(p["length"]), bool(p["reverse"])
        if length == 0:
            raise NotImplementedError("ONNX export: zero-length scan")
        const_names = [self.name_of(v) for v in eqn.invars[:n_c]]
        carry_names = [self.name_of(v) for v in eqn.invars[n_c:n_c + n_carry]]
        xs_vars = eqn.invars[n_c + n_carry:]
        xs_names = [self.name_of(v) for v in xs_vars]   # hoisted: one
        xs_shapes = [tuple(v.aval.shape) for v in xs_vars]  # init per Literal
        n_ys = len(eqn.outvars) - n_carry
        ys_steps: List[List[str]] = [[None] * length for _ in range(n_ys)]

        const_inits = [self.add_init(_np_of(cv), "c") for cv in closed.consts]
        axis0 = self.add_init(np.asarray([0], np.int64), "axes")
        order = range(length - 1, -1, -1) if reverse else range(length)
        for t in order:
            x_names = []
            for xs_nm, shape in zip(xs_names, xs_shapes):
                ins = [xs_nm,
                       self.add_init(np.asarray([t], np.int64), "starts"),
                       self.add_init(np.asarray([t + 1], np.int64), "ends"),
                       axis0]
                (sl,) = self.emit("Slice", ins)
                (xt,) = self.emit("Reshape", [sl, self.add_init(
                    np.asarray(shape[1:] or (1,), np.int64), "shape")])
                x_names.append(xt)
            for iv, nm in zip(inner.invars,
                              const_names + carry_names + x_names):
                self.names[iv] = nm
            for cv, nm in zip(inner.constvars, const_inits):
                self.names[cv] = nm
            self.convert_jaxpr_body(inner)
            step_out = []
            for ov in inner.outvars:
                if isinstance(ov, core.Literal):
                    step_out.append(self.add_init(np.asarray(ov.val), "lit"))
                else:
                    step_out.append(self.names[ov])
            carry_names = step_out[:n_carry]
            for i, y in enumerate(step_out[n_carry:]):
                y_shape = tuple(eqn.outvars[n_carry + i].aval.shape)
                (yk,) = self.emit("Reshape", [y, self.add_init(
                    np.asarray((1,) + y_shape[1:], np.int64), "shape")])
                ys_steps[i][t] = yk
        for ov, nm in zip(eqn.outvars[:n_carry], carry_names):
            self.names[ov] = nm
        for i, ov in enumerate(eqn.outvars[n_carry:]):
            if length == 1:
                self.names[ov] = ys_steps[i][0]
            else:
                (out,) = self.emit("Concat", ys_steps[i], axis=0)
                self.names[ov] = out

    def convert_jaxpr_body(self, jaxpr):
        for eqn in jaxpr.eqns:
            self.convert_eqn(eqn)


def export(layer, path: str, input_spec=None, opset_version: int = 13,
           **configs) -> str:
    """Trace ``layer.forward`` and write ``<path>.onnx``.

    ``input_spec``: list of example inputs — Tensors, numpy arrays, or
    ``static.InputSpec``-like objects with ``.shape``/``.dtype``.  Returns the
    written file path.  (Reference: ``python/paddle/onnx/export.py`` — same
    call shape, but self-contained instead of delegating to paddle2onnx.)
    """
    import jax

    from ..framework.tensor import Tensor
    from ..jit import functional_call

    if input_spec is None:
        raise ValueError("onnx.export needs input_spec (example inputs)")
    if not 13 <= int(opset_version) <= 17:
        raise ValueError(
            f"opset_version={opset_version} unsupported: the emitted op set "
            "follows opset 13 semantics (ReduceSum axes-as-input, "
            "ReduceMax/Min axes-as-attribute), valid through opset 17")

    examples = []
    dynamic_axes: List[List[int]] = []  # per input: axes traced at 1 but dynamic
    for spec in input_spec:
        if isinstance(spec, Tensor):
            examples.append(spec._data)
            dynamic_axes.append([])
        elif hasattr(spec, "shape") and hasattr(spec, "dtype") and not isinstance(
                spec, np.ndarray):
            # static.InputSpec normalizes None dims to -1; both mean "dynamic":
            # trace with 1 and declare a symbolic dim_param on the graph input
            dims, dyn = [], []
            for ax, d in enumerate(spec.shape):
                if d is None or int(d) < 0:
                    dims.append(1)
                    dyn.append(ax)
                else:
                    dims.append(int(d))
            examples.append(np.zeros(dims, np.dtype(str(spec.dtype))))
            dynamic_axes.append(dyn)
        else:
            examples.append(np.asarray(spec))
            dynamic_axes.append([])

    params = {n: p._data for n, p in layer.named_parameters()}
    buffers = {n: b._data for n, b in layer.named_buffers()}

    def fn(*xs):
        out = functional_call(layer, params, buffers, *xs)
        return out

    closed = jax.make_jaxpr(fn)(*examples)
    conv = _Converter()
    jaxpr = closed.jaxpr

    input_names, input_vis = [], []
    for idx, (var, ex) in enumerate(zip(jaxpr.invars, examples)):
        nm = conv.fresh("input_")
        conv.names[var] = nm
        input_names.append(nm)
        dims = list(var.aval.shape)
        for ax in dynamic_axes[idx]:
            dims[ax] = f"{nm}_dim{ax}"  # symbolic dim_param
        input_vis.append(proto.value_info(
            nm, proto.onnx_dtype(var.aval.dtype), dims))
    for cv, cval in zip(jaxpr.constvars, closed.consts):
        conv.names[cv] = conv.add_init(_np_of(cval), "p")

    conv.convert_jaxpr_body(jaxpr)
    if conv.has_baked_reshape and any(dynamic_axes):
        import warnings

        warnings.warn(
            "onnx.export: the graph contains Reshape nodes whose target "
            "shapes were baked at trace time; the declared dynamic dims "
            "(dim_param) will NOT generalize through them — run with the "
            "traced sizes, or avoid reshapes over dynamic axes",
            stacklevel=2)

    output_vis = []
    out_names = []
    for var in jaxpr.outvars:
        nm = conv.name_of(var)
        out_names.append(nm)
        output_vis.append(proto.value_info(
            nm, proto.onnx_dtype(var.aval.dtype), var.aval.shape))

    g = proto.graph(conv.nodes, type(layer).__name__, input_vis, output_vis,
                    conv.initializers)
    payload = proto.model(g, opset=opset_version)
    out_path = path if path.endswith(".onnx") else path + ".onnx"
    with open(out_path, "wb") as f:
        f.write(payload)
    return out_path


def load_graph(path: str) -> Dict:
    """Parse an exported .onnx file back into a dict (see proto.read_model)."""
    with open(path, "rb") as f:
        return proto.read_model(f.read())
