"""``paddle_tpu.jit`` — dynamic-to-static compilation.

Reference: ``python/paddle/jit/`` (35k LoC: AST transpiler + SOT bytecode
tracer + partial programs + CINN hook).  The TPU-native replacement collapses
all of it into ``jax.jit`` tracing:

- the eager Tensor ops are jnp calls, so a Layer's ``forward`` *is already
  traceable* — no bytecode interpretation or AST rewriting is needed;
- ``to_static(layer)`` = extract parameters as inputs, trace once per input
  signature, cache the compiled executable (the role of their guard system is
  played by jax.jit's shape/dtype cache key);
- the fusion compiler (CINN's job) is XLA itself;
- ``TrainStep`` compiles forward+backward+optimizer into ONE XLA program via
  ``jax.value_and_grad`` — the counterpart of the reference's fwd/bwd partial
  programs (``pir_partial_program.py``), and the performance path on TPU.
"""

from __future__ import annotations

import contextlib
import functools
import re
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..framework import random as rnd
from ..framework.autograd import no_grad
from ..framework.dispatch import unwrap, wrap
from ..framework.tensor import Parameter, Tensor

__all__ = ["to_static", "not_to_static", "TrainStep", "functional_call", "ignore_module",
           "enable_to_static", "set_verbosity", "set_code_level", "TranslatedLayer",
           "save", "load", "bucketed", "capture"]

from .subgraph import capture  # noqa: E402  (SOT-equivalent fragment capture)


@contextlib.contextmanager
def _bind_state(layer, param_values: Dict[str, Any], buffer_values: Dict[str, Any]):
    """Temporarily swap parameter/buffer storage to (traced) arrays."""
    named_p = dict(layer.named_parameters())
    named_b = dict(layer.named_buffers())
    old_p = {n: p._data for n, p in named_p.items()}
    old_b = {n: b._data for n, b in named_b.items()}
    try:
        for n, v in param_values.items():
            named_p[n]._data = v
        for n, v in buffer_values.items():
            named_b[n]._data = v
        yield
    finally:
        for n, p in named_p.items():
            p._data = old_p[n]
        for n, b in named_b.items():
            b._data = old_b[n]


def functional_call(layer, params: Dict[str, Any], buffers: Dict[str, Any], *args, rng_key=None, **kwargs):
    """Run ``layer`` as a pure function of (params, buffers, inputs).

    Tape recording is disabled inside — use jax.grad over this function for
    gradients (the compiled path), not the eager tape.
    """
    t_args = wrap(args)
    t_kwargs = wrap(kwargs)
    ctx = rnd.rng_guard(rng_key) if rng_key is not None else contextlib.nullcontext()
    with _bind_state(layer, params, buffers), no_grad(), ctx:
        out = layer(*t_args, **t_kwargs)
    return unwrap(out)


def _get_state(layer):
    params = {n: p._data for n, p in layer.named_parameters()}
    buffers = {n: b._data for n, b in layer.named_buffers()}
    return params, buffers


class StaticFunction:
    """A compiled callable wrapping a Layer or plain function.

    Untraceable code (data-dependent Python control flow, host side effects —
    what the reference's SOT bytecode tracer would fall back to dygraph on)
    falls back to EAGER execution with a one-time warning instead of raising;
    ``full_graph=True`` disables the fallback (trace errors propagate)."""

    def __init__(self, fn_or_layer, input_spec=None, full_graph=False, backend=None):
        from ..nn.layers import Layer

        self._is_layer = isinstance(fn_or_layer, Layer)
        self._target = fn_or_layer
        self._jitted = None
        self._input_spec = input_spec
        self._full_graph = full_graph
        # input signatures whose trace failed — jax.jit retraces per
        # signature, so a batch-1-only host branch must not de-optimize
        # every other shape. Failed signatures run under FRAGMENT CAPTURE
        # (jit.subgraph), not plain eager: the FLOPs stay compiled.
        self._fallback_sigs = set()
        self._reported_breaks = False
        self._last_capture = None      # last Recorder (diagnostics)

    def _build(self):
        if self._is_layer:
            layer = self._target

            def pure(params, buffers, key, args, kwargs):
                t_args = wrap(args)
                t_kwargs = wrap(kwargs)
                with _bind_state(layer, params, buffers), no_grad(), rnd.rng_guard(key):
                    out = layer(*t_args, **t_kwargs)
                return unwrap(out)

            self._jitted = jax.jit(pure)
        else:
            fn = self._target

            def pure(key, args, kwargs):
                with no_grad(), rnd.rng_guard(key):
                    out = fn(*wrap(args), **wrap(kwargs))
                return unwrap(out)

            self._jitted = jax.jit(pure)

    def _call_eager(self, args, kwargs, key):
        # match the compiled path's ambient contexts: no tape, functional RNG
        # (reusing the already-drawn key keeps the seeded stream in sync with
        # the compiled path: one key per call either way)
        with no_grad(), rnd.rng_guard(key):
            out = self._target(*wrap(args), **wrap(kwargs))
        return self._wrap_out(out)

    def _wrap_out(self, out):
        if self._is_layer or isinstance(out, Tensor) or not hasattr(out, "dtype"):
            return out
        return wrap(out)

    def _call_fragments(self, args, kwargs, key):
        """SOT-equivalent fallback: run the Python untraceably, but batch the
        tensor ops into XLA-compiled fragments cut at the graph breaks
        (jit.subgraph). All FLOPs stay compiled; only control flow is eager.
        Model exceptions propagate exactly as they would in eager."""
        from . import subgraph

        name = getattr(self._target, "__name__", type(self._target).__name__)
        rec = subgraph.Recorder(name)
        with rnd.rng_guard(key), rec:   # Recorder enters no_grad itself
            out = self._target(*wrap(args), **wrap(kwargs))
        self._last_capture = rec
        if not self._reported_breaks:
            self._reported_breaks = True
            import warnings

            warnings.warn(
                f"to_static({name}): whole-graph tracing failed; running with "
                f"fragment capture instead.\n{rec.report()}",
                RuntimeWarning, stacklevel=3)
        return self._wrap_out(out)

    @staticmethod
    def _signature(raw_args, raw_kwargs):
        return tuple(
            (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else a
            for a in jax.tree.leaves((raw_args, raw_kwargs)))

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            # jit.enable_to_static(False): run everything eagerly
            return self._call_eager(args, kwargs, rnd.next_key())
        if self._jitted is None:
            self._build()
        key = rnd.next_key()
        raw_args = unwrap(tuple(a if not isinstance(a, Tensor) else a for a in args))
        raw_kwargs = unwrap(kwargs)
        # signature check only once a fallback exists — the hot path stays free
        if self._fallback_sigs and self._signature(raw_args, raw_kwargs) in self._fallback_sigs:
            return self._call_fragments(args, kwargs, key)
        try:
            if self._is_layer:
                params, buffers = _get_state(self._target)
                out = self._jitted(params, buffers, key, raw_args, raw_kwargs)
            else:
                out = self._jitted(key, raw_args, raw_kwargs)
        except jax.errors.JAXTypeError:
            # data-dependent control flow / host-value use inside the trace —
            # the SOT situation. Fall back to FRAGMENT CAPTURE for this input
            # signature (other shapes may trace whole and stay one program):
            # compiled fragments + eager stitching, with a break report.
            if self._full_graph:
                raise
            self._fallback_sigs.add(self._signature(raw_args, raw_kwargs))
            return self._call_fragments(args, kwargs, key)
        return wrap(out)

    # paddle API surface
    @property
    def forward(self):
        return self

    def concrete_program_specify_input_spec(self, *a, **k):
        return None


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper: compile a function or Layer with XLA (``paddle.jit.to_static``,
    reference ``python/paddle/jit/api.py:196``)."""

    def decorate(fn):
        from ..nn.layers import Layer

        full_graph = bool(kwargs.get("full_graph", False))
        if isinstance(fn, Layer):
            static = StaticFunction(fn, input_spec, full_graph=full_graph)
            fn.forward_static = static
            # replace __call__ path: wrap forward
            orig_cls_call = fn.__call__
            fn._static_function = static
            return fn if kwargs.get("inplace", False) else static
        return functools.wraps(fn)(StaticFunction(fn, input_spec, full_graph=full_graph))

    if function is not None:
        return decorate(function)
    return decorate


def bucketed(fn=None, *, axes, buckets=None, pad_value=0, out_axes=None,
             size_range=None, max_overhead=0.25):
    """Shape-bucketing wrapper: pad dynamic axes up to the next bucket so XLA
    compiles once per BUCKET instead of once per shape.

    This is the framework's dynamic-shape policy (the role of the reference's
    symbolic-shape machinery, ``pir/include/dialect/shape`` — on TPU, static
    shapes + bucketing beat true dynamic shapes, which defeat MXU tiling).

    - ``axes``: list of ``(arg_index, axis)`` pairs to bucket (e.g. the batch
      dim of arg 0 and the seq dim of arg 1).
    - ``buckets``: ascending sizes to round up into; default powers of two;
      ``"auto"`` SYNTHESIZES the minimal ladder for ``size_range=(lo, hi)``
      whose padding waste provably stays under ``max_overhead``
      (``framework.dim_expr.synthesize_buckets`` — the proven bound is
      exposed as ``wrapper._bucket_waste_bound``).
    - ``pad_value``: fill for padded slots (mask semantics are the caller's —
      e.g. pad token ids with an ignore/pad id).
    - ``out_axes``: explicit output slicing as ``(out_axis, arg_index,
      in_axis)`` triples applied to every output leaf.  Without it, each
      output's FIRST axis matching a padded bucket size is cut back (leading-
      batch convention); two bucketed axes landing on the same bucket from
      different lengths is ambiguous and raises.

    Usable as a decorator::

        @jit.bucketed(axes=[(0, 0)])
        def predict(x): ...
    """

    def decorate(f):
        static = StaticFunction(f) if not isinstance(f, StaticFunction) else f

        ladder = buckets
        waste_bound = None
        if buckets == "auto":
            from ..framework.dim_expr import synthesize_buckets

            if size_range is None:
                raise ValueError('buckets="auto" needs size_range=(lo, hi)')
            ladder, waste_bound = synthesize_buckets(
                int(size_range[0]), int(size_range[1]),
                max_overhead=max_overhead)

        def next_bucket(n: int) -> int:
            if ladder is not None:
                for b in sorted(ladder):
                    if b >= n:
                        return int(b)
                raise ValueError(f"size {n} exceeds the largest bucket {max(ladder)}")
            b = 1
            while b < n:
                b *= 2
            return b

        @functools.wraps(f if not isinstance(f, StaticFunction) else f._target)
        def wrapper(*args, **kwargs):
            args = list(args)
            pads = []  # (arg_index, in_axis, bucket, original)
            for i, ax in axes:
                t = args[i]
                raw = t._data if isinstance(t, Tensor) else jnp.asarray(t)
                n = int(raw.shape[ax])
                b = next_bucket(n)
                if b != n:
                    widths = [(0, 0)] * raw.ndim
                    widths[ax] = (0, b - n)
                    raw = jnp.pad(raw, widths, constant_values=pad_value)
                    args[i] = Tensor(raw) if isinstance(t, Tensor) else raw
                pads.append((i, ax, b, n))
            out = static(*args, **kwargs)

            bucket_orig: Dict[int, int] = {}
            for _, _, b, n in pads:
                if b == n:
                    continue
                if out_axes is None and b in bucket_orig and bucket_orig[b] != n:
                    raise ValueError(
                        f"ambiguous output slicing: two bucketed axes padded to "
                        f"bucket {b} from different lengths "
                        f"({bucket_orig[b]} and {n}); pass out_axes=[...]")
                bucket_orig[b] = n

            def unslice(o):
                if isinstance(o, dict):
                    return {k: unslice(v) for k, v in o.items()}
                if isinstance(o, (list, tuple)):
                    return type(o)(unslice(v) for v in o)
                raw = o._data if isinstance(o, Tensor) else o
                if not hasattr(raw, "shape"):
                    return o
                idx = [slice(None)] * raw.ndim
                cut = False
                if out_axes is not None:
                    for oax, i, iax in out_axes:
                        for pi, pax, b, n in pads:
                            if pi == i and pax == iax and b != n:
                                idx[oax] = slice(0, n)
                                cut = True
                else:
                    # leading-batch convention: the FIRST axis matching each
                    # padded bucket is the one that was padded; later axes of
                    # the same size (e.g. a feature dim that happens to equal
                    # the bucket) are left alone
                    remaining = dict(bucket_orig)
                    for d, size in enumerate(raw.shape):
                        if size in remaining:
                            idx[d] = slice(0, remaining.pop(size))
                            cut = True
                if not cut:
                    return o
                sliced = raw[tuple(idx)]
                return Tensor(sliced) if isinstance(o, Tensor) else sliced

            return unslice(out)

        wrapper._static = static
        wrapper._buckets = tuple(sorted(ladder)) if ladder else None
        wrapper._bucket_waste_bound = waste_bound
        return wrapper

    if fn is not None:
        return decorate(fn)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


_LAYER_IDX_RE = re.compile(r"(?:^|\.)layers\.(\d+)\.")


def _overlap_gather_plan(names, n_buckets: int) -> List[List[str]]:
    """Group param names into contiguous layer-group buckets for the
    head-of-step re-gather (ZeRO-1 ``shard_update(overlap_gather=True)``).

    Names matching ``...layers.<i>...`` are bucketed by layer index into
    ``n_buckets`` contiguous groups; everything else (embeddings, final
    norm, lm head) joins the first bucket — those leaves are either
    consumed immediately (embedding) or independent of almost the whole
    forward (head/norm), so their schedule position barely matters.
    Bucketing only controls gather *issue order* (buckets are chained with
    ``optimization_barrier``); correctness never depends on the grouping.
    """
    idx_of = {}
    for n in names:
        m = _LAYER_IDX_RE.search(n)
        if m:
            idx_of[n] = int(m.group(1))
    layer_order = sorted(set(idx_of.values()))
    if not layer_order:
        return [list(names)]
    g = max(1, min(int(n_buckets), len(layer_order)))
    group_of = {li: i * g // len(layer_order)
                for i, li in enumerate(layer_order)}
    buckets: List[List[str]] = [[] for _ in range(g)]
    for n in names:
        buckets[group_of.get(idx_of.get(n, layer_order[0]), 0)].append(n)
    return [b for b in buckets if b]


def _gather_bucketed(params, plan, mesh):
    """Re-gather sharded params to replicated, one bucket at a time.

    Each bucket's leaves get a replicated sharding constraint (GSPMD emits
    the all-gather); bucket k+1's *sharded* inputs are routed through an
    ``optimization_barrier`` together with one of bucket k's gathered
    outputs, so the scheduler cannot issue every gather up front — bucket
    k+1's gather starts after bucket k's completes, i.e. behind bucket k's
    forward compute.  ``optimization_barrier`` is identity on its operands:
    bit-exactness with the sequential path is structural."""
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    out = dict(params)
    prev = None
    for bucket in plan:
        vals = {n: out[n] for n in bucket}
        if prev is not None:
            vals, _ = jax.lax.optimization_barrier((vals, prev))
        vals = {n: jax.lax.with_sharding_constraint(v, rep)
                for n, v in vals.items()}
        out.update(vals)
        prev = vals[bucket[0]]
    return out


def _remat_wrapper(remat):
    """Resolve a TrainStep ``remat`` setting to a loss-function wrapper:
    None/"off" -> no wrapper, "full" -> plain ``jax.checkpoint`` (save
    nothing), a string -> ``jax.checkpoint_policies.<name>``, a callable ->
    used as the checkpoint policy directly."""
    if remat is None or remat == "off":
        return None
    if remat == "full":
        return jax.checkpoint
    pol = remat if callable(remat) else getattr(jax.checkpoint_policies,
                                                str(remat))
    return lambda f: jax.checkpoint(f, policy=pol)


class TrainStep:
    """Compile forward+backward+optimizer into one XLA executable.

    Counterpart of the reference's partial fwd/bwd programs + optimizer fusion;
    on TPU this is the hot path: one device launch per training step.

    Usage::

        def loss_fn(model, x, y):             # receives the (traced) model + batch
            return F.cross_entropy(model(x), y)

        step = paddle_tpu.jit.TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)                     # updates model params in place
    """

    def __init__(self, model, loss_fn, optimizer, donate: bool = True, grads_fn=None,
                 grad_dtype=None, accumulate_steps: int = 1, remat=None,
                 host_grads: bool = False):
        """``grads_fn(params, buffers, *args) -> (loss, grads)`` replaces the
        default ``jax.value_and_grad`` over ``loss_fn`` when given — used by
        schedules that hand-roll their vjp (compiled 1F1B pipeline).

        ``host_grads=True``: ``grads_fn`` runs EAGERLY on the host instead of
        inside the step's jit — the MPMD pipeline runtime drives one jitted
        program per stage with explicit inter-device transfers, so the
        schedule walk cannot live under a single jit.  Only grad clip + the
        optimizer update compile, as a separate jitted apply program.

        ``grad_dtype`` (e.g. ``"bfloat16"``): cast gradient buffers to this
        dtype between backward and the optimizer update — with fp32-stored
        params the cotangents are fp32, and casting lets XLA fuse the
        down-cast into the grad matmul epilogues, halving gradient HBM
        traffic/peak; the optimizer's fp32 math upcasts again.  bf16 grads
        are the standard loss-scaling-free TPU recipe; leave None for exact
        fp32 gradient accumulation.

        ``accumulate_steps`` > 1: gradient accumulation ON DEVICE — each
        call takes args with a leading micro-batch axis of that length,
        runs forward+backward per micro-batch under ``lax.scan`` summing
        gradients (mean-equivalent: summed then divided), and applies ONE
        optimizer update.  The TPU form of the reference's GradientMerge /
        ``accumulate_steps`` (``dygraph_sharding_optimizer.py`` semantics):
        the optimizer's bandwidth-bound elementwise pass — measured 28% of
        the base-preset step — is paid once per k micro-batches.  Gradients
        accumulate in fp32 (or ``grad_dtype`` when set); loss returned is
        the micro-batch mean.  Incompatible with ``grads_fn`` (pipeline
        schedules do their own accumulation).

        ``remat``: wrap the loss in ``jax.checkpoint`` before
        ``value_and_grad`` — "full" saves nothing (classic remat), a string
        names a ``jax.checkpoint_policies`` member, a callable is the
        policy itself.  Defaults to the optimizer's ``set_remat_policy``
        value (the hook ``analysis.autotune``'s remat plans set); not
        applied to a custom ``grads_fn``, which owns its own vjp."""
        accumulate_steps = int(accumulate_steps)
        if accumulate_steps < 1:
            raise ValueError(f"accumulate_steps must be >= 1, "
                             f"got {accumulate_steps}")
        if accumulate_steps > 1 and grads_fn is not None:
            raise ValueError("accumulate_steps is incompatible with grads_fn "
                             "(pipeline schedules accumulate internally)")
        if host_grads and grads_fn is None:
            raise ValueError("host_grads=True needs a grads_fn — it IS the "
                             "host-driven schedule")
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.accumulate_steps = accumulate_steps
        self._params, self._buffers = _get_state(model)
        init_fn, update_fn = optimizer.functional()
        self._opt_state = init_fn(self._params)
        wus = getattr(optimizer, "_wus", None)
        overlap_active = getattr(optimizer, "_wus_overlap_active",
                                 lambda: False)()
        gather_plan = None
        if wus is not None:
            # ZeRO-1 (shard_update) constrains the update to the optimizer's
            # mesh; state committed to a single device would conflict with
            # those constraints at trace time.  Start replicated ON the mesh —
            # the first step's sharding constraints scatter the slots.
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(wus[0], PartitionSpec())
            if overlap_active:
                # overlap_gather: the step consumes and produces *sharded*
                # params (gathered to replicated at the head of step_fn, in
                # layer buckets, behind the forward).  Start them sharded so
                # step 1 compiles the same executable as steady state.
                from ..optimizer.optimizer import _wus_partition_spec

                mesh, axis = wus
                n = mesh.shape[axis]
                self._params = {
                    name: jax.device_put(
                        a, NamedSharding(
                            mesh, _wus_partition_spec(a.shape, n, axis)))
                    for name, a in self._params.items()}
                gather_plan = _overlap_gather_plan(
                    list(self._params),
                    getattr(optimizer, "_wus_buckets", 4))
            else:
                self._params = jax.device_put(self._params, rep)
            self._buffers = jax.device_put(self._buffers, rep)
            self._opt_state = jax.device_put(self._opt_state, rep)
        self._update_fn = update_fn
        self._gather_plan = gather_plan
        self._step = 0
        grad_clip = optimizer._grad_clip
        if remat is None:
            remat = getattr(optimizer, "_remat_policy", None)
        self.remat = remat
        remat_wrap = _remat_wrapper(remat)

        def grads_of(params, buffers, margs, mkey):
            def loss_of(p):
                t_args = wrap(margs)
                with _bind_state(model, p, buffers), no_grad(), rnd.rng_guard(mkey):
                    loss = self.loss_fn(model, *t_args)
                return unwrap(loss)

            if remat_wrap is not None:
                loss_of = remat_wrap(loss_of)
            return jax.value_and_grad(loss_of)(params)

        def step_fn(params, buffers, opt_state, lr, step, key, args):
            if gather_plan is not None:
                # head-of-step bucketed re-gather of last step's sharded
                # update: bucket k+1's all-gather issues behind bucket k's
                # forward layers instead of serializing at the update tail
                params = _gather_bucketed(params, gather_plan, wus[0])
            if grads_fn is not None:
                loss, grads = grads_fn(params, buffers, *args)
            elif accumulate_steps > 1:
                acc_dt = jnp.dtype(grad_dtype) if grad_dtype else jnp.float32
                keys = jax.random.split(key, accumulate_steps)

                def micro(carry, xs):
                    margs, mkey = xs[:-1], xs[-1]
                    mloss, mgrads = grads_of(params, buffers, margs, mkey)
                    acc, ls = carry
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype), acc, mgrads)
                    return (acc, ls + mloss.astype(jnp.float32)), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, acc_dt), params)
                (grads, loss_sum), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32)),
                    (*args, keys))
                inv = 1.0 / accumulate_steps
                grads = jax.tree.map(lambda g: g * jnp.asarray(inv, g.dtype),
                                     grads)
                loss = loss_sum * inv
            else:
                loss, grads = grads_of(params, buffers, args, key)
            if grad_dtype is not None and accumulate_steps == 1:
                gd = jnp.dtype(grad_dtype)
                grads = jax.tree.map(lambda g: g.astype(gd), grads)
            if grad_clip is not None:
                flat = [(None, g) for g in jax.tree.leaves(grads)]
                clipped = [g for _, g in grad_clip(flat)]
                grads = jax.tree.unflatten(jax.tree.structure(grads), clipped)
            new_params, new_state = update_fn(params, grads, opt_state, lr, step)
            return loss, new_params, new_state

        self._host_grads = bool(host_grads)
        self._grads_fn = grads_fn
        if host_grads:
            if gather_plan is not None:
                raise ValueError("host_grads is incompatible with the "
                                 "overlap_gather ZeRO step")

            # the schedule already ran on the host; compile only the tail —
            # clip + optimizer update — as one program
            def apply_fn(params, grads, opt_state, lr, step):
                if grad_dtype is not None:
                    gd = jnp.dtype(grad_dtype)
                    grads = jax.tree.map(lambda g: g.astype(gd), grads)
                if grad_clip is not None:
                    flat = [(None, g) for g in jax.tree.leaves(grads)]
                    clipped = [g for _, g in grad_clip(flat)]
                    grads = jax.tree.unflatten(jax.tree.structure(grads),
                                               clipped)
                return update_fn(params, grads, opt_state, lr, step)

            self._jitted = None
            self._apply = jax.jit(
                apply_fn, donate_argnums=(0, 2) if donate else ())
        else:
            self._jitted = jax.jit(
                step_fn, donate_argnums=(0, 2) if donate else ())

    def __call__(self, *args):
        with obs.span("train.step", cat="train"):
            return self._call(args)

    def _call(self, args):
        raw = unwrap(tuple(args))
        self._step += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(self._step, jnp.int32)
        key = rnd.next_key()
        if self._host_grads:
            loss, grads = self._grads_fn(self._params, self._buffers, *raw)
            # a host-driven schedule (e.g. the MPMD executor) may hand grads
            # back on its own stage devices; the update runs on the params'
            # shardings, so land them there first
            grads = jax.tree.map(
                lambda p, g: jax.device_put(g, p.sharding), self._params,
                grads)
            self._params, self._opt_state = self._apply(
                self._params, grads, self._opt_state, lr, step)
        else:
            loss, self._params, self._opt_state = self._jitted(
                self._params, self._buffers, self._opt_state, lr, step, key,
                raw)
        # reflect updated weights into the eager Layer
        for n, p in self.model.named_parameters():
            p._data = self._params[n]
        return Tensor(loss)

    # -- checkpoint/resume surface (used by fleet.CheckpointManager) --------

    def state_dict(self):
        """Flat dict of everything a resume needs: params, optimizer-state
        leaves (path-keyed — ``opt['<param>']['<slot>']`` — so a positional
        shift can never load one layer's moments into another), the numeric
        LR-scheduler fields, and the step counter."""
        from ..optimizer.lr import LRScheduler

        flat = {f"param.{n}": a for n, a in self._params.items()}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self._opt_state)[0]:
            flat[f"opt{jax.tree_util.keystr(path)}"] = leaf
        flat["step"] = jnp.asarray(self._step, jnp.int32)
        if isinstance(self.optimizer._lr, LRScheduler):
            # numeric fields only (last_epoch, last_lr, plateau counters...);
            # strings/config are rebuilt by the resuming process's constructor
            for k, v in self.optimizer._lr.state_dict().items():
                if isinstance(v, (bool, int, float)):
                    flat[f"lr_sched.{k}"] = jnp.asarray(v)
        return flat

    def set_state_dict(self, flat):
        from ..optimizer.lr import LRScheduler

        self._params = {n: flat[f"param.{n}"] for n in self._params}
        paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(self._opt_state)
        leaves = [flat[f"opt{jax.tree_util.keystr(p)}"] for p, _ in paths_leaves]
        self._opt_state = jax.tree.unflatten(treedef, leaves)
        self._step = int(flat["step"])
        if isinstance(self.optimizer._lr, LRScheduler):
            sched = self.optimizer._lr
            for k, cur in sched.state_dict().items():
                fk = f"lr_sched.{k}"
                if fk in flat and isinstance(cur, (bool, int, float)):
                    sched.__dict__[k] = type(cur)(flat[fk])
        for n, p in self.model.named_parameters():
            p._data = self._params[n]


def save(layer, path, input_spec=None, **configs):
    """AOT-export a Layer (reference ``paddle.jit.save`` -> inference program;
    here: a serialized StableHLO artifact via ``jax.export`` + weights).

    Writes ``path.jaxir`` (the compiled-ahead program, params baked as
    captured constants are NOT used — params are explicit inputs), plus
    ``path.pdiparams`` (weights) and ``path.pdmodel.json`` (IO metadata).
    Requires ``input_spec`` (list of ``static.InputSpec``) or prior example
    inputs recorded by calling the layer.
    """
    import json

    import numpy as np

    from jax import export as jax_export

    from ..framework.io import save as _save
    from ..nn.layers import Layer

    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects a Layer")
    if input_spec is None:
        raise ValueError("jit.save needs input_spec=[static.InputSpec(shape, dtype), ...] "
                         "to trace the exported program")

    params, buffers = _get_state(layer)

    def pure(params, buffers, *inputs):
        t_in = wrap(inputs)
        with _bind_state(layer, params, buffers), no_grad():
            out = layer(*t_in)
        return unwrap(out)

    from ..framework.dtype import convert_dtype

    arg_structs = tuple(
        jax.ShapeDtypeStruct(tuple(int(s) if s is not None and s != -1 else 1 for s in spec.shape),
                             convert_dtype(spec.dtype))
        for spec in input_spec)
    exported = jax_export.export(jax.jit(pure))(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), buffers),
        *arg_structs)
    with open(path + ".jaxir", "wb") as f:
        f.write(exported.serialize())
    _save({"params": {k: np.asarray(v) for k, v in params.items()},
           "buffers": {k: np.asarray(v) for k, v in buffers.items()}}, path + ".pdiparams")
    meta = {
        "inputs": [{"shape": list(s.shape), "dtype": str(s.dtype)} for s in arg_structs],
        "format": "jax.export.stablehlo",
    }
    with open(path + ".pdmodel.json", "w") as f:
        json.dump(meta, f)


class _LoadedFunction:
    """Callable rehydrated from a ``jit.save`` artifact."""

    def __init__(self, path):
        import json

        from jax import export as jax_export

        from ..framework.io import load as _load

        with open(path + ".jaxir", "rb") as f:
            self._exported = jax_export.deserialize(f.read())
        state = _load(path + ".pdiparams")
        self._params = {k: jnp.asarray(v) for k, v in state["params"].items()}
        self._buffers = {k: jnp.asarray(v) for k, v in state["buffers"].items()}
        with open(path + ".pdmodel.json") as f:
            self.meta = json.load(f)

    def __call__(self, *inputs):
        raw = tuple(i._data if isinstance(i, Tensor) else jnp.asarray(i) for i in inputs)
        out = self._exported.call(self._params, self._buffers, *raw)
        return wrap(out)

    # paddle Layer-ish surface so loaded artifacts drop into eval code
    def eval(self):
        return self

    @property
    def forward(self):
        return self


def load(path, **configs):
    """Load a ``jit.save`` artifact as a callable (reference ``paddle.jit.load``)."""
    import os

    if os.path.exists(path + ".jaxir"):
        return _LoadedFunction(path)
    # legacy round-1 artifacts: bare state dicts
    from ..framework.io import load as _load

    return _load(path + ".pdparams")


# -- reference jit utility surface ------------------------------------------

_to_static_enabled = True


def enable_to_static(enable: bool = True) -> None:
    """Globally toggle ``to_static`` tracing (reference
    ``paddle.jit.enable_to_static``): when off, decorated functions run
    eagerly — the SOT-style global fallback switch."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


def set_verbosity(level: int = 0, also_to_stdout: bool = False) -> None:
    """Transcription log verbosity (reference ``jit.set_verbosity``); maps to
    jax's compiler logging."""
    import logging

    logging.getLogger("jax").setLevel(
        logging.DEBUG if level >= 3 else
        logging.INFO if level >= 1 else logging.WARNING)


def set_code_level(level: int = 100, also_to_stdout: bool = False) -> None:
    """Reference ``jit.set_code_level`` dumps transformed code; here the
    traced artifact is the jaxpr — enable jax logging of lowered programs."""
    set_verbosity(3 if level else 0, also_to_stdout)


class TranslatedLayer:
    """A loaded inference program exposed as a callable layer (reference
    ``TranslatedLayer`` — the object ``paddle.jit.load`` returns).  Our
    ``jit.load`` returns the same callable surface; this class is the
    isinstance-able named type wrapping it."""

    def __init__(self, program):
        self._program = program

    def __call__(self, *args, **kwargs):
        return self._program(*args, **kwargs)

    def forward(self, *args, **kwargs):
        return self._program(*args, **kwargs)
