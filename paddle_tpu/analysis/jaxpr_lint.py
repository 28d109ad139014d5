"""Level 1 lint: the traced jaxpr and lowering metadata, pre-XLA.

Everything here runs from an abstract trace (``jit(fn).lower(*args)``) — no
model execution, no compile needed — and catches the hazards that are
invisible once GSPMD and the fusion passes have rewritten the module:

- **donation misses** (``lowered.args_info`` vs ``lowered.out_info``): a
  large input with a same-shape/dtype output that was not donated keeps two
  copies of the buffer live across the step — the classic optimizer-state
  double-buffer burn;
- **dtype upcasts** (``convert_element_type`` widening a non-scalar
  operand): f32→f64 from an x64-weak Python constant, bf16→f32 creep, int
  widening — each doubles the traffic of every consumer downstream;
- **Python scalar arguments**: weakly typed, retrace on every new Python
  type, and the usual source of the silent promotions above;
- **host transfers** (``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / ``device_put`` inside the traced step): a host
  round-trip serialized into every step.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Iterator, List, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

from .findings import Report

__all__ = [
    "lint_donation", "lint_jaxpr", "lint_python_scalars", "walk_eqns",
    "arg_aval", "DEFAULT_BIG_BUFFER",
]

# below this, a missed donation is noise (scalars, step counters, rng keys)
DEFAULT_BIG_BUFFER = 1 << 20  # 1 MiB

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")
_HOST_PRIMS_MED = ("device_put",)


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * jnp.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def arg_aval(info):
    """The aval of a ``Lowered.args_info`` leaf (public attr on new jax,
    ``_aval`` on 0.4.x)."""
    return getattr(info, "aval", None) or getattr(info, "_aval", None)


def _keystr(path) -> str:
    try:
        return jax.tree_util.keystr(path)
    except Exception:
        return str(path)


# ---------------------------------------------------------------------------
# donation


def lint_donation(lowered, big_buffer_bytes: int = DEFAULT_BIG_BUFFER) -> Report:
    """Flag non-donated large inputs whose (shape, dtype) matches an output.

    Works on ``jit(fn).lower(...)``: ``args_info`` carries the per-argument
    ``donated`` flag, ``out_info`` the output avals.  Outputs already claimed
    by a donated input are consumed first so only genuinely unaliased
    updates are reported.
    """
    rep = Report()
    try:
        args_info = jax.tree_util.tree_flatten_with_path(lowered.args_info)[0]
        out_info = jax.tree_util.tree_leaves(lowered.out_info)
    except Exception:
        return rep

    def key(aval):
        return (tuple(aval.shape), jnp.dtype(aval.dtype).str)

    slots = Counter(key(o) for o in out_info)  # OutInfo has shape/dtype attrs
    for _, info in args_info:            # donated args claim their output slot
        if getattr(info, "donated", False):
            slots[key(arg_aval(info))] -= 1

    for path, info in args_info:
        if getattr(info, "donated", False):
            continue
        aval = arg_aval(info)
        nbytes = _aval_bytes(aval)
        if nbytes < big_buffer_bytes or slots[key(aval)] <= 0:
            continue
        slots[key(aval)] -= 1
        rep.add(
            "donation-miss", "high",
            f"input {jnp.dtype(aval.dtype).name}{list(aval.shape)} has a "
            "same-shape output but is not donated — the update "
            "double-buffers in HBM",
            where=f"arg{_keystr(path)}", bytes=nbytes,
            suggestion="add it to donate_argnums (and accept the donated "
                       "buffer being consumed)")
    return rep


# ---------------------------------------------------------------------------
# jaxpr walk


def walk_eqns(jaxpr, prefix: str = "") -> Iterator[Tuple[str, Any]]:
    """Yield ``(path, eqn)`` for every equation, recursing into sub-jaxprs
    (pjit bodies, scan/while/cond carriers, custom_* rules)."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        path = f"{prefix}/{name}" if prefix else name
        yield path, eqn
        for pname, pval in eqn.params.items():
            for sub in (pval if isinstance(pval, (list, tuple)) else (pval,)):
                if isinstance(sub, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                    inner = (f"{path}[{eqn.params.get('name', pname)}]"
                             if name == "pjit" else path)
                    yield from walk_eqns(sub, inner)


def lint_jaxpr(closed_jaxpr) -> Report:
    """Upcast + host-transfer lint over a (closed) jaxpr."""
    rep = Report()
    for path, eqn in walk_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name == "convert_element_type":
            _lint_convert(rep, path, eqn)
        elif name in _CALLBACK_PRIMS or "callback" in name:
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))
            rep.add(
                "host-transfer", "high",
                f"`{name}` inside the traced step — a host round-trip "
                "serialized into every execution",
                where=path, bytes=nbytes,
                suggestion="move it out of the step function, or batch it "
                           "behind jax.debug/async dispatch")
        elif name in _HOST_PRIMS_MED or name in ("infeed", "outfeed"):
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))
            rep.add(
                "host-transfer", "medium",
                f"`{name}` inside the traced step — placement/transfer "
                "constraint under jit",
                where=path, bytes=nbytes,
                suggestion="place inputs before calling the step; "
                           "use in_shardings instead of device_put")
    return rep


def _lint_convert(rep: Report, path: str, eqn) -> None:
    invar = eqn.invars[0]
    if not hasattr(invar, "aval") or not hasattr(invar.aval, "dtype"):
        return
    old = jnp.dtype(invar.aval.dtype)
    new = jnp.dtype(eqn.params.get("new_dtype", old))
    size = int(getattr(invar.aval, "size", 0) or 0)
    if size <= 1 or new.itemsize <= old.itemsize:
        return  # scalar churn and narrowings are not traffic hazards
    if old.kind == "b":
        return  # bool masks (comparisons, eye/tri) must widen to be used
    weak = bool(getattr(invar.aval, "weak_type", False)
                or eqn.params.get("weak_type", False))
    sixty_four = new.itemsize >= 8 and new.kind in "fiu"
    rep.add(
        "dtype-upcast",
        "high" if sixty_four else "medium",
        f"{old.name}[{size}] widened to {new.name}"
        + (" via weak-type promotion" if weak else "")
        + (" — 64-bit math is emulated/unsupported on TPU" if sixty_four
           else ""),
        where=path, bytes=size * new.itemsize,
        suggestion=("pin the Python/numpy constant to an explicit dtype "
                    "(jnp.asarray(c, dtype=...))" if weak else
                    "cast where the precision is needed, not the whole "
                    "operand"))


# ---------------------------------------------------------------------------
# python scalars


def lint_python_scalars(args: Tuple[Any, ...], kwargs=None) -> Report:
    """Flag bare Python ``bool``/``int``/``float`` leaves in the call args."""
    rep = Report()
    leaves = jax.tree_util.tree_flatten_with_path((tuple(args), kwargs or {}))[0]
    for path, leaf in leaves:
        if isinstance(leaf, (bool, int, float)) and not hasattr(leaf, "dtype"):
            rep.add(
                "python-scalar-arg", "low",
                f"Python {type(leaf).__name__} argument traces as a "
                "weak-typed scalar: retraces when the Python type changes "
                "and silently promotes dtypes",
                where=f"arg{_keystr(path[1:])}",
                suggestion="pass jnp.asarray(x, dtype=...) or mark it "
                           "static_argnums")
    return rep


def lint_abstract(fn, args, kwargs=None,
                  big_buffer_bytes: int = DEFAULT_BIG_BUFFER) -> Report:
    """Convenience: full Level-1 report for a jitted ``fn`` at ``args``."""
    rep = lint_python_scalars(args, kwargs)
    lowered = fn.lower(*args, **(kwargs or {}))
    rep.extend(lint_donation(lowered, big_buffer_bytes))
    closed = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    rep.extend(lint_jaxpr(closed))
    return rep
