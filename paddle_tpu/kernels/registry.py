"""Admission-gated Pallas kernel registry.

Every kernel module registers its ``pallas_call`` sites here as a *spec
builder* — a zero-cost closure returning ``(fn, example_args)`` where the
example args are ``ShapeDtypeStruct``s at representative (small, exactly
tiled) shapes.  The builder is only invoked when something asks for
verification; registration itself allocates nothing.

Three consumers:

- ``python -m paddle_tpu.kernels.registry`` — one JSON line with per-kernel
  finding counts and modeled VMEM bytes, rc 1 on any finding; what
  ``scripts/kernel_gate.sh`` runs.  ``KERNEL_GATE_INJECT=write-race|
  parallel-carry`` registers a seeded-defect kernel, proving the gate can
  fail.
- ``bench.py --lint`` — the per-preset kernel section (entries are tagged
  with the presets that exercise them).
- **admission mode** (``FLAGS_kernel_admission``, mirroring
  ``schedule_engine.admit()``): the public kernel wrappers call
  :func:`ensure_admitted` before their first ``pallas_call``; a registered
  kernel whose verifier report is non-empty raises :class:`KernelRejected`
  with the full report instead of silently corrupting output.  This is the
  seam ROADMAP item 4's *generated* kernels must pass through — a fusion
  transformer registers its emitted kernel and admission refuses it unless
  the write-race/coverage/carry/aliasing proofs go through.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "KernelEntry", "KernelRejected", "admit", "check", "check_all",
    "ensure_admitted", "entries", "load_all", "names", "register",
    "reset_admission_cache",
]


@dataclass
class KernelEntry:
    name: str
    build: Callable[[], tuple]       # () -> (fn, args) or (fn, args, kwargs)
    presets: Tuple[str, ...] = ()    # bench presets that exercise the kernel
    description: str = ""


_REGISTRY: Dict[str, KernelEntry] = {}
_ADMITTED: set = set()
_LOCK = threading.Lock()


class KernelRejected(RuntimeError):
    """Raised by admission when a registered kernel fails the verifier."""


def register(name: str, build: Optional[Callable[[], tuple]] = None, *,
             presets: Tuple[str, ...] = (), description: str = ""):
    """Register a kernel spec builder (usable as a decorator)."""
    def _do(b):
        with _LOCK:
            _REGISTRY[name] = KernelEntry(name, b, tuple(presets), description)
        return b
    return _do if build is None else _do(build)


def entries() -> Dict[str, KernelEntry]:
    return dict(_REGISTRY)


def names() -> list:
    return sorted(_REGISTRY)


def load_all() -> None:
    """Import every kernel module so its registrations run."""
    from . import adamw, flash_attention, rms_norm, ssd_scan  # noqa: F401
    from . import decode_attention  # noqa: F401  (not in package __init__)
    from . import gqa_attention, mla_attention  # noqa: F401
    from . import emit  # noqa: F401  (fusion-transformer emitted kernels)


def check(name: str, vmem_budget: Optional[int] = None):
    """Run the static verifier over one registered kernel -> Report."""
    from ..analysis import pallas_lint

    entry = _REGISTRY[name]
    built = entry.build()
    fn, args = built[0], built[1]
    kwargs = built[2] if len(built) > 2 else {}
    rep = pallas_lint.check_kernel(fn, *args, vmem_budget=vmem_budget,
                                   **kwargs)
    rep.meta["registry_name"] = name
    return rep


def check_all(presets=None, vmem_budget: Optional[int] = None) -> Dict[str, object]:
    """Verify every registered kernel (optionally only those tagged with one
    of ``presets``) -> {name: Report}."""
    want = None if presets is None else (
        {presets} if isinstance(presets, str) else set(presets))
    out = {}
    for name in names():
        if want is not None and not (set(_REGISTRY[name].presets) & want):
            continue
        out[name] = check(name, vmem_budget=vmem_budget)
    return out


def admit(name: str, vmem_budget: Optional[int] = None):
    """Verify; raise :class:`KernelRejected` with the full report on ANY
    finding (the ``schedule_engine.admit`` contract).  Returns the clean
    report otherwise."""
    rep = check(name, vmem_budget=vmem_budget)
    if rep:
        raise KernelRejected(
            f"kernel {name!r} refused by the static verifier "
            f"({len(rep)} finding(s))\n{rep.report()}")
    return rep


def ensure_admitted(name: str) -> None:
    """Admission guard for the public kernel wrappers: verify the named
    registered kernel once per process before its first call, only when
    ``FLAGS_kernel_admission`` is on.  Unregistered names pass (there is
    nothing to certify); a failing verifier raises :class:`KernelRejected`
    *before* the pallas_call executes."""
    from ..framework import flags

    if not flags.get_flag("kernel_admission"):
        return
    with _LOCK:
        if name in _ADMITTED or name not in _REGISTRY:
            return
    admit(name)
    with _LOCK:
        _ADMITTED.add(name)


def reset_admission_cache() -> None:
    with _LOCK:
        _ADMITTED.clear()


# ---------------------------------------------------------------------------
# seeded-defect kernels (KERNEL_GATE_INJECT legs — prove the gate can fail)
# ---------------------------------------------------------------------------

def _build_injected_write_race():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            # every grid point writes block (0, 0): a race once the axis is
            # parallel, and blocks 1..3 are never written (coverage hole)
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
        )(x)

    return fn, (jax.ShapeDtypeStruct((32, 128), jnp.float32),)


def _build_injected_parallel_carry():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, acc):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        s = acc[...] + x_ref[0]
        acc[...] = s
        o_ref[0] = s

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(2, 4),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda g, i: (g, i, 0))],
            out_specs=pl.BlockSpec((1, 8, 128), lambda g, i: (g, i, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 32, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            # the scratch carries across axis 1 (reset only at i == 0);
            # declaring that axis parallel is exactly the ssd_scan bug class
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
        )(x)

    return fn, (jax.ShapeDtypeStruct((2, 32, 128), jnp.float32),)


def _build_injected_emit_race():
    # the fusion transformer's own seeded defect: with
    # KERNEL_GATE_INJECT=emit-race in the environment, every *emitted*
    # kernel's output index_map collapses to block (0, 0) under parallel
    # semantics (emit._row_block_call reads the env var at trace time), so
    # the real registered ``fuse_*`` entries fail lint on their own.  This
    # builder re-exposes one of them under the ``injected_*`` name the gate
    # greps for, proving the defect rides the genuine emission path rather
    # than a purpose-built toy kernel.
    from . import emit

    return emit._fwd_builder(emit.SITES["fuse_swiglu_mlp"])()


_INJECTIONS = {
    "write-race": _build_injected_write_race,
    "parallel-carry": _build_injected_parallel_carry,
    "emit-race": _build_injected_emit_race,
}


def _apply_injection(kind: str) -> None:
    if kind not in _INJECTIONS:
        raise SystemExit(f"unknown KERNEL_GATE_INJECT={kind!r} "
                         f"(known: {sorted(_INJECTIONS)})")
    register(f"injected_{kind.replace('-', '_')}", _INJECTIONS[kind],
             description=f"seeded defect: {kind}")


# ---------------------------------------------------------------------------
# CLI (what scripts/kernel_gate.sh runs)
# ---------------------------------------------------------------------------

def _main() -> int:
    load_all()
    inject = os.environ.get("KERNEL_GATE_INJECT", "").strip()
    if inject:
        _apply_injection(inject)
    reports = check_all()
    kernels = {}
    total = 0
    for name, rep in sorted(reports.items()):
        kernels[name] = {
            "findings": len(rep),
            "codes": rep.counts(),
            "pallas_calls": int(rep.meta.get("kernels", 0)),
            "vmem_bytes": int(rep.meta.get("kernel_vmem_bytes", 0)),
        }
        total += len(rep)
        if rep:
            print(f"[kernel-lint] {name}:\n{rep.report()}", file=sys.stderr)
    print(json.dumps({"kernels": kernels, "kernel_count": len(kernels),
                      "total_findings": total}, sort_keys=True))
    return 1 if total else 0


if __name__ == "__main__":
    # run via the canonical module object: under ``python -m`` this file
    # executes as ``__main__`` while the kernel modules register into
    # ``paddle_tpu.kernels.registry`` — two different registries otherwise
    from paddle_tpu.kernels import registry as _canonical
    raise SystemExit(_canonical._main())
