"""Pallas/TPU fused kernel library.

Counterpart of the reference's fused GPU kernels (``paddle/phi/kernels/fusion/gpu``:
flash_attn, fused_rope, fused_rms_norm, fused_bias_act, block_multi_head_attention)
and its flashattn third-party dynload.  Each kernel ships two implementations:

- a Pallas TPU kernel (the performance path), and
- an XLA reference implementation (CPU tests, correctness oracle, fallback).

Selection: ``FLAGS_use_pallas_kernels`` AND running on TPU.
"""

from __future__ import annotations

import jax

from ..framework import flags


def use_pallas() -> bool:
    return bool(flags.get_flag("use_pallas_kernels")) \
        and jax.default_backend() != "cpu"


def per_shard(fn, shard, n_sharded: int, n_replicated: int = 0):
    """``fn`` run on each device's shard of its first ``n_sharded`` operands
    (all laid out as ``spec``; the ``n_replicated`` after them whole), or
    ``fn`` itself when ``shard`` is None.

    ``shard = (jax Mesh, PartitionSpec)``.  GSPMD cannot partition a Mosaic
    kernel ("wrap the call in a shard_map"), so under a mesh of several
    devices the caller, who knows the layout, names it.  Mesh axes that do
    not divide an operand's dim are dropped from the spec: that dim is then
    gathered, never padded."""
    if shard is None:
        return fn
    from jax.sharding import PartitionSpec as P

    from ..framework.shard_map_compat import shard_map

    mesh, spec = shard

    def call(*args):
        fit = P(*(_dividing(ax, [a.shape[i] for a in args[:n_sharded]], mesh)
                  for i, ax in enumerate(spec)))
        return shard_map(fn, mesh=mesh,
                         in_specs=(fit,) * n_sharded + (P(),) * n_replicated,
                         out_specs=fit, check_vma=False)(*args)

    return call


def _dividing(axes, dims, mesh):
    if axes is None:
        return None
    n = 1
    for ax in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[ax]
    return axes if all(d % n == 0 for d in dims) else None


from . import registry  # noqa: E402,F401  (before kernel modules: they register)
from . import adamw, flash_attention, rms_norm, rope, ssd_scan, swiglu  # noqa: E402,F401
