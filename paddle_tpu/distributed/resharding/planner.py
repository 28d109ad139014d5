"""Collective-program planner for sharded-array redistribution.

``plan_reshard`` takes a source layout (mesh + ``PartitionSpec``) and a
destination layout — possibly on a *different* mesh, e.g. the shrunken
one after an elastic scale-down — and emits a ``ReshardPlan``: an ordered
list of ``ReshardStep``s, each a single portable collective (slice /
all-gather / all-to-all / collective-permute / all-reduce /
reduce-scatter) plus at most one cross-mesh ``remesh`` transfer.  The
rule set is ``analysis/spec_algebra.axis_transitions`` run *forward*
(ROADMAP item 3: the same transition table the HLO lint runs backward),
following the bounded-redistribution scheme of arXiv:2112.01075 instead
of gather-then-scatter.

Phase order is what makes the per-step peak-memory bound hold:

1. **additions** (dst-only axes, local slice) — shards only shrink;
2. **moves** (axis changes dim, all-to-all) — shard volume preserved;
3. **removals** (src-only axes, all-gather) — shards grow toward the
   destination shard size, never past it;
4. **reorders** (tile-order collective-permutes) — volume preserved;
5. **remesh** — the single cross-mesh hop, assembled shard-by-shard.

An axis can only be gathered or all-to-all'd out of a multi-axis tuple
from the *innermost* (last) position — otherwise tiles interleave — so
phases 2/3 insert a tile-order permute first when needed; every such
permute is within ``spec_algebra.expected_collectives`` for the pair
(either the displaced kept axis is "reordered", or an all-to-all is
present, which implies a permute).

Each step records ``peak_bytes``: live input + output bytes per device.
When every step stays ≤ ``2 * max(src_shard, dst_shard)`` the plan is
``bounded``; when divisibility or a missing mesh axis forces the
all-gather last resort, ``bounded`` is False and ``note`` says why.

The planner is pure Python over ``mesh.axis_names`` / ``mesh.devices``
— no jax arrays are touched until ``executor.execute``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ...analysis.spec_algebra import axis_transitions, normalize_spec

__all__ = ["PlanError", "ReshardStep", "ReshardPlan", "plan_reshard",
           "mesh_axis_sizes", "shard_nbytes"]

Norm = Tuple[Tuple[str, ...], ...]

#: step kinds that move data between devices (mirrors Transfer.is_communication)
COMM_KINDS = frozenset({"all-gather", "all-to-all", "collective-permute",
                        "all-reduce", "reduce-scatter"})


class PlanError(ValueError):
    """No bounded collective program exists for the request (non-divisible
    tiling or an axis missing from the planning mesh); ``plan_reshard``
    falls back to the all-gather last resort."""


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def shard_nbytes(shape: Sequence[int], norm: Norm, sizes: Dict[str, int],
                 itemsize: int) -> int:
    """Per-device shard bytes for ``shape`` tiled by ``norm`` on a mesh
    with axis ``sizes``; raises PlanError on non-divisible tiling or an
    unknown axis."""
    n = itemsize
    for dim, axes in enumerate(norm):
        t = 1
        for a in axes:
            if a not in sizes:
                raise PlanError(f"mesh axis {a!r} absent from planning mesh "
                                f"(axes: {sorted(sizes)})")
            t *= sizes[a]
        if t > 1 and shape[dim] % t:
            raise PlanError(f"dim {dim} of size {shape[dim]} not divisible "
                            f"by tile count {t} ({'x'.join(axes)})")
        n *= shape[dim] // t if t > 1 else shape[dim]
    return n


def _mesh_eq(a, b) -> bool:
    if a is b:
        return True
    try:
        return (tuple(a.axis_names) == tuple(b.axis_names)
                and a.devices.shape == b.devices.shape
                and bool((a.devices == b.devices).all()))
    except (AttributeError, TypeError):
        return False


@dataclass(frozen=True)
class ReshardStep:
    """One collective (or the single cross-mesh hop) of a ReshardPlan.

    ``spec_before`` / ``spec_after`` are normalized per-dim axis tuples
    (``normalize_spec`` form); ``mesh`` is the mesh the step executes on
    — for ``remesh`` it is the *destination* mesh.
    """

    kind: str          # "slice" | "all-gather" | "all-to-all" |
                       # "collective-permute" | "all-reduce" |
                       # "reduce-scatter" | "remesh"
    mesh: object
    spec_before: Norm
    spec_after: Norm
    peak_bytes: int
    axis: Optional[str] = None       # mesh axis driving the collective
    dim: int = -1                    # array dim operated on (a2a: dst dim)
    src_dim: int = -1                # a2a only: dim the axis leaves
    order_from: Tuple[str, ...] = ()  # permute only: dim's tuple before
    order_to: Tuple[str, ...] = ()    # permute only: dim's tuple after

    @property
    def is_communication(self) -> bool:
        return self.kind in COMM_KINDS


@dataclass
class ReshardPlan:
    src_mesh: object
    src_spec: object
    dst_mesh: object
    dst_spec: object
    global_shape: Tuple[int, ...]
    dtype: str
    itemsize: int
    plan_mesh: object
    steps: List[ReshardStep] = field(default_factory=list)
    bounded: bool = True
    note: str = ""

    @property
    def src_shard_bytes(self) -> int:
        return shard_nbytes(self.global_shape,
                            normalize_spec(self.src_spec, len(self.global_shape)),
                            mesh_axis_sizes(self.src_mesh), self.itemsize)

    @property
    def dst_shard_bytes(self) -> int:
        return shard_nbytes(self.global_shape,
                            normalize_spec(self.dst_spec, len(self.global_shape)),
                            mesh_axis_sizes(self.dst_mesh), self.itemsize)

    @property
    def bound_bytes(self) -> int:
        return 2 * max(self.src_shard_bytes, self.dst_shard_bytes)

    @property
    def peak_bytes(self) -> int:
        if not self.steps:
            return self.src_shard_bytes
        return max(s.peak_bytes for s in self.steps)

    def collective_kinds(self) -> Set[str]:
        return {s.kind for s in self.steps if s.is_communication}

    def summary(self) -> str:
        kinds = " ".join(s.kind for s in self.steps) or "noop"
        tag = "bounded" if self.bounded else f"UNBOUNDED ({self.note})"
        return (f"reshard {self.global_shape} {self.dtype}: [{kinds}] "
                f"peak={self.peak_bytes}B bound={self.bound_bytes}B {tag}")

    def findings(self):
        """Report the plan through the analyzer's findings catalogue.

        An unbounded plan (all-gather fallback, or a phase program whose
        peak broke the 2x-shard bound) becomes a ``reshard-unbounded``
        finding so lint consumers can rank it by the HBM bytes at stake.
        """
        from ...analysis.findings import Report
        rep = Report(meta={"peak_bytes": self.peak_bytes,
                           "bound_bytes": self.bound_bytes})
        if not self.bounded:
            rep.add("reshard-unbounded", "high",
                    f"reshard {self.global_shape} {self.dtype} peaks at "
                    f"{self.peak_bytes}B > 2x-shard bound {self.bound_bytes}B",
                    where=f"{self.src_spec} -> {self.dst_spec}",
                    bytes=self.peak_bytes,
                    suggestion=self.note or "pick a divisible tiling or "
                    "stage the move through an intermediate spec")
        return rep


def _collective_steps(mesh, sizes: Dict[str, int], src_norm: Norm,
                      dst_norm: Norm, shape: Sequence[int], itemsize: int,
                      src_partial: Sequence[str]) -> List[ReshardStep]:
    """Same-mesh collective program src_norm -> dst_norm, phase-ordered."""
    ndim = len(shape)
    cur: List[List[str]] = [list(t) for t in src_norm]
    steps: List[ReshardStep] = []

    def norm() -> Norm:
        return tuple(tuple(t) for t in cur)

    def shard() -> int:
        return shard_nbytes(shape, norm(), sizes, itemsize)

    def permute_to(d: int, want: List[str]) -> None:
        if cur[d] == want:
            return
        before = norm()
        frm = tuple(cur[d])
        cur[d] = list(want)
        steps.append(ReshardStep("collective-permute", mesh, before, norm(),
                                 2 * shard(), dim=d, order_from=frm,
                                 order_to=tuple(want)))

    trans = axis_transitions(src_norm, dst_norm, ndim=ndim,
                             src_partial=src_partial)

    # phase 0: pending partial sums resolve first
    for t in trans:
        if t.kind != "partial":
            continue
        before_spec, b = norm(), shard()
        if t.dst_pos is not None:
            d = t.dst_pos[0]
            cur[d].append(t.axis)
            steps.append(ReshardStep("reduce-scatter", mesh, before_spec,
                                     norm(), b + shard(), axis=t.axis, dim=d))
        else:
            steps.append(ReshardStep("all-reduce", mesh, before_spec,
                                     before_spec, 2 * b, axis=t.axis))

    # phase 1: additions — shards only shrink from here
    for t in sorted((t for t in trans if t.kind == "added"),
                    key=lambda t: t.dst_pos):
        before_spec, b = norm(), shard()
        d = t.dst_pos[0]
        cur[d].append(t.axis)
        steps.append(ReshardStep("slice", mesh, before_spec, norm(),
                                 b + shard(), axis=t.axis, dim=d))

    # phase 2: moves — volume-preserving all-to-alls, innermost-first
    for t in trans:
        if t.kind != "moved":
            continue
        i, j = t.src_pos[0], t.dst_pos[0]
        permute_to(i, [a for a in cur[i] if a != t.axis] + [t.axis])
        before_spec, b = norm(), shard()
        cur[i].pop()
        cur[j].append(t.axis)
        steps.append(ReshardStep("all-to-all", mesh, before_spec, norm(),
                                 2 * b, axis=t.axis, dim=j, src_dim=i))
        shard()  # validate divisibility of the new tiling

    # phase 3: removals — shards grow toward (never past) the dst shard
    removed = {t.axis for t in trans if t.kind == "removed"}
    for d in range(ndim):
        gone = [a for a in cur[d] if a in removed]
        if not gone:
            continue
        permute_to(d, [a for a in cur[d] if a not in removed] + gone)
        for a in reversed(gone):
            before_spec, b = norm(), shard()
            assert cur[d][-1] == a
            cur[d].pop()
            steps.append(ReshardStep("all-gather", mesh, before_spec, norm(),
                                     b + shard(), axis=a, dim=d))

    # phase 4: tile-order fixup to the exact dst tuples
    for d in range(ndim):
        want = list(dst_norm[d])
        if cur[d] != want:
            if sorted(cur[d]) != sorted(want):
                raise PlanError(f"dim {d}: planned axes {cur[d]} != dst "
                                f"{want}")  # planner invariant violated
            permute_to(d, want)

    assert norm() == dst_norm
    return steps


def _remesh_step(src_mesh, dst_mesh, norm: Norm, shape: Sequence[int],
                 itemsize: int) -> ReshardStep:
    src_b = shard_nbytes(shape, norm, mesh_axis_sizes(src_mesh), itemsize)
    dst_b = shard_nbytes(shape, norm, mesh_axis_sizes(dst_mesh), itemsize)
    return ReshardStep("remesh", dst_mesh, norm, norm,
                       dst_b + min(src_b, dst_b))


def _gather_fallback(src_mesh, dst_mesh, src_norm: Norm, dst_norm: Norm,
                     shape: Sequence[int], itemsize: int,
                     src_partial: Sequence[str],
                     note: str) -> List[ReshardStep]:
    """All-gather last resort: replicate on the src mesh, hop meshes, then
    re-slice.  Peak is the full array — correct but unbounded."""
    src_sizes = mesh_axis_sizes(src_mesh)
    dst_sizes = mesh_axis_sizes(dst_mesh)
    repl: Norm = tuple(() for _ in shape)
    cur: List[List[str]] = [list(t) for t in src_norm]
    steps: List[ReshardStep] = []

    def norm() -> Norm:
        return tuple(tuple(t) for t in cur)

    def shard(sizes) -> int:
        return shard_nbytes(shape, norm(), sizes, itemsize)

    for a in src_partial:
        steps.append(ReshardStep("all-reduce", src_mesh, norm(), norm(),
                                 2 * shard(src_sizes), axis=a))
    for d in range(len(shape)):
        while cur[d]:  # innermost-out, so tiles never interleave
            before_spec, b = norm(), shard(src_sizes)
            a = cur[d].pop()
            steps.append(ReshardStep("all-gather", src_mesh, before_spec,
                                     norm(), b + shard(src_sizes),
                                     axis=a, dim=d))
    if not _mesh_eq(src_mesh, dst_mesh):
        steps.append(_remesh_step(src_mesh, dst_mesh, repl, shape, itemsize))
    for d, axes in enumerate(dst_norm):
        for a in axes:
            before_spec, b = norm(), shard(dst_sizes)
            cur[d].append(a)
            steps.append(ReshardStep("slice", dst_mesh, before_spec, norm(),
                                     b + shard(dst_sizes), axis=a, dim=d))
    return steps


def plan_reshard(src_mesh, src_spec, dst_mesh, dst_spec,
                 global_shape: Sequence[int], dtype, *,
                 src_partial: Sequence[str] = ()) -> ReshardPlan:
    """Plan moving an array of ``global_shape``/``dtype`` from
    (``src_mesh``, ``src_spec``) to (``dst_mesh``, ``dst_spec``).

    When the meshes differ, collectives run on whichever mesh admits a
    valid tiling — preferring the source mesh (remesh last, so the hop
    moves destination-sized shards on a shrink) — and a single ``remesh``
    step crosses over.  If neither mesh admits a bounded program the
    all-gather fallback is returned with ``bounded=False``.
    """
    shape = tuple(int(s) for s in global_shape)
    dt = np.dtype(dtype)
    itemsize = dt.itemsize
    ndim = len(shape)
    src_norm = normalize_spec(src_spec, ndim)
    dst_norm = normalize_spec(dst_spec, ndim)

    def finish(plan_mesh, steps, bounded=True, note=""):
        plan = ReshardPlan(src_mesh, src_spec, dst_mesh, dst_spec, shape,
                           dt.name, itemsize, plan_mesh, steps, bounded, note)
        if bounded and plan.steps and plan.peak_bytes > plan.bound_bytes:
            plan.bounded = False
            plan.note = (f"peak {plan.peak_bytes}B exceeds "
                         f"2x shard bound {plan.bound_bytes}B")
        return plan

    if _mesh_eq(src_mesh, dst_mesh):
        candidates = [(src_mesh, None)]
    elif src_mesh.devices.size >= dst_mesh.devices.size:
        candidates = [(src_mesh, "last"), (dst_mesh, "first")]
    else:
        candidates = [(dst_mesh, "first"), (src_mesh, "last")]

    last_err: Optional[PlanError] = None
    for mesh, remesh_pos in candidates:
        sizes = mesh_axis_sizes(mesh)
        try:
            steps: List[ReshardStep] = []
            if remesh_pos == "first":
                # src tiling must survive on the dst mesh before collectives
                steps.append(_remesh_step(src_mesh, mesh, src_norm, shape,
                                          itemsize))
            steps += _collective_steps(mesh, sizes, src_norm, dst_norm,
                                       shape, itemsize, src_partial)
            if remesh_pos == "last":
                steps.append(_remesh_step(mesh, dst_mesh, dst_norm, shape,
                                          itemsize))
            return finish(mesh, steps)
        except PlanError as e:
            last_err = e

    note = f"all-gather fallback: {last_err}"
    steps = _gather_fallback(src_mesh, dst_mesh, src_norm, dst_norm, shape,
                             itemsize, src_partial, note)
    return finish(src_mesh, steps, bounded=False, note=note)
