"""Static verifier for Pallas TPU kernels (the ``krn-*`` finding family).

The kernel inventory (fused AdamW, flash attention, ssd_scan, the decode
family) rests on invariants nothing checked until now: output blocks must
not be written by two parallel grid points, block footprints must tile the
whole output, VMEM scratch carried across grid steps is only correct when
the carrying axis runs sequentially (ssd_scan's state accumulator), in-place
aliasing needs matching layouts on both sides, and the resident working set
must fit a core's VMEM.  A wrong index map violates these *silently* — the
kernel runs and corrupts output instead of erroring.  This module proves or
refutes each invariant **without executing on hardware**, from the traced
``pallas_call`` equations alone.

Checks and their catalogue codes (see :mod:`.findings` for the report API):

=========================  ================================================
``krn-write-race``         two grid points that differ along a ``parallel``
                           grid axis write the same output block — the
                           store order (and thus the result) is undefined
``krn-coverage-hole``      the union of output block footprints over the
                           grid misses elements — the holes keep whatever
                           garbage the output buffer held
``krn-oob-read``           a block footprint extends past the array edge:
                           entirely out-of-range block index (high) or a
                           partial overhang whose padding lanes are read
                           unmasked (medium)
``krn-parallel-carry``     VMEM scratch is read before it is written
                           (i.e. carries state from the previous grid
                           step) across an axis declared ``parallel`` —
                           the exact invariant ssd_scan's chunk state and
                           flash attention's online-softmax rest on
``krn-alias-mismatch``     ``input_output_aliases`` pairs operands whose
                           shape or dtype differ — the in-place update
                           reinterprets bytes
``krn-alias-raw``          an aliased input's block is read at a grid point
                           after another grid point already overwrote it
                           through the aliased output (index maps of the
                           pair are not pointwise-equal over the grid)
``krn-vmem-over-budget``   resident block working set (double-buffered
                           pipeline blocks) + scratch exceeds the per-core
                           VMEM bound
``krn-dynamic-index``      an index map depends on scalar-prefetch data or
                           the grid is too large to enumerate — footprint
                           checks are skipped for that operand (advisory)
=========================  ================================================

Index maps are evaluated **symbolically** when they are pure coordinate
selections (every block index is a grid axis or a constant — all
hand-written kernels in :mod:`paddle_tpu.kernels` qualify), which proves the
properties for *any* grid size; otherwise they are evaluated exhaustively
over the grid (``jax.core.eval_jaxpr`` per grid point, capped at
``ENUM_CAP`` points — flash attention's clamped causal KV map takes this
path).

Entry points::

    report = pallas_lint.check_kernel(fn, *example_args)   # trace + lint
    specs  = pallas_lint.extract_kernel_specs(fn, *args)   # just the specs
    report = pallas_lint.lint_kernel_spec(spec)            # one kernel

``KernelSpec`` can also be built by hand (``BlockUse`` index maps as plain
callables) — the admission seam ROADMAP item 4's generated kernels pass
through, and the only way to reach ``krn-alias-mismatch`` (pallas itself
refuses mismatched aliases at trace time; generated specs have no tracer
protecting them).
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import core as jax_core
from jax.extend import core as jex_core

from .findings import Report

__all__ = [
    "BlockUse", "ScratchUse", "KernelSpec", "DEFAULT_VMEM_BUDGET",
    "ENUM_CAP", "KRN_CODES", "check_kernel", "extract_kernel_specs",
    "lint_kernel_spec", "spec_from_eqn",
]

# v5e-class scoped VMEM is ~16 MiB/core (see the flash kernels' residency
# budget); the check reports the modeled bytes either way, liveness-style
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

# exhaustive-evaluation cap: grids beyond this fall back to the symbolic
# path or (for genuinely dynamic maps) an advisory finding
ENUM_CAP = 4096

KRN_CODES = (
    "krn-write-race", "krn-coverage-hole", "krn-oob-read",
    "krn-parallel-carry", "krn-alias-mismatch", "krn-alias-raw",
    "krn-vmem-over-budget", "krn-dynamic-index",
)


# ---------------------------------------------------------------------------
# spec model (buildable from a traced eqn OR by hand)
# ---------------------------------------------------------------------------

@dataclass
class BlockUse:
    """One operand's blocking: array shape/dtype + block shape + index map.

    ``index_map`` is either a plain callable ``(*grid_idxs) -> block_idxs``
    (hand-built specs), a resolved form produced by :func:`spec_from_eqn`
    (``("affine", dims)`` / ``("table", {point: idxs})`` / ``("dynamic",
    reason)``), or ``None`` for full-array / ``ANY``-space operands."""
    shape: Tuple[int, ...]
    dtype: Any
    block_shape: Tuple[int, ...] = ()
    index_map: Any = None
    memory_space: str = "vmem"          # "vmem" | "any" | "smem"
    name: str = ""

    def itemsize(self) -> int:
        try:
            return jnp.dtype(self.dtype).itemsize
        except Exception:
            return 4

    def nblocks(self) -> Tuple[int, ...]:
        return tuple(-(-d // b) for d, b in zip(self.shape, self.block_shape))


@dataclass
class ScratchUse:
    shape: Tuple[int, ...]
    dtype: Any
    memory_space: str = "vmem"          # "vmem" | "smem" | "semaphore"

    def nbytes(self) -> int:
        if self.memory_space == "semaphore":
            return 0
        try:
            return int(math.prod(self.shape)) * jnp.dtype(self.dtype).itemsize
        except Exception:
            return 0


@dataclass
class KernelSpec:
    """Everything the verifier needs about one ``pallas_call`` site."""
    name: str
    grid: Tuple[int, ...]
    inputs: List[BlockUse] = field(default_factory=list)
    outputs: List[BlockUse] = field(default_factory=list)
    scratch: List[ScratchUse] = field(default_factory=list)
    # input BlockUse index -> output BlockUse index (in-place pairs)
    aliases: Dict[int, int] = field(default_factory=dict)
    # per grid axis: "parallel" | "arbitrary"; None = all arbitrary
    dimension_semantics: Optional[Tuple[str, ...]] = None
    # (scratch index, axes the carry crosses) for scratch that is read
    # before it is unconditionally written — filled by the jaxpr walk, or
    # by hand for generated specs
    carried_scratch: List[Tuple[int, frozenset]] = field(default_factory=list)

    def parallel_axes(self) -> frozenset:
        if not self.dimension_semantics:
            return frozenset()
        return frozenset(k for k, s in enumerate(self.dimension_semantics)
                         if str(s) == "parallel")


# ---------------------------------------------------------------------------
# index-map resolution
# ---------------------------------------------------------------------------

def _resolve_index_map(bu: BlockUse, grid: Tuple[int, ...]):
    """Normalize ``bu.index_map`` to ("affine", dims) / ("table", images) /
    ("dynamic", reason) / None.  ``dims`` entries are ("const", c) or
    ("axis", k); ``images`` maps every grid point to its block-index tuple."""
    im = bu.index_map
    if im is None:
        return None
    if isinstance(im, tuple) and im and im[0] in ("affine", "table", "dynamic"):
        return im
    if callable(im):
        if math.prod(grid) > ENUM_CAP:
            return ("dynamic", f"grid {grid} exceeds ENUM_CAP={ENUM_CAP}")
        images = {}
        for pt in itertools.product(*map(range, grid)):
            try:
                idxs = im(*pt)
            except Exception as e:
                return ("dynamic", f"index map raised {e!r}")
            idxs = tuple(int(i) for i in (idxs if isinstance(idxs, tuple)
                                          else (idxs,)))
            images[pt] = idxs
        return ("table", images)
    return ("dynamic", f"unrecognized index map {type(im).__name__}")


def _images(resolution, grid: Tuple[int, ...]):
    """Grid point -> block-index tuple, or None when not enumerable."""
    if resolution is None or resolution[0] == "dynamic":
        return None
    if resolution[0] == "table":
        return resolution[1]
    if math.prod(grid) > ENUM_CAP:
        return None
    dims = resolution[1]
    images = {}
    for pt in itertools.product(*map(range, grid)):
        images[pt] = tuple(c if kind == "const" else pt[c]
                           for kind, c in dims)
    return images


def _affine_axes(resolution) -> Optional[frozenset]:
    """Grid axes an affine map's image depends on (None if not affine)."""
    if resolution is None or resolution[0] != "affine":
        return None
    return frozenset(c for kind, c in resolution[1] if kind == "axis")


def _affine_injective(resolution) -> bool:
    """True when each grid axis appears in at most one block dim — the image
    is then a product over dims and per-axis reasoning is exact."""
    axes = [c for kind, c in resolution[1] if kind == "axis"]
    return len(axes) == len(set(axes))


# ---------------------------------------------------------------------------
# traced-eqn extraction
# ---------------------------------------------------------------------------

def _find_pallas_eqns(jaxpr, out: list) -> list:
    """Recursively collect pallas_call eqns through pjit/custom_vjp/etc."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for sub in eqn.params.values():
            subs = sub if isinstance(sub, (tuple, list)) else (sub,)
            for s in subs:
                if isinstance(s, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                    _find_pallas_eqns(s, out)
    return out


def _int_block_shape(block_shape) -> Tuple[int, ...]:
    # a squeezed dim consumes one index and contributes one element
    return tuple(int(getattr(b, "block_size", 1)) for b in block_shape)


def _classify_index_jaxpr(cj, n_axes: int, grid: Tuple[int, ...]):
    """Resolve a BlockMapping's index_map_jaxpr.

    Fast path: no equations — every output is a grid-axis invar or a literal
    constant, so the map is proven affine for ANY grid size.  Otherwise the
    map is evaluated per grid point (flash's clamped causal KV index); maps
    that read the scalar-prefetch ref (or grids past ENUM_CAP) are dynamic.
    """
    jx = cj.jaxpr
    axis_vars = list(jx.invars[:n_axes])
    if not jx.eqns:
        dims = []
        for ov in jx.outvars:
            if isinstance(ov, jex_core.Literal):
                dims.append(("const", int(ov.val)))
            elif ov in axis_vars:
                dims.append(("axis", axis_vars.index(ov)))
            else:
                return ("dynamic", "index map returns non-grid value")
        return ("affine", tuple(dims))
    if math.prod(grid) > ENUM_CAP:
        return ("dynamic", f"grid {grid} exceeds ENUM_CAP={ENUM_CAP}")
    # non-axis invars are scalar-prefetch refs: pass None — a map that
    # actually loads from them fails evaluation and is reported dynamic
    n_extra = len(jx.invars) - n_axes
    images = {}
    for pt in itertools.product(*map(range, grid)):
        try:
            vals = jax_core.eval_jaxpr(jx, cj.consts, *pt, *([None] * n_extra))
        except Exception:
            return ("dynamic", "index map reads scalar-prefetch data")
        images[pt] = tuple(int(v) for v in vals)
    return ("table", images)


def _scratch_space(aval) -> str:
    s = str(getattr(aval, "memory_space", "")).lower()
    if "sema" in s:
        return "semaphore"
    if "smem" in s:
        return "smem"
    return "vmem"


def _union_taint(taint: dict, invars) -> frozenset:
    out: frozenset = frozenset()
    for v in invars:
        if isinstance(v, jex_core.Var):
            out = out | taint.get(v, frozenset())
    return out


def _carried_scratch(kernel_jaxpr, scratch_vars: list,
                     n_axes: int) -> List[Tuple[int, frozenset]]:
    """Which scratch refs carry state across grid steps, and across which axes.

    A scratch ref is *carried* when a read (``get``, or a ``swap`` whose old
    value is used) — top-level or under ``pl.when`` — happens before any
    unconditional top-level write: the read then observes the previous grid
    step's value.  Conditional writes are classified by data flow: a write
    whose stored value does NOT derive from the scratch's own previous
    contents is a *reset* (ssd_scan's ``ci == 0`` zero-init, flash's
    ``ki == 0`` init), and the carry only crosses the axes whose
    ``program_id`` taints the reset guard — state flows across the chunk
    axis but never across ``g``, because the reset cuts it.  A write whose
    value reads the scratch first (flash's masked accumulate step) is an
    update, not a reset, and contributes nothing.  A carried ref with no
    reset carries across every axis.

    Only top-level and ``cond``-branch statements are inspected: reads and
    writes inside ``while``/``scan`` bodies (the decode kernels' DMA
    double-buffer loops) are per-step working state, not grid-carried.
    """
    scratch_set = set(scratch_vars)
    taint: Dict[Any, frozenset] = {}
    derives: Dict[Any, frozenset] = {}    # var -> scratch refs its value read
    first_read: Dict[Any, int] = {}
    first_uncond_write: Dict[Any, int] = {}
    guard_axes: Dict[Any, frozenset] = {}

    def union_derives(dmap, invars):
        out: frozenset = frozenset()
        for v in invars:
            if isinstance(v, jex_core.Var):
                out = out | dmap.get(v, frozenset())
        return out

    def scan_stmt(eqn, pos, dmap, remap, guard):
        """Handle one get/swap statement; remap maps branch vars to outer
        vars (identity at top level), guard is the reset-guard taint
        (None at top level = unconditional)."""
        prim = eqn.primitive.name
        ref = remap.get(eqn.invars[0]) if eqn.invars else None
        d = union_derives(dmap, eqn.invars)
        if prim == "get" and ref in scratch_set:
            first_read.setdefault(ref, pos)
            d = d | frozenset([ref])
        elif prim == "swap" and ref in scratch_set:
            if any(not isinstance(ov, jax_core.DropVar) for ov in eqn.outvars):
                first_read.setdefault(ref, pos)
                d = d | frozenset([ref])
            if guard is None:
                first_uncond_write.setdefault(ref, pos)
            elif ref not in union_derives(dmap, eqn.invars[1:]):
                guard_axes[ref] = guard_axes.get(ref, frozenset()) | guard
        for ov in eqn.outvars:
            if not isinstance(ov, jax_core.DropVar):
                dmap[ov] = d
        return dmap

    for pos, eqn in enumerate(kernel_jaxpr.eqns):
        prim = eqn.primitive.name
        if prim == "program_id":
            ax = eqn.params.get("axis")
            for ov in eqn.outvars:
                taint[ov] = frozenset() if ax is None else frozenset([int(ax)])
        else:
            t = _union_taint(taint, eqn.invars)
            for ov in eqn.outvars:
                if not isinstance(ov, jax_core.DropVar):
                    taint[ov] = t
        if prim in ("get", "swap"):
            ident = {v: v for v in eqn.invars if isinstance(v, jex_core.Var)}
            scan_stmt(eqn, pos, derives, ident, None)
        elif prim == "cond":
            pred = eqn.invars[0]
            g = taint.get(pred, frozenset()) if isinstance(pred, jex_core.Var) \
                else frozenset()
            for branch in eqn.params.get("branches", ()):
                bj = branch.jaxpr if isinstance(branch, jex_core.ClosedJaxpr) \
                    else branch
                remap = {bv: ov for bv, ov in zip(bj.invars, eqn.invars[1:])
                         if isinstance(ov, jex_core.Var)}
                bmap = {bv: derives.get(ov, frozenset())
                        for bv, ov in remap.items()}
                for be in bj.eqns:
                    if be.primitive.name in ("get", "swap"):
                        bmap = scan_stmt(be, pos, bmap, remap, g)
                    else:
                        d = union_derives(bmap, be.invars)
                        for ov in be.outvars:
                            if not isinstance(ov, jax_core.DropVar):
                                bmap[ov] = d

    out = []
    for i, var in enumerate(scratch_vars):
        rd = first_read.get(var)
        if rd is None:
            continue
        wr = first_uncond_write.get(var)
        if wr is not None and wr < rd:
            continue                      # initialized every step before use
        axes = guard_axes.get(var)
        if axes is None or not axes:
            axes = frozenset(range(n_axes))   # no reset: carries everywhere
        out.append((i, axes))
    return out


def spec_from_eqn(eqn, name: str = "") -> KernelSpec:
    """Build a :class:`KernelSpec` from a traced ``pallas_call`` equation."""
    params = eqn.params
    gm = params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_axes = len(grid)
    n_in = int(gm.num_inputs)
    n_out = int(gm.num_outputs)
    n_scalar = int(getattr(gm, "num_index_operands", 0))
    n_scratch = int(getattr(gm, "num_scratch_operands", 0))

    if not name:
        name = params.get("name") or "pallas_call"

    def block_use(bm, label):
        sds = bm.array_aval
        space = str(getattr(bm.block_aval, "memory_space", "")).lower()
        if "any" in space:
            return BlockUse(tuple(sds.shape), sds.dtype,
                            tuple(sds.shape), None, "any", label)
        bs = _int_block_shape(tuple(bm.block_shape))
        res = _classify_index_jaxpr(bm.index_map_jaxpr, n_axes, grid)
        ms = "smem" if "smem" in space else "vmem"
        return BlockUse(tuple(sds.shape), sds.dtype, bs, res, ms, label)

    bms = list(gm.block_mappings)
    inputs = [block_use(bm, f"in{i}") for i, bm in enumerate(bms[:n_in])]
    outputs = [block_use(bm, f"out{i}")
               for i, bm in enumerate(bms[n_in:n_in + n_out])]

    kj = params.get("jaxpr")
    if isinstance(kj, jex_core.ClosedJaxpr):
        kj = kj.jaxpr
    scratch: List[ScratchUse] = []
    carried: List[Tuple[int, frozenset]] = []
    if kj is not None and n_scratch:
        svars = list(kj.invars[-n_scratch:])
        for v in svars:
            aval = v.aval
            scratch.append(ScratchUse(
                tuple(getattr(aval, "shape", ())),
                getattr(aval, "dtype", jnp.float32), _scratch_space(aval)))
        carried = _carried_scratch(kj, svars, n_axes)

    aliases: Dict[int, int] = {}
    for pair in params.get("input_output_aliases", ()) or ():
        in_idx, out_idx = int(pair[0]), int(pair[1])
        aliases[in_idx - n_scalar] = out_idx

    sem = None
    cp = params.get("compiler_params") or {}
    ds = getattr(cp.get("mosaic_tpu"), "dimension_semantics", None)
    if ds is not None:
        sem = tuple(str(s) for s in ds)

    return KernelSpec(name=name, grid=grid, inputs=inputs, outputs=outputs,
                      scratch=scratch, aliases=aliases,
                      dimension_semantics=sem, carried_scratch=carried)


def extract_kernel_specs(fn, *args, **kwargs) -> List[KernelSpec]:
    """Trace ``fn(*args, **kwargs)`` (never executes) and return one
    :class:`KernelSpec` per ``pallas_call`` site, recursing through
    pjit/custom_vjp wrappers.  Args may be arrays or ShapeDtypeStructs."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    eqns = _find_pallas_eqns(closed, [])
    specs = []
    seen: Dict[str, int] = {}
    for eqn in eqns:
        spec = spec_from_eqn(eqn)
        n = seen.get(spec.name, 0)
        seen[spec.name] = n + 1
        if n:
            spec.name = f"{spec.name}#{n}"
        specs.append(spec)
    return specs


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _fmt_pt(pt) -> str:
    return "(" + ", ".join(str(i) for i in pt) + ")"


def _check_footprints(spec: KernelSpec, rep: Report) -> None:
    """Write-race, coverage, and OOB over every blocked operand."""
    par = spec.parallel_axes()
    for is_out, bu in ([(False, b) for b in spec.inputs]
                       + [(True, b) for b in spec.outputs]):
        if bu.memory_space == "any" or bu.index_map is None:
            continue                      # manual-DMA operand: no footprint
        where = f"{spec.name}:{bu.name}"
        res = _resolve_index_map(bu, spec.grid)
        if res is not None and res[0] == "dynamic":
            rep.add("krn-dynamic-index", "low",
                    f"index map not statically evaluable ({res[1]}); "
                    "footprint checks skipped", where=where)
            continue
        nblocks = bu.nblocks()
        ragged = [d % b != 0 for d, b in zip(bu.shape, bu.block_shape)]

        if res[0] == "affine" and _affine_injective(res):
            _check_affine(spec, bu, res, is_out, nblocks, ragged, where,
                          par, rep)
            continue
        images = _images(res, spec.grid)
        if images is None:
            rep.add("krn-dynamic-index", "low",
                    f"grid {spec.grid} too large to enumerate a non-product "
                    "index map; footprint checks skipped", where=where)
            continue
        _check_enumerated(spec, bu, images, is_out, nblocks, ragged, where,
                          par, rep)


def _check_affine(spec, bu, res, is_out, nblocks, ragged, where, par, rep):
    """Exact per-dim reasoning for product-form maps — any grid size."""
    dims = res[1]
    rw = "written" if is_out else "read"
    for d, (kind, c) in enumerate(dims):
        nb = nblocks[d]
        if kind == "const":
            lo = hi = c
        else:
            lo, hi = 0, spec.grid[c] - 1
        if hi >= nb or lo < 0:
            rep.add("krn-oob-read", "high",
                    f"block index {hi if hi >= nb else lo} on dim {d} is "
                    f"outside the {nb}-block range of array dim "
                    f"{bu.shape[d]} (block {bu.block_shape[d]}) — "
                    f"{rw} entirely out of bounds",
                    where=where,
                    suggestion="clamp the index map or shrink the grid")
        elif not is_out and ragged[d] and hi == nb - 1:
            pad = nb * bu.block_shape[d] - bu.shape[d]
            rep.add("krn-oob-read", "medium",
                    f"last block on dim {d} overhangs the array edge by "
                    f"{pad} elements — padding lanes are read unmasked",
                    where=where,
                    suggestion="mask the tail block or pad the operand")
        if is_out and (hi - lo + 1) < nb:
            rep.add("krn-coverage-hole", "high",
                    f"dim {d} covers blocks [{lo}, {hi}] of {nb} — "
                    f"{(nb - (hi - lo + 1)) * bu.block_shape[d]} elements "
                    "per orthogonal slice are never written",
                    where=where,
                    suggestion="index every output block from some grid axis")
    if is_out and par:
        used = _affine_axes(res)
        free = [k for k in sorted(par - used) if spec.grid[k] > 1]
        if free:
            rep.add("krn-write-race", "high",
                    f"output block is revisited across grid axis(es) "
                    f"{free} declared 'parallel' — {math.prod(spec.grid[k] for k in free)} "
                    "programs store the same block in undefined order",
                    where=where,
                    suggestion="declare the axis 'arbitrary' or index the "
                               "output by it")


def _check_enumerated(spec, bu, images, is_out, nblocks, ragged, where,
                      par, rep):
    """Exhaustive check over enumerated images (non-product / eval'd maps)."""
    rw = "written" if is_out else "read"
    oob_seen = overhang_seen = False
    groups: Dict[Tuple[int, ...], list] = {}
    for pt, idxs in images.items():
        groups.setdefault(idxs, []).append(pt)
        for d, i in enumerate(idxs):
            if (i < 0 or i >= nblocks[d]) and not oob_seen:
                oob_seen = True
                rep.add("krn-oob-read", "high",
                        f"grid point {_fmt_pt(pt)} {rw}s block "
                        f"{_fmt_pt(idxs)} outside the {nblocks} block range",
                        where=where,
                        suggestion="clamp the index map or shrink the grid")
            elif (not is_out and ragged[d] and i == nblocks[d] - 1
                  and not overhang_seen):
                overhang_seen = True
                pad = nblocks[d] * bu.block_shape[d] - bu.shape[d]
                rep.add("krn-oob-read", "medium",
                        f"last block on dim {d} overhangs the array edge by "
                        f"{pad} elements — padding lanes are read unmasked",
                        where=where,
                        suggestion="mask the tail block or pad the operand")
    if is_out:
        needed = set(itertools.product(*map(range, nblocks)))
        covered = {i for i in groups if i in needed}
        missing = needed - covered
        if missing:
            ex = min(missing)
            elems = math.prod(bu.block_shape)
            rep.add("krn-coverage-hole", "high",
                    f"{len(missing)} of {len(needed)} output blocks are "
                    f"never written (e.g. block {_fmt_pt(ex)}) — "
                    f"~{len(missing) * elems} elements keep garbage",
                    where=where,
                    suggestion="make the grid x index map cover every block")
        for ax in sorted(par):
            for idxs, pts in groups.items():
                vals = {pt[ax] for pt in pts}
                if len(vals) > 1:
                    a, b = sorted(pts)[:2]
                    rep.add("krn-write-race", "high",
                            f"grid points {_fmt_pt(a)} and {_fmt_pt(b)} "
                            f"both write block {_fmt_pt(idxs)} while axis "
                            f"{ax} is 'parallel' — store order undefined",
                            where=where,
                            suggestion="declare the axis 'arbitrary' or "
                                       "index the output by it")
                    break


def _check_carry(spec: KernelSpec, rep: Report) -> None:
    par = spec.parallel_axes()
    if not par:
        return
    for si, axes in spec.carried_scratch:
        bad = sorted(axes & par)
        if not bad:
            continue
        sc = spec.scratch[si] if si < len(spec.scratch) else None
        rep.add("krn-parallel-carry", "high",
                f"VMEM scratch {si}"
                + (f" {tuple(sc.shape)}" if sc is not None else "")
                + f" is read before it is written — state carried across "
                  f"grid axis(es) {bad} declared 'parallel', where program "
                  "order is not guaranteed",
                where=f"{spec.name}:scratch{si}",
                bytes=sc.nbytes() if sc is not None else 0,
                suggestion="declare the carrying axis 'arbitrary' "
                           "(sequential) in dimension_semantics")


def _check_aliases(spec: KernelSpec, rep: Report) -> None:
    for in_idx, out_idx in sorted(spec.aliases.items()):
        if in_idx >= len(spec.inputs) or out_idx >= len(spec.outputs):
            rep.add("krn-alias-mismatch", "high",
                    f"alias pair in{in_idx}->out{out_idx} is out of range "
                    f"({len(spec.inputs)} inputs, {len(spec.outputs)} "
                    "outputs)", where=spec.name)
            continue
        bi, bo = spec.inputs[in_idx], spec.outputs[out_idx]
        where = f"{spec.name}:in{in_idx}->out{out_idx}"
        if tuple(bi.shape) != tuple(bo.shape) or \
                jnp.dtype(bi.dtype) != jnp.dtype(bo.dtype):
            rep.add("krn-alias-mismatch", "high",
                    f"aliased operands disagree: input {tuple(bi.shape)} "
                    f"{jnp.dtype(bi.dtype).name} vs output "
                    f"{tuple(bo.shape)} {jnp.dtype(bo.dtype).name} — the "
                    "in-place store reinterprets bytes",
                    where=where,
                    bytes=int(math.prod(bi.shape)) * bi.itemsize(),
                    suggestion="alias only identically-shaped/typed pairs")
            continue
        ri = _resolve_index_map(bi, spec.grid)
        ro = _resolve_index_map(bo, spec.grid)
        if any(r is not None and r[0] == "dynamic" for r in (ri, ro)):
            rep.add("krn-dynamic-index", "low",
                    "aliased pair has a dynamic index map; read-after-"
                    "overwrite check skipped", where=where)
            continue
        if ri == ro:                       # structurally identical (affine)
            continue
        ii, io = _images(ri, spec.grid), _images(ro, spec.grid)
        if ii is None or io is None:
            rep.add("krn-dynamic-index", "low",
                    "aliased pair not enumerable; read-after-overwrite "
                    "check skipped", where=where)
            continue
        bad = next((pt for pt in ii if ii[pt] != io[pt]), None)
        if bad is not None or tuple(bi.block_shape) != tuple(bo.block_shape):
            rep.add("krn-alias-raw", "high",
                    "aliased input is not read through the same blocks it "
                    "is overwritten through"
                    + (f" (grid point {_fmt_pt(bad)} reads block "
                       f"{_fmt_pt(ii[bad])} but writes "
                       f"{_fmt_pt(io[bad])})" if bad is not None else
                       " (block shapes differ)")
                    + " — a later grid point reads already-clobbered data",
                    where=where,
                    suggestion="give the aliased pair pointwise-equal "
                               "index maps")


def _vmem_bytes(spec: KernelSpec) -> int:
    """Modeled resident VMEM: pipeline blocks are double-buffered unless the
    map is constant over the grid; ``ANY``-space operands stay in HBM."""
    total = 0
    for bu in spec.inputs + spec.outputs:
        if bu.memory_space != "vmem" or not bu.block_shape:
            continue
        res = _resolve_index_map(bu, spec.grid)
        if res is not None and res[0] == "affine":
            varies = bool(_affine_axes(res))
        elif res is not None and res[0] == "table":
            varies = len(set(res[1].values())) > 1
        else:
            varies = True
        total += (2 if varies else 1) * \
            int(math.prod(bu.block_shape)) * bu.itemsize()
    total += sum(s.nbytes() for s in spec.scratch)
    return total


def lint_kernel_spec(spec: KernelSpec, *,
                     vmem_budget: Optional[int] = None) -> Report:
    """Run every ``krn-*`` check over one kernel spec."""
    rep = Report()
    _check_footprints(spec, rep)
    _check_carry(spec, rep)
    _check_aliases(spec, rep)
    vb = _vmem_bytes(spec)
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    if vb > budget:
        rep.add("krn-vmem-over-budget", "high",
                f"modeled resident VMEM {vb / 1e6:.3f} MB exceeds the "
                f"{budget / 1e6:.3f} MB per-core budget",
                where=spec.name, bytes=vb - budget,
                suggestion="shrink block shapes or page operands via ANY "
                           "+ manual DMA")
    rep.meta["kernel"] = spec.name
    rep.meta["kernel_grid"] = tuple(spec.grid)
    rep.meta["kernel_vmem_bytes"] = vb
    return rep


def check_kernel(fn, *args, vmem_budget: Optional[int] = None,
                 **kwargs) -> Report:
    """Trace ``fn(*args, **kwargs)`` and lint every ``pallas_call`` inside.

    The public entry point (also re-exported as ``analysis.check_kernel``):
    traces abstractly — nothing executes, so it runs on CPU against kernels
    that only compile for TPU.  A kernel that cannot be traced raises: a
    trace failure is a broken kernel, not a finding.  The report's meta
    carries the kernel count and the per-kernel modeled VMEM bytes."""
    rep = Report()
    specs = extract_kernel_specs(fn, *args, **kwargs)
    vm: Dict[str, int] = {}
    for spec in specs:
        r = lint_kernel_spec(spec, vmem_budget=vmem_budget)
        vm[spec.name] = int(r.meta.get("kernel_vmem_bytes", 0))
        rep.findings.extend(r.findings)
    rep.meta["kernels"] = len(specs)
    rep.meta["kernel_names"] = [s.name for s in specs]
    rep.meta["kernel_vmem_bytes"] = max(vm.values(), default=0)
    rep.meta["vmem_bytes_by_kernel"] = vm
    return rep
