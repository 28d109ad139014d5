"""Findings catalogue for the sharding & communication static analyzer.

Every lint (jaxpr level or HLO level) reports through a common ``Finding``
record so downstream consumers — ``bench.py --lint``, ``scripts/lint_gate.sh``,
tests — can rank, count, and diff results without caring which level produced
them.

Finding codes (the stable catalogue; gates key on these strings):

========================  =====  ========================================
code                      level  meaning
========================  =====  ========================================
``donation-miss``         jaxpr  large input buffer with a same-shape/dtype
                                 output was not donated — the update
                                 double-buffers in HBM
``dtype-upcast``          jaxpr  ``convert_element_type`` widens a non-scalar
                                 operand (f32->f64, weak-type promotion, ...)
``python-scalar-arg``     jaxpr  a bare Python ``bool``/``int``/``float``
                                 argument — weakly typed, retraces on type
                                 change, silently promotes
``host-transfer``         jaxpr  ``pure_callback`` / ``io_callback`` /
                                 ``debug_callback`` / ``device_put`` inside
                                 the traced step — host round-trip per step
``unintended-collective`` hlo    a compiled collective (all-gather,
                                 all-reduce, reduce-scatter, all-to-all,
                                 collective-permute) not in the expected set
``unpartitioned-custom-call`` hlo  a custom call fed by a GSPMD-inserted
                                 all-gather: the op could not be partitioned
                                 and runs replicated on full data (the
                                 Mosaic / shard_map gap)
``replicated-buffer``     hlo    an entry parameter materialized at full
                                 (global) size although its declared spec
                                 shards it
``schedule-deadlock``     sched  cycle or lag-violating edge in the pipeline
                                 schedule's tick DAG — a ppermute waits on a
                                 message produced at/after its own tick
``schedule-missing-edge`` sched  a dependency the schedule semantics require
                                 (comm hop, stash reuse) has no edge — the
                                 consumer can fire before its producer
``schedule-order``        sched  a microbatch's backward is ticked at or
                                 before its forward on some stage
``schedule-tick-count``   sched  warmup/cooldown tick count wrong (op
                                 scheduled outside [0, total_ticks), idle
                                 tail, late warmup) — the off-by-one class
``schedule-memory``       sched  peak in-flight activations on a stage
                                 exceed the stash watermark the step
                                 function allocates
``collective-mismatch``   coll   two ranks' collective sequences diverge in
                                 count, op kind, participant set, or payload
                                 bytes — the rendezvous never completes
``rank-divergent-collective`` coll  a collective under a ``cond`` whose
                                 predicate derives from axis_index /
                                 partition-id: only some ranks enter it
                                 (static deadlock)
``host-unbounded-store-op``   host  blocking store ``get``/``wait``/
                                 ``barrier`` with no explicit timeout —
                                 inherits the rendezvous-scale default
``host-barrier-in-rank-branch`` host  store barrier inside a rank-dependent
                                 ``if`` — skipping ranks leave the arrival
                                 count short forever
``host-blocking-under-lock``  host  blocking store op while holding a lock —
                                 a network stall serializes every other
                                 thread behind it
``reshard-unbounded``     plan   a resharding plan fell back to the
                                 all-gather last resort (or broke the
                                 2x-shard peak bound) — the move
                                 materializes the full array per device
``mem-over-budget``       mem    liveness-modeled peak-resident bytes
                                 exceed the declared per-device HBM
                                 budget — the program cannot fit
``mem-donation-would-help`` mem  a non-donated large input has a matching
                                 un-aliased output slot and donating it
                                 provably lowers the modeled peak (the
                                 finding carries the byte delta)
``mem-remat-candidate``   mem    a large activation stays resident across
                                 >= K compute instructions while the peak
                                 is hit — remat would trade the bytes for
                                 FLOPs (advisory, not gated)
``mem-replicated-resident`` mem  a buffer is resident at global size on
                                 every device despite a sharded declared
                                 spec — the residency twin of
                                 ``replicated-buffer``
``comm-exposed``          hlo    a collective without enough independent
                                 concurrent compute (dependence + shared-
                                 capacity model over the scheduled HLO) —
                                 its wire latency sits on the critical
                                 path instead of hiding behind compute
``krn-write-race``        krn    two grid points differing along a
                                 ``parallel`` axis write the same output
                                 block — store order undefined
``krn-coverage-hole``     krn    output block footprints miss elements
                                 over the grid — holes keep garbage
``krn-oob-read``          krn    block index outside the array's block
                                 range (high), or a ragged last block
                                 whose padding is read unmasked (medium)
``krn-parallel-carry``    krn    VMEM scratch read before written — state
                                 carried across a grid axis declared
                                 ``parallel`` (the ssd_scan chunk state)
``krn-alias-mismatch``    krn    ``input_output_aliases`` pairs operands
                                 with differing shape/dtype — the
                                 in-place store reinterprets bytes
``krn-alias-raw``         krn    aliased input read through different
                                 blocks than it is overwritten through —
                                 reads already-clobbered data
``krn-vmem-over-budget``  krn    modeled resident working set (double-
                                 buffered blocks + scratch) exceeds the
                                 per-core VMEM bound
``krn-dynamic-index``     krn    index map depends on scalar-prefetch
                                 data or the grid is too large to
                                 enumerate — footprint checks skipped
                                 for that operand (advisory)
``fuse-unmatched-site``   fuse   an audit pallas-candidate has no emitter
                                 site in ``kernels.emit`` — the pattern
                                 is real but nothing acts on it yet
                                 (advisory)
``fuse-no-byte-win``      fuse   the audit's analytic-minimum model shows
                                 no traffic saved — substitution would be
                                 churn, the seam stays stock
``fuse-verify-mismatch``  fuse   an emitted kernel (fwd, bwd, or the
                                 end-to-end grad through its custom_vjp)
                                 diverges bit-wise from the jnp reference
                                 in interpret mode
``fuse-admission-rejected`` fuse  ``kernels.registry`` admission
                                 (pallas_lint) refused an emitted kernel
                                 — the site is never activated and a
                                 ``fuse=auto`` tuner plan is pruned
========================  =====  ========================================

Severity is ``high`` / ``medium`` / ``low``; ranking is by severity first,
then by the number of bytes at stake, so the top of the report is always the
biggest HBM burn.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, Iterator, List

__all__ = ["Finding", "Report", "SEVERITY_RANK"]

SEVERITY_RANK = {"high": 0, "medium": 1, "low": 2}


@dataclass
class Finding:
    code: str                 # catalogue code, see module docstring
    severity: str             # "high" | "medium" | "low"
    message: str              # one-line human description
    where: str = ""           # arg path / HLO instruction name
    bytes: int = 0            # HBM bytes at stake (0 when unknown)
    suggestion: str = ""      # concrete next action

    def line(self) -> str:
        b = f" [{self.bytes / 1e6:.3f} MB]" if self.bytes else ""
        loc = f" @ {self.where}" if self.where else ""
        s = f"  -> {self.suggestion}" if self.suggestion else ""
        return f"{self.severity.upper():<7}{self.code:<28}{self.message}{loc}{b}{s}"


@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)
    meta: Dict[str, object] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.findings)

    def __iter__(self) -> Iterator[Finding]:
        return iter(self.findings)

    def __bool__(self) -> bool:  # truthy iff something was found
        return bool(self.findings)

    def add(self, *args, **kwargs) -> Finding:
        f = Finding(*args, **kwargs)
        self.findings.append(f)
        return f

    def extend(self, other: "Report") -> None:
        self.findings.extend(other.findings)
        for k, v in other.meta.items():
            self.meta.setdefault(k, v)

    def ranked(self) -> List[Finding]:
        return sorted(
            self.findings,
            key=lambda f: (SEVERITY_RANK.get(f.severity, 3), -f.bytes, f.code))

    def counts(self) -> Dict[str, int]:
        """Findings per catalogue code (what the lint gate diffs)."""
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.code] = out.get(f.code, 0) + 1
        return dict(sorted(out.items()))

    def by_code(self, code: str) -> List[Finding]:
        return [f for f in self.findings if f.code == code]

    def report(self, top: int = 20) -> str:
        head = (f"lint: {len(self.findings)} finding(s)"
                + (f" — {self._counts_str()}" if self.findings else ""))
        lines = [head]
        lines.extend(f.line() for f in self.ranked()[:top])
        if len(self.findings) > top:
            lines.append(f"... {len(self.findings) - top} more")
        return "\n".join(lines)

    def _counts_str(self) -> str:
        return ", ".join(f"{c}:{n}" for c, n in self.counts().items())

    def to_json(self) -> str:
        return json.dumps({
            "counts": self.counts(),
            "meta": {k: v for k, v in self.meta.items()
                     if isinstance(v, (str, int, float, bool))},
            "findings": [vars(f) for f in self.ranked()],
        }, indent=2, sort_keys=True)
