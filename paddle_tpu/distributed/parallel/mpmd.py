"""MPMD pipeline runtime: one jitted program per stage, explicit transfers.

The SPMD pipelines in :mod:`.pipeline` compile the WHOLE schedule into one
lockstep XLA program — every stage executes the full round body every round,
masked off during fill/drain, and a stage failure kills the program.  This
module is the per-stage-program alternative (arXiv:2412.14374): each stage
compiles its own forward / input-grad / weight-grad programs on its own
device, activations and grads move between stages as explicit
``jax.device_put`` transfers, and a host executor walks a tick program
emitted by :mod:`paddle_tpu.analysis.schedule_engine` from
``build_schedule(...)`` itself.

Admission gate: the executor can only be constructed through
``schedule_engine.admit`` — the PR-8 verifier (``lint_schedule``) must
certify the emitted tick DAG deadlock-free BEFORE the first tick runs; a
lint finding raises ``ScheduleRejected`` instead of executing a hang.

Bit-identity: the per-stage programs replicate the EXACT op/vjp/astype
structure of ``pipeline_1f1b_step`` / ``pipeline_zb_step`` (same vjp
closures, same cast points, same microbatch-order accumulation from a
zeros init), so losses and grads are bitwise equal to the single-program
schedules on the same values — the property ``tests/test_mpmd.py`` pins.

Transfers follow the PR-13 double-buffer discipline: a transfer is POSTED
the tick its producer completes (``jax.device_put`` is asynchronous — the
copy rides the wire while later ticks compute) and consumed at the
verifier-checked due tick.

Elasticity: a detected stage failure (``fault_tolerance`` injector, flags
``ft_inject_stage_kill_*``) does NOT shrink the job — the executor drops
the dead device, re-plans the stage→device assignment round-robin over the
survivors, migrates the displaced per-stage params through the PR-9
resharding engine (``fleet.elastic.migrate_to_mesh`` → ``plan_reshard``),
and restarts the step on the shrunken assignment.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...analysis.schedule_engine import (ScheduleRejected, Transfer,
                                         admit, emit_tick_program)

__all__ = ["StageAssignment", "MPMDPipeline", "measure_mpmd_bubble",
           "trace_bubble_from_events", "mpmd_bubble_crosscheck",
           "ScheduleRejected"]


@dataclass(frozen=True)
class StageAssignment:
    """stage -> device map; round-robin when stages outnumber devices (the
    shrunken-mesh case after a failure re-plan)."""

    n_stages: int
    devices: Tuple

    def device(self, stage: int):
        return self.devices[stage % len(self.devices)]

    def without(self, dead) -> "StageAssignment":
        survivors = tuple(d for d in self.devices if d != dead)
        if not survivors:
            raise RuntimeError(
                "mpmd re-plan: no survivor devices left for the pipeline")
        return StageAssignment(self.n_stages, survivors)


class _StageFailure(Exception):
    def __init__(self, stage: int, tick: int):
        super().__init__(f"stage {stage} failed at tick {tick}")
        self.stage = stage
        self.tick = tick


class MPMDPipeline:
    """Per-stage-program pipeline executor.

    ``block_fn(stage_params_local, x, *extra) -> y`` runs one stage body on
    its ``[1, ...]``-leading param shard (VPP: ``[Lps_v, ...]`` chunk params,
    matching :func:`pipeline_vpp_step`).  Training schedules (``1F1B``,
    ``ZB``) additionally need ``first_fn(first_params, data_m) -> x`` and
    ``last_fn(last_params, y, data_m) -> loss_m`` with the
    :func:`pipeline_1f1b_step` contracts; forward schedules (``GPipe``,
    ``VPP``) use ``run_forward``.

    The constructor ADMITS the schedule: ``build_schedule`` →
    ``lint_schedule`` → tick program; ``ScheduleRejected`` is raised before
    any program compiles when the emitted DAG fails the static lint.  The
    clean report is kept on ``self.lint_report`` as admission evidence.
    """

    TRAIN_KINDS = ("1F1B", "ZB")
    FWD_KINDS = ("GPipe", "VPP")

    def __init__(self, block_fn: Callable, n_stages: int, n_micro: int, *,
                 first_fn: Optional[Callable] = None,
                 last_fn: Optional[Callable] = None,
                 schedule: str = "1F1B", virtual_pp_degree: int = 1,
                 double_buffer: bool = False,
                 devices: Optional[Sequence] = None):
        self.n_stages = int(n_stages)
        self.n_micro = int(n_micro)
        self.virtual_pp_degree = int(virtual_pp_degree)
        # admission gate: emit + lint BEFORE anything compiles or runs
        self._sched, self.lint_report = admit(
            schedule, n_stages, n_micro, virtual_pp_degree,
            double_buffer=double_buffer)
        self._program = emit_tick_program(self._sched, self.lint_report)
        self.schedule = self._sched.kind
        if self.schedule in self.TRAIN_KINDS and (
                first_fn is None or last_fn is None):
            raise ValueError(
                f"schedule {self.schedule!r} trains end-to-end: first_fn and "
                "last_fn are required (see pipeline_1f1b_step)")
        self._block_fn = block_fn
        self._first_fn = first_fn
        self._last_fn = last_fn
        devs = tuple(devices) if devices else tuple(
            jax.devices()[:self.n_stages])
        self._assign = StageAssignment(self.n_stages, devs)
        self._stage_mesh: Dict[int, Mesh] = {}
        self.stats = {"ticks": 0, "transfers_posted": 0, "transfer_bytes": 0,
                      "replans": 0, "migrated_arrays": 0,
                      "migrate_peak_bytes": 0, "stash_high_water": 0}
        self._build_programs()

    # -- placement -----------------------------------------------------------

    def _mesh(self, stage: int) -> Mesh:
        mesh = self._stage_mesh.get(stage)
        dev = self._assign.device(stage)
        if mesh is None or mesh.devices.ravel()[0] is not dev:
            # per-stage 1-device mesh: NamedSharding placement is what lets
            # the failure re-plan route through fleet.migrate_to_mesh
            mesh = Mesh(np.array([dev]), ("mpmd",))
            self._stage_mesh[stage] = mesh
        return mesh

    def _put(self, tree, stage: int):
        sh = NamedSharding(self._mesh(stage), P())
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    def _put_dev(self, tree, stage: int):
        dev = self._assign.device(stage)
        return jax.tree.map(lambda a: jax.device_put(a, dev), tree)

    # -- per-stage programs ---------------------------------------------------
    # Each closure mirrors the corresponding sub-step of the single-program
    # schedule op for op (same vjp closures, same astype points) — that, plus
    # microbatch-order accumulation, is what makes the outputs bit-identical.
    # One jax.jit per role; placement does the rest: jit specializes per
    # device, so stage s's calls compile stage s's own program on its device.

    def _build_programs(self):
        block_fn, first_fn, last_fn = \
            self._block_fn, self._first_fn, self._last_fn

        self._p_fwd = jax.jit(
            lambda sp, x, *e: block_fn(sp, x, *e))

        if self.schedule in self.FWD_KINDS:
            return

        def fwd_first(fp, sp, data_m, *e):
            x_in = first_fn(fp, data_m)
            return x_in, block_fn(sp, x_in, *e)

        def bwd_mid(sp, x_m, gy, *e):
            _, blk_vjp = jax.vjp(
                lambda p, xx: block_fn(p, xx, *e), sp, x_m)
            g_sp, gx = blk_vjp(gy)
            return g_sp, gx

        def bwd_last(sp, lp, x_m, data_m, *e):
            y_m, blk_vjp = jax.vjp(
                lambda p, xx: block_fn(p, xx, *e), sp, x_m)

            def loss_of(lpp, yy):
                return last_fn(lpp, yy, data_m)

            loss_m, (g_lp, gy) = jax.value_and_grad(
                loss_of, argnums=(0, 1))(lp, y_m)
            g_sp, gx = blk_vjp(gy.astype(y_m.dtype))
            return loss_m.astype(jnp.float32), g_lp, g_sp, gx

        def bwd_first(sp, fp, x_m, gy, data_m, *e):
            _, blk_vjp = jax.vjp(
                lambda p, xx: block_fn(p, xx, *e), sp, x_m)
            g_sp, gx = blk_vjp(gy)
            _, first_vjp = jax.vjp(lambda p: first_fn(p, data_m), fp)
            (g_fp,) = first_vjp(gx.astype(x_m.dtype))
            return g_sp, g_fp

        self._p_fwd_first = jax.jit(fwd_first)
        self._p_bwd_mid = jax.jit(bwd_mid)
        self._p_bwd_last = jax.jit(bwd_last)
        self._p_bwd_first = jax.jit(bwd_first)

        if self.schedule != "ZB":
            return

        # zero-bubble split: B = input-grad only (params closed over as
        # constants — no dW on the critical path), W = one deferred
        # full-batch vjp per stage
        def zb_bwd_mid(sp, x_m, gy, *e):
            _, vjp_x = jax.vjp(lambda xx: block_fn(sp, xx, *e), x_m)
            (gx,) = vjp_x(gy)
            return gy.astype(x_m.dtype), gx

        def zb_bwd_last(sp, lp, x_m, data_m, *e):
            y_m, vjp_x = jax.vjp(lambda xx: block_fn(sp, xx, *e), x_m)

            def loss_of(lpp, yy):
                return last_fn(lpp, yy, data_m)

            loss_m, (g_lp, gy0) = jax.value_and_grad(
                loss_of, argnums=(0, 1))(lp, y_m)
            gy = gy0.astype(y_m.dtype)
            (gx,) = vjp_x(gy)
            return (loss_m.astype(jnp.float32), g_lp,
                    gy.astype(x_m.dtype), gx)

        def zb_bwd_first(sp, fp, x_m, gy, data_m, *e):
            _, vjp_x = jax.vjp(lambda xx: block_fn(sp, xx, *e), x_m)
            (gx,) = vjp_x(gy)
            _, first_vjp = jax.vjp(lambda p: first_fn(p, data_m), fp)
            (g_fp,) = first_vjp(gx.astype(x_m.dtype))
            return gy.astype(x_m.dtype), g_fp

        def zb_w(sp, xs, gys, *e):
            _, vjp_p = jax.vjp(lambda p: block_fn(p, xs, *e), sp)
            (g_sp,) = vjp_p(gys)
            return g_sp

        self._p_zb_bwd_mid = jax.jit(zb_bwd_mid)
        self._p_zb_bwd_last = jax.jit(zb_bwd_last)
        self._p_zb_bwd_first = jax.jit(zb_bwd_first)
        self._p_zb_w = jax.jit(zb_w)

    # -- fault detection / re-plan -------------------------------------------

    def _check_fault(self, tick: int):
        from ..fault_tolerance.injection import get_injector
        inj = get_injector()
        if inj is None or not inj.active():
            return
        victim = inj.stage_kill_due(tick, list(range(self.n_stages)))
        if victim is not None:
            raise _StageFailure(victim, tick)

    def _replan(self, placed: dict, failure: _StageFailure) -> dict:
        """Drop the failed stage's device, re-plan the assignment over the
        survivors, and migrate displaced per-stage params through the
        resharding engine.  (The CPU simulation still holds the dead
        device's bytes; production restores them from the replicated
        store / checkpoint before this migration.)"""
        from ...distributed.fleet import elastic

        old = self._assign
        self._assign = old.without(old.device(failure.stage))
        self.stats["replans"] += 1

        def migrate(tree, stage):
            if old.device(stage) is self._assign.device(stage):
                return tree
            flat, treedef = jax.tree_util.tree_flatten(tree)
            target = {f"leaf{i}": a for i, a in enumerate(flat)}
            res = elastic.migrate_to_mesh(target, self._mesh(stage))
            self.stats["migrated_arrays"] += res["arrays"]
            self.stats["migrate_peak_bytes"] = max(
                self.stats["migrate_peak_bytes"], res["peak_bytes"])
            return jax.tree_util.tree_unflatten(
                treedef, [target[f"leaf{i}"] for i in range(len(flat))])

        out = dict(placed)
        out["stage"] = [migrate(placed["stage"][s], s)
                        for s in range(len(placed["stage"]))]
        if "first" in placed:
            out["first"] = migrate(placed["first"], 0)
            out["last"] = migrate(placed["last"], self.n_stages - 1)
        return out

    # -- transfer posting -----------------------------------------------------

    def _post(self, t: Transfer, produced, fwd_in, gy_in):
        val = produced[t.src]
        arr = jax.device_put(val, self._assign.device(t.dst_stage))
        self.stats["transfers_posted"] += 1
        self.stats["transfer_bytes"] += int(arr.size) * arr.dtype.itemsize
        if t.dst[0] == "F":
            fwd_in[(t.dst_stage, t.dst[2], t.dst[3])] = arr
        else:
            gy_in[(t.dst_stage, t.dst[2])] = arr

    @staticmethod
    def _take(buf, key, what):
        try:
            return buf.pop(key)
        except KeyError:
            raise RuntimeError(
                f"mpmd executor: {what} for {key} was never delivered — the "
                "walked schedule violates its own certified DAG") from None

    # -- training step (1F1B / ZB) -------------------------------------------

    def step(self, stage_params, first_params, last_params, micro_data,
             *extra):
        """Run one training step; returns ``(loss, g_stage, g_first,
        g_last)`` with the :func:`pipeline_1f1b_step` shapes (``g_stage``
        re-stacked to the global ``[n_stages, ...]`` layout).  On an
        injected stage failure the step re-plans onto the survivors and
        restarts from tick 0."""
        if self.schedule not in self.TRAIN_KINDS:
            raise ValueError(
                f"step() drives the training schedules {self.TRAIN_KINDS}; "
                f"use run_forward() for {self.schedule}")
        from ...obs import dump_flight, flight_event

        placed = self._place_train(stage_params, first_params, last_params)
        for _ in range(self.n_stages + 1):
            try:
                out = self._run_train(placed, micro_data, extra)
                self._record_step_metrics()
                return out
            except _StageFailure as f:
                flight_event("mpmd.stage-kill", stage=f.stage, tick=f.tick)
                placed = self._replan(placed, f)
                flight_event("mpmd.replan", dead_stage=f.stage,
                             survivors=len(self._assign.devices))
                # postmortem AFTER the recovery events so the artifact
                # holds the kill and what the executor did about it
                dump_flight("stage-kill", victim=f"stage {f.stage}",
                            tick=f.tick)
        raise RuntimeError("mpmd: every re-plan attempt failed")

    def _record_step_metrics(self) -> None:
        """Once per step (not per op — the hot path stays untouched):
        mirror the cumulative executor stats into the registry so an
        ``--otrace`` dump's metrics snapshot carries the MPMD side too."""
        from ...obs import registry

        reg = registry()
        lbl = {"schedule": self.schedule, "pp": self.n_stages}
        reg.counter("mpmd.steps", **lbl).inc()
        for k in ("ticks", "transfers_posted", "transfer_bytes", "replans"):
            reg.gauge(f"mpmd.{k}", **lbl).set(self.stats[k])

    def _place_train(self, stage_params, first_params, last_params) -> dict:
        S = self.n_stages
        return {
            # same [1, ...]-leading local shard a P('pp') shard_map would hand
            # block_fn
            "stage": [self._put(jax.tree.map(lambda a: a[s:s + 1],
                                             stage_params), s)
                      for s in range(S)],
            "first": self._put(first_params, 0),
            "last": self._put(last_params, S - 1),
        }

    def _run_train(self, placed, micro_data, extra):
        from ... import obs

        # explicit tracing only: the per-op block_until_ready below must
        # not serialise a pipeline that someone merely profiles
        tr = obs.explicit_tracer()
        S, M = self.n_stages, self.n_micro
        zb = self.schedule == "ZB"
        dev0, devL = self._assign.device(0), self._assign.device(S - 1)
        data = [jax.tree.map(lambda a: a[m], micro_data) for m in range(M)]
        d0 = [self._put_dev(dm, 0) for dm in data]
        dl = d0 if devL is dev0 else [self._put_dev(dm, S - 1) for dm in data]
        ex = [tuple(self._put_dev(e, s) for e in extra) for s in range(S)]

        stash, gy_stash = {}, {}
        fwd_in, gy_in = {}, {}
        g_stage = [jax.tree.map(jnp.zeros_like, placed["stage"][s])
                   for s in range(S)]
        g_first = jax.tree.map(jnp.zeros_like, placed["first"])
        g_last = jax.tree.map(jnp.zeros_like, placed["last"])
        loss_sum = jnp.zeros((), jnp.float32)
        add = lambda acc, g: jax.tree.map(lambda a, b: a + b, acc, g)
        produced = {}

        def _exec(it):
            """One SchedOp, exactly as the untraced walk runs it (same ops,
            same order, same accumulation — bit-identity is preserved);
            returns the values the op just materialized, which the traced
            walk blocks on so a span's dur is the op's completion time."""
            nonlocal loss_sum, g_first, g_last
            s, m = it.stage, it.micro
            if it.kind == "F":
                if s == 0:
                    x_in, y = self._p_fwd_first(
                        placed["first"], placed["stage"][0], d0[m],
                        *ex[0])
                else:
                    x_in = self._take(fwd_in, (s, m, 0), "activation")
                    if tr is not None:
                        tr.instant("mpmd.xfer-due", cat="mpmd", tid=s,
                                   args={"stage": s, "micro": m})
                    y = self._p_fwd(placed["stage"][s], x_in, *ex[s])
                stash[(s, m)] = x_in
                self.stats["stash_high_water"] = max(
                    self.stats["stash_high_water"],
                    sum(1 for k in stash if k[0] == s))
                produced[it.key] = y
                return y
            if it.kind == "B":
                x_m = stash[(s, m)] if zb else stash.pop((s, m))
                if zb:
                    if s == S - 1:
                        loss_m, g_lp, gy_c, gx = self._p_zb_bwd_last(
                            placed["stage"][s], placed["last"], x_m,
                            dl[m], *ex[s])
                        loss_sum = loss_sum + loss_m
                        g_last = add(g_last, g_lp)
                        out = (loss_sum, g_last, gy_c, gx)
                    elif s == 0:
                        gy = self._take(gy_in, (s, m), "output grad")
                        if tr is not None:
                            tr.instant("mpmd.xfer-due", cat="mpmd", tid=s,
                                       args={"stage": s, "micro": m})
                        gy_c, g_fp = self._p_zb_bwd_first(
                            placed["stage"][0], placed["first"], x_m,
                            gy, d0[m], *ex[0])
                        g_first = add(g_first, g_fp)
                        gx = None
                        out = (g_first, gy_c)
                    else:
                        gy = self._take(gy_in, (s, m), "output grad")
                        if tr is not None:
                            tr.instant("mpmd.xfer-due", cat="mpmd", tid=s,
                                       args={"stage": s, "micro": m})
                        gy_c, gx = self._p_zb_bwd_mid(
                            placed["stage"][s], x_m, gy, *ex[s])
                        out = (gy_c, gx)
                    gy_stash[(s, m)] = gy_c
                else:
                    if s == S - 1:
                        loss_m, g_lp, g_sp, gx = self._p_bwd_last(
                            placed["stage"][s], placed["last"], x_m,
                            dl[m], *ex[s])
                        loss_sum = loss_sum + loss_m
                        g_last = add(g_last, g_lp)
                        out = (loss_sum, g_last, gx)
                    elif s == 0:
                        gy = self._take(gy_in, (s, m), "output grad")
                        if tr is not None:
                            tr.instant("mpmd.xfer-due", cat="mpmd", tid=s,
                                       args={"stage": s, "micro": m})
                        g_sp, g_fp = self._p_bwd_first(
                            placed["stage"][0], placed["first"], x_m,
                            gy, d0[m], *ex[0])
                        g_first = add(g_first, g_fp)
                        gx = None
                        out = (g_first,)
                    else:
                        gy = self._take(gy_in, (s, m), "output grad")
                        if tr is not None:
                            tr.instant("mpmd.xfer-due", cat="mpmd", tid=s,
                                       args={"stage": s, "micro": m})
                        g_sp, gx = self._p_bwd_mid(
                            placed["stage"][s], x_m, gy, *ex[s])
                        out = (gx,)
                    g_stage[s] = add(g_stage[s], g_sp)
                    out = out + (g_stage[s],)
                if gx is not None:
                    produced[it.key] = gx
                return out
            # W: deferred full-batch weight grad (ZB only)
            xs = jnp.stack([stash.pop((s, mm)) for mm in range(M)])
            gys = jnp.stack([gy_stash.pop((s, mm))
                             for mm in range(M)])
            flat = lambda a: a.reshape((M * a.shape[1],)
                                       + a.shape[2:])
            g_stage[s] = self._p_zb_w(
                placed["stage"][s], flat(xs), flat(gys), *ex[s])
            return g_stage[s]

        if tr is not None:
            for s in range(S):
                tr.thread_name(s, f"stage {s}")
        for tick, items in enumerate(self._program.ticks):
            self._check_fault(tick)
            produced = {}
            for it in items:
                if isinstance(it, Transfer):
                    if tr is not None:
                        with tr.span("mpmd.xfer-post", cat="mpmd",
                                     tid=it.src_stage,
                                     args={"tick": tick,
                                           "src_stage": it.src_stage,
                                           "dst_stage": it.dst_stage,
                                           "due_tick": it.due_tick}):
                            self._post(it, produced, fwd_in, gy_in)
                    else:
                        self._post(it, produced, fwd_in, gy_in)
                    continue
                if tr is None:
                    _exec(it)
                else:
                    # block inside the span: the measured dur is the op's
                    # true completion time, which is what the trace-derived
                    # bubble (mpmd_bubble_crosscheck) prices per tick
                    with tr.span(it.kind, cat="mpmd.op", tid=it.stage,
                                 args={"tick": tick, "stage": it.stage,
                                       "micro": it.micro,
                                       "kind": it.kind}):
                        jax.block_until_ready(_exec(it))
            self.stats["ticks"] += 1

        # the single-program schedules psum loss/g_first/g_last over stages
        # (only the owning stage's term is nonzero — summing exact zeros);
        # here the owning stage's accumulator already IS that sum
        gather = self._assign.device(0)
        g_glob = jax.tree.map(
            lambda *parts: jnp.concatenate(
                [jax.device_put(p, gather) for p in parts], axis=0),
            *g_stage)
        return loss_sum, g_glob, g_first, g_last

    # -- forward schedules (GPipe / VPP) --------------------------------------

    def run_forward(self, stage_params, micro_inputs, *extra):
        """Walk a forward schedule; returns the last stage's outputs stacked
        ``[n_micro, ...]`` (what row ``-1`` of :func:`pipeline_spmd_step`'s
        global output holds)."""
        if self.schedule not in self.FWD_KINDS:
            raise ValueError(
                f"run_forward() drives {self.FWD_KINDS}; use step() for "
                f"{self.schedule}")
        S, V = self.n_stages, self.virtual_pp_degree
        if self.schedule == "VPP":
            placed = {(s, j): self._put(
                jax.tree.map(lambda a: a[s, j], stage_params), s)
                for s in range(S) for j in range(V)}
        else:
            placed = {(s, 0): self._put(
                jax.tree.map(lambda a: a[s:s + 1], stage_params), s)
                for s in range(S)}
        from ...obs import dump_flight, flight_event

        for _ in range(self.n_stages + 1):
            try:
                out = self._run_forward(placed, micro_inputs, extra)
                self._record_step_metrics()
                return out
            except _StageFailure as f:
                flight_event("mpmd.stage-kill", stage=f.stage, tick=f.tick)
                old = self._assign
                self._assign = old.without(old.device(f.stage))
                self.stats["replans"] += 1
                placed = {k: self._put(v, k[0]) for k, v in placed.items()}
                flight_event("mpmd.replan", dead_stage=f.stage,
                             survivors=len(self._assign.devices))
                dump_flight("stage-kill", victim=f"stage {f.stage}",
                            tick=f.tick)
        raise RuntimeError("mpmd: every re-plan attempt failed")

    def _run_forward(self, placed, micro_inputs, extra):
        from ... import obs

        # explicit tracing only: the per-op block_until_ready below must
        # not serialise a pipeline that someone merely profiles
        tr = obs.explicit_tracer()
        S, M = self.n_stages, self.n_micro
        last_chunk = self.virtual_pp_degree - 1
        in0 = [self._put_dev(jax.tree.map(lambda a: a[m], micro_inputs), 0)
               for m in range(M)]
        ex = [tuple(self._put_dev(e, s) for e in extra) for s in range(S)]
        fwd_in, outs = {}, [None] * M
        if tr is not None:
            for s in range(S):
                tr.thread_name(s, f"stage {s}")
        for tick, items in enumerate(self._program.ticks):
            self._check_fault(tick)
            produced = {}
            for it in items:
                if isinstance(it, Transfer):
                    self._post(it, produced, fwd_in, {})
                    continue
                s, m, j = it.stage, it.micro, it.chunk
                if s == 0 and j == 0:
                    x = in0[m]
                else:
                    x = self._take(fwd_in, (s, m, j), "activation")
                if tr is None:
                    y = self._p_fwd(placed[(s, j)], x, *ex[s])
                else:
                    with tr.span("F", cat="mpmd.op", tid=s,
                                 args={"tick": tick, "stage": s,
                                       "micro": m, "kind": "F"}):
                        y = self._p_fwd(placed[(s, j)], x, *ex[s])
                        jax.block_until_ready(y)
                produced[it.key] = y
                if s == S - 1 and j == last_chunk:
                    outs[m] = y
            self.stats["ticks"] += 1
        return jnp.stack(outs)


def measure_mpmd_bubble(n_stages: int = 2, n_micro: int = 4, dim: int = 512,
                        mb: int = 64, reps: int = 7,
                        schedule: str = "ZB") -> Dict[str, float]:
    """Scan-measure the MPMD executor's bubble with the same toy model and
    M/2M-differencing protocol as
    ``analysis.schedule_lint.measure_bubble_fraction`` (so the two runtimes'
    numbers are directly comparable): ``t_round = (T(2M) - T(M)) / M``,
    ``measured = 1 - M * t_round / T(M)``.

    Unlike the lockstep scan, MPMD stages IDLE during fill/drain instead of
    executing masked round bodies, so per-step work is ``M`` round-equivalents
    rather than ``M + 2(S-1)`` — on the host (and on any schedule whose
    transfers hide behind compute) the measured bubble collapses toward the
    fixed walk overhead.  ``lockstep_predicted`` carries the analytic
    fraction of the equivalent single-program schedule for the A/B.
    """
    from ...analysis.schedule_lint import bubble_fraction, _canon_kind

    kind = _canon_kind(schedule)
    if kind not in MPMDPipeline.TRAIN_KINDS:
        raise NotImplementedError("measurement harness covers 1F1B and ZB")
    S, M = n_stages, n_micro

    def first_fn(fp, d):
        return d @ fp

    def block_fn(sp, x):
        return jnp.tanh(x @ sp[0])

    def last_fn(lp, y, d):
        return ((y @ lp) ** 2).mean() / M

    rng = np.random.default_rng(0)
    fp = jnp.asarray(rng.normal(size=(dim, dim)), jnp.float32) * 0.05
    lp = jnp.asarray(rng.normal(size=(dim, 1)), jnp.float32) * 0.05
    sp = jnp.asarray(rng.normal(size=(S, dim, dim)), jnp.float32) * 0.05

    def built(m):
        pipe = MPMDPipeline(block_fn, S, m, first_fn=first_fn,
                            last_fn=last_fn, schedule=kind)
        d = jnp.asarray(rng.normal(size=(m, mb, dim)), jnp.float32)
        jax.block_until_ready(pipe.step(sp, fp, lp, d))  # compile
        jax.block_until_ready(pipe.step(sp, fp, lp, d))  # warm caches
        return pipe, d

    def once(pipe, d):
        t0 = time.perf_counter()
        jax.block_until_ready(pipe.step(sp, fp, lp, d))
        return time.perf_counter() - t0

    pipe_lo, d_lo = built(M)
    pipe_hi, d_hi = built(2 * M)
    ts_lo, ts_hi = [], []
    for _ in range(reps):
        ts_lo.append(once(pipe_lo, d_lo))
        ts_hi.append(once(pipe_hi, d_hi))
    t_lo, t_hi = float(min(ts_lo)), float(min(ts_hi))
    t_round = (t_hi - t_lo) / M
    measured = 1.0 - (M * t_round) / t_lo if t_lo > 0 else float("nan")
    return {
        "n_stages": S, "n_micro": M,
        "t_lo_s": t_lo, "t_hi_s": t_hi, "t_round_s": t_round,
        "measured": measured,
        "lockstep_predicted": bubble_fraction(kind, S, M)["fraction"],
        "transfers_posted": float(pipe_lo.stats["transfers_posted"]),
        "transfer_bytes": float(pipe_lo.stats["transfer_bytes"]),
    }


def trace_bubble_from_events(events, n_stages: int) -> Dict[str, object]:
    """Trace-derived per-stage idle fraction of an MPMD run.

    ``events`` are Chrome-trace events (``obs.tracer().events()`` or a
    loaded ``--otrace`` dump); only ``cat == "mpmd.op"`` complete events
    count.  Repeated steps re-emit the same op identity
    ``(tick, stage, kind, micro)`` — durations are de-noised to the
    per-identity median before pricing, so one GC pause or scheduler
    hiccup doesn't masquerade as bubble.  The timeline is then priced
    exactly like :func:`analysis.schedule_lint.dag_bubble_fraction`
    prices the certified DAG: wall = Σ over ticks of the heaviest
    stage's cost in that tick (what a real MPMD deployment's wall clock
    is, with per-stage devices running concurrently), busy(s) = Σ of
    stage ``s``'s op durations, idle(s) = 1 − busy(s)/wall.

    Also returns the measured per-``(kind, stage)`` median cost table —
    the ``cost_of`` input that turns ``dag_bubble_fraction`` into the
    analytic half of the cross-check.
    """
    import statistics

    per_op: Dict[tuple, list] = {}
    for ev in events:
        if ev.get("cat") != "mpmd.op" or ev.get("ph") != "X":
            continue
        a = ev.get("args") or {}
        key = (a.get("tick"), a.get("stage"), a.get("kind"),
               a.get("micro"))
        if key[0] is None or key[1] is None:
            continue
        per_op.setdefault(key, []).append(float(ev["dur"]))
    if not per_op:
        raise ValueError("no mpmd.op spans in the event stream — was "
                         "tracing enabled around the MPMD steps?")
    by_tick: Dict[int, Dict[int, float]] = {}
    kind_stage: Dict[tuple, list] = {}
    for (tick, stage, kind, _micro), durs in per_op.items():
        d = statistics.median(durs)
        row = by_tick.setdefault(tick, {})
        row[stage] = row.get(stage, 0.0) + d
        kind_stage.setdefault((kind, stage), []).append(d)
    wall = sum(max(row.values()) for row in by_tick.values())
    busy = [0.0] * n_stages
    for row in by_tick.values():
        for s, d in row.items():
            busy[s] += d
    per_stage = [0.0 if wall == 0 else (wall - b) / wall for b in busy]
    cost_table = {k: statistics.median(v) for k, v in kind_stage.items()}
    return {
        "fraction": sum(per_stage) / n_stages,
        "per_stage": per_stage,
        "wall_us": wall,
        "busy_us": busy,
        "n_ticks": len(by_tick),
        "n_ops": len(per_op),
        "cost_table": cost_table,
    }


def mpmd_bubble_crosscheck(n_stages: int = 2, n_micro: int = 8,
                           dim: int = 512, mb: int = 64, steps: int = 5,
                           schedule: str = "ZB") -> Dict[str, float]:
    """Trace-vs-analytic bubble cross-check: the observability layer
    proves the schedule analyzer (the PR-8 ``measure_bubble_fraction``
    move, upgraded from aggregate tok/s differencing to a real per-op
    timeline).

    Runs the toy-model MPMD pipeline for ``steps`` traced steps, derives
    the per-stage idle fraction from the op spans
    (:func:`trace_bubble_from_events`), then asks ``schedule_lint``'s
    :func:`~paddle_tpu.analysis.schedule_lint.dag_bubble_fraction` to
    predict the same number from the certified tick DAG priced with the
    trace's measured per-(kind, stage) cost table.  If the executor
    really walked the DAG the linter certified — every op in its
    emitted tick, co-scheduled exactly as emitted — the two agree
    (rel err ≤ 0.15 on the CPU mesh, ``tests/test_obs.py``); a dropped
    span, a mis-ticked op, or a schedule the executor silently
    reordered all blow the residual.

    Tracing stays in whatever state it was found (events appended to a
    live tracer are kept — ``bench.py --otrace`` dumps them).
    """
    from ... import obs
    from ...analysis.schedule_lint import (bubble_fraction,
                                           dag_bubble_fraction,
                                           _canon_kind)

    kind = _canon_kind(schedule)
    S, M = n_stages, n_micro

    def first_fn(fp, d):
        return d @ fp

    def block_fn(sp, x):
        return jnp.tanh(x @ sp[0])

    def last_fn(lp, y, d):
        return ((y @ lp) ** 2).mean() / M

    rng = np.random.default_rng(0)
    fp = jnp.asarray(rng.normal(size=(dim, dim)), jnp.float32) * 0.05
    lp = jnp.asarray(rng.normal(size=(dim, 1)), jnp.float32) * 0.05
    sp = jnp.asarray(rng.normal(size=(S, dim, dim)), jnp.float32) * 0.05
    pipe = MPMDPipeline(block_fn, S, M, first_fn=first_fn, last_fn=last_fn,
                        schedule=kind)
    d = jnp.asarray(rng.normal(size=(M, mb, dim)), jnp.float32)

    was_on = obs.trace_enabled()
    jax.block_until_ready(pipe.step(sp, fp, lp, d))      # compile, untraced
    tr = obs.enable_tracing(clear=False)
    n0 = len(tr.events())
    try:
        for _ in range(steps):
            jax.block_until_ready(pipe.step(sp, fp, lp, d))
        events = tr.events()[n0:]
    finally:
        if not was_on:
            obs.disable_tracing()

    trace = trace_bubble_from_events(events, S)
    table = trace["cost_table"]
    analytic = dag_bubble_fraction(
        kind, S, M, cost_of=lambda k, s: table[(k, s)])
    rel = (abs(trace["fraction"] - analytic["fraction"])
           / analytic["fraction"]) if analytic["fraction"] else float("inf")
    return {
        "n_stages": S, "n_micro": M, "schedule": kind, "steps": steps,
        "trace_bubble": trace["fraction"],
        "trace_per_stage": trace["per_stage"],
        "analytic_bubble": analytic["fraction"],
        "analytic_per_stage": analytic["per_stage"],
        "rel_err": rel,
        "lockstep_bubble": bubble_fraction(kind, S, M)["fraction"],
        "n_op_spans": trace["n_ops"],
    }
