"""Observability layer (``paddle_tpu.obs``): span tracer, metrics
registry, flight recorder — and the wiring contracts that make them
trustworthy:

- the disabled fast path allocates nothing and takes no lock;
- tracing never perturbs values (serving outputs bit-identical on/off);
- request lifecycle chains are complete and exactly-once, across the
  router AND through a replica-kill failover;
- the MPMD op-span timeline agrees with ``schedule_lint``'s
  DAG-priced analytic bubble (rel err <= 0.15) — the tracer proving
  the analyzer, and vice versa.
"""

import json
import threading
import tracemalloc

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.obs import trace as trace_mod


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off, metrics empty, and
    the flight ring clear — obs state is process-global."""
    obs.disable_tracing()
    obs.reset_metrics()
    obs.flight().clear()
    yield
    obs.disable_tracing()
    obs.reset_metrics()
    obs.flight().clear()


# -- tracer ------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_export_schema(self):
        tr = obs.enable_tracing()
        with obs.span("outer", cat="t", tid=3, args={"k": 1}):
            with obs.span("inner", cat="t", tid=3):
                pass
        obs.instant("tick", cat="t")
        tr.thread_name(3, "stage 3")
        evs = tr.events()
        # completion order: inner closes before outer
        assert [e["name"] for e in evs] == ["inner", "outer", "tick",
                                            "thread_name"]
        inner, outer = evs[0], evs[1]
        assert outer["ph"] == "X" and outer["args"] == {"k": 1}
        assert outer["tid"] == 3
        # containment: inner starts after and ends before outer
        assert inner["ts"] >= outer["ts"]
        assert (inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-6)
        assert obs.validate_chrome_trace(tr.to_chrome_trace()) == []

    def test_dump_round_trips_with_metrics(self, tmp_path):
        tr = obs.enable_tracing()
        with obs.span("s", cat="c"):
            pass
        obs.registry().counter("n").inc(3)
        path = str(tmp_path / "t.json")
        tr.dump(path, metrics=obs.registry().snapshot())
        with open(path) as f:
            doc = json.load(f)
        assert obs.validate_chrome_trace(doc) == []
        assert doc["metrics"]["n"]["value"] == 3
        assert any(e["name"] == "s" for e in doc["traceEvents"])

    def test_disabled_span_is_shared_singleton(self):
        assert obs.span("a") is obs.span("b")
        assert obs.tracer() is None and not obs.trace_enabled()

    def test_disabled_fast_path_allocates_nothing(self):
        N = 1000
        tracemalloc.start()
        try:
            for _ in range(N):               # warm the code path fully
                with obs.span("x", cat="c", args=None):
                    pass
                obs.instant("y")
            tracemalloc.reset_peak()
            cur0, _ = tracemalloc.get_traced_memory()
            for _ in range(N):
                with obs.span("x", cat="c", args=None):
                    pass
                obs.instant("y")
            cur1, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        # any per-span allocation would show up as O(N) growth (N spans *
        # >=56B per smallest object); allow a constant few hundred bytes of
        # interpreter noise
        assert cur1 - cur0 < 1024, "disabled span allocates per call"
        assert peak - cur0 < 1024, "disabled span allocates transiently"

    def test_disabled_fast_path_takes_no_lock(self):
        class _Poisoned:
            def __enter__(self):
                raise AssertionError("module lock acquired on fast path")

            def __exit__(self, *a):
                return False

            def acquire(self, *a, **kw):
                raise AssertionError("module lock acquired on fast path")

            def release(self):
                pass

        old = trace_mod._lock
        trace_mod._lock = _Poisoned()
        try:
            with obs.span("x"):
                pass
            obs.instant("y")
        finally:
            trace_mod._lock = old

    def test_lifecycle_chain_exactly_once(self):
        tr = obs.enable_tracing()
        assert tr.lifecycle_begin("r1") is True
        assert tr.lifecycle_begin("r1") is False     # second begin dedups
        tr.lifecycle_mark("r1", "queued")
        tr.lifecycle_mark("r1", "decode-round", args={"k": 4})
        assert tr.lifecycle_end("r1") is True
        assert tr.lifecycle_end("r1") is False       # second end dropped
        assert tr.lifecycle_end("never-begun") is False
        evs = tr.events()
        assert [e["ph"] for e in evs] == ["b", "n", "n", "e"]
        assert all(e["id"] == "r1" for e in evs)
        assert obs.validate_chrome_trace(tr.to_chrome_trace()) == []

    def test_validator_catches_broken_chains(self):
        def ev(ph, **kw):
            base = {"name": "r", "cat": "c", "ph": ph, "id": "x",
                    "ts": 0.0, "pid": 1, "tid": 1}
            base.update(kw)
            return base

        probs = obs.validate_chrome_trace({"traceEvents": [ev("b")]})
        assert any("never ended" in p for p in probs)
        probs = obs.validate_chrome_trace({"traceEvents": [ev("e")]})
        assert any("end without begin" in p for p in probs)
        probs = obs.validate_chrome_trace(
            {"traceEvents": [ev("b"), ev("b"), ev("e"), ev("e")]})
        assert any("duplicate begin" in p for p in probs)
        probs = obs.validate_chrome_trace(
            {"traceEvents": [{"name": "s", "ph": "X", "ts": 0.0,
                              "dur": -1.0, "pid": 1, "tid": 1}]})
        assert any("negative dur" in p for p in probs)
        assert obs.validate_chrome_trace({}) == ["missing traceEvents key"]


# -- following the profiler ---------------------------------------------------


@pytest.fixture
def profiler(tmp_path):
    """``start()`` opens a JAX profiler session, ``stop()`` closes it and
    returns the capture; a test that fails in between leaves none open."""
    import jax

    class _P:
        on = False

        def start(self):
            jax.profiler.start_trace(str(tmp_path))
            self.on = True

        def stop(self):
            from benchmarks.tracered import find_xplane

            jax.profiler.stop_trace()
            self.on = False
            return jax.profiler.ProfileData.from_file(
                find_xplane(str(tmp_path)))

    p = _P()
    yield p
    if p.on:
        jax.profiler.stop_trace()
    obs.profiled_events()          # the first call after a session ends it


def _names(events, ph="X"):
    return [e["name"] for e in events if e["ph"] == ph]


class TestFollowsProfiler:
    def test_span_is_recorded_and_lies_in_the_capture(self, profiler):
        profiler.start()
        with obs.span("serve.step", cat="serve", args={"k": 3}) as sp:
            with obs.span("serve.readback", cat="serve"):
                pass
            sp.set(tokens=7)
        data = profiler.stop()
        evs = obs.profiled_events()
        assert _names(evs) == ["serve.readback", "serve.step"]
        assert evs[1]["args"] == {"k": 3, "tokens": 7}
        host = next(pl for pl in data.planes if pl.name == "/host:CPU")
        seen = {e.name: e for ln in host.lines for e in ln.events
                if e.name.startswith("serve.")}
        assert set(seen) == {"serve.step", "serve.readback"}
        # the capture holds the same nesting, on its own clock
        outer, inner = seen["serve.step"], seen["serve.readback"]
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.duration_ns
                <= outer.start_ns + outer.duration_ns)

    def test_nothing_is_recorded_between_sessions(self, profiler):
        profiler.start()
        with obs.span("in-session"):
            pass
        profiler.stop()
        assert obs.span("after") is obs.span("after-too")   # the no-op
        with obs.span("after"):
            pass
        obs.instant("after")
        assert obs.tracer() is None
        assert _names(obs.profiled_events()) == ["in-session"]

    def test_next_session_starts_with_an_empty_buffer(self, profiler):
        profiler.start()
        with obs.span("first"):
            pass
        profiler.stop()
        with obs.span("between"):
            pass
        profiler.start()
        with obs.span("second"):
            pass
        assert _names(obs.profiled_events()) == ["second"]
        profiler.stop()
        assert _names(obs.profiled_events()) == ["second"]

    def test_explicit_tracer_does_not_follow(self, profiler):
        """The MPMD executor blocks per op when ITS tracer is live; a
        profiler session alone must not switch that on."""
        profiler.start()
        assert obs.tracer() is not None
        assert obs.explicit_tracer() is None and not obs.trace_enabled()
        tr = obs.enable_tracing()
        assert obs.explicit_tracer() is tr and obs.tracer() is tr
        profiler.stop()
        assert obs.tracer() is tr                  # explicit tracing stays

    def test_enabled_tracing_keeps_its_buffer_through_a_session(
            self, profiler):
        tr = obs.enable_tracing()
        with obs.span("before"):
            pass
        profiler.start()
        with obs.span("during"):
            pass
        data = profiler.stop()
        assert _names(tr.events()) == ["before", "during"]
        host = next(pl for pl in data.planes if pl.name == "/host:CPU")
        assert "during" in {e.name for ln in host.lines for e in ln.events}


# -- metrics -----------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_basics(self):
        reg = obs.registry()
        reg.counter("serve.requests").inc()
        reg.counter("serve.requests").inc(2)
        assert reg.counter("serve.requests").value == 3
        reg.gauge("serve.queue_depth").set(5)
        reg.gauge("serve.queue_depth").dec(2)
        assert reg.gauge("serve.queue_depth").value == 3

    def test_labeled_families_are_distinct(self):
        reg = obs.registry()
        reg.counter("serve.requests", replica=0).inc()
        reg.counter("serve.requests", replica=1).inc(5)
        snap = reg.snapshot()
        assert snap["serve.requests{replica=0}"]["value"] == 1
        assert snap["serve.requests{replica=1}"]["value"] == 5
        assert snap["serve.requests{replica=1}"]["labels"] == {"replica": 1}

    def test_type_conflict_raises(self):
        reg = obs.registry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_histogram_quantiles(self):
        h = obs.registry().histogram(
            "lat", buckets=tuple(float(b) for b in range(10, 101, 10)))
        for v in range(1, 101):                # 1..100, 10 per bucket
            h.observe(float(v))
        assert h.count == 100 and h.min == 1.0 and h.max == 100.0
        # rank interpolation is exact at bucket edges for uniform data
        assert h.quantile(0.50) == pytest.approx(50.0, abs=1.0)
        assert h.quantile(0.95) == pytest.approx(95.0, abs=1.0)
        assert h.quantile(0.99) == pytest.approx(99.0, abs=1.0)
        h.observe(1e9)                         # overflow bucket
        assert h.quantile(0.999) == h.max      # clamped to observed max

    def test_histogram_empty_quantile_is_nan(self):
        h = obs.registry().histogram("empty")
        assert np.isnan(h.quantile(0.5))
        assert "p50" not in h._snap()

    def test_snapshot_round_trip(self):
        reg = obs.registry()
        reg.counter("c", replica=0).inc(7)
        reg.gauge("g").set(2.5)
        h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        snap = reg.snapshot()
        rebuilt = obs.Registry.from_snapshot(snap)
        assert rebuilt.snapshot() == snap      # quantiles included
        # the snapshot is plain JSON
        assert json.loads(json.dumps(snap)) == snap

    def test_reset_isolates_runs(self):
        obs.registry().counter("c").inc()
        obs.reset_metrics()
        assert obs.registry().snapshot() == {}


# -- flight recorder ---------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_evicts_oldest(self):
        fr = obs.FlightRecorder(capacity=8)
        for i in range(20):
            fr.event("e", i=i)
        assert len(fr) == 8 and fr.capacity == 8
        snap = fr.snapshot()
        assert [e["args"]["i"] for e in snap] == list(range(12, 20))
        assert [e["seq"] for e in snap] == list(range(13, 21))

    def test_span_tee_when_tracing(self):
        obs.enable_tracing()
        with obs.span("mpmd.op", cat="mpmd"):
            pass
        kinds = [e["kind"] for e in obs.flight().snapshot()]
        assert "span" in kinds

    def test_dump_and_last_dump_path(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
        obs.flight_event("inject.serve-kill", victim=1)
        obs.flight_event("serve.reroute", rid="rtr-1")
        path = obs.dump_flight("serve-kill", victim="replica 1")
        assert path == obs.last_flight_dump()
        assert path.startswith(str(tmp_path))
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "serve-kill"
        assert doc["victim"] == "replica 1"
        names = [e["name"] for e in doc["events"]]
        assert names.index("inject.serve-kill") < names.index(
            "serve.reroute")

    def test_events_named(self):
        fr = obs.FlightRecorder(capacity=4)
        fr.event("a")
        fr.event("b")
        fr.event("a")
        assert len(fr.events_named("a")) == 2


# -- serving lifecycle -------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config

    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config())


def _engine(model, **kw):
    from paddle_tpu.serving import Engine

    kw.setdefault("max_batch", 2)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("block_size", 128)
    kw.setdefault("prefill_buckets", (128, 256))
    return Engine(model, **kw)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


class TestServingLifecycle:
    def test_outputs_bit_identical_and_chain_complete(self, tiny_model):
        from paddle_tpu.serving import GenRequest

        cfg = tiny_model.config
        prompts = _prompts(cfg, (20, 45, 33))

        def run():
            eng = _engine(tiny_model)
            rids = [eng.add_request(GenRequest(prompt_ids=p,
                                               max_new_tokens=6))
                    for p in prompts]
            outs = {o.request_id: o.output_ids
                    for o in eng.run_to_completion()}
            return rids, outs

        rids_off, outs_off = run()
        tr = obs.enable_tracing()
        rids_on, outs_on = run()
        assert outs_on == outs_off, "tracing changed serving outputs"

        evs = tr.events()
        for rid in rids_on:
            assert [e["ph"] for e in evs
                    if e.get("id") == rid and e["ph"] in "be"] == ["b", "e"]
            phases = [e["name"] for e in evs
                      if e.get("id") == rid and e["ph"] == "n"]
            assert phases[0] == "queued"
            assert "admitted" in phases and "prefill" in phases
            assert "decode-round" in phases
        end = next(e for e in evs
                   if e.get("id") == rids_on[0] and e["ph"] == "e")
        assert end["args"]["tokens"] == len(outs_on[rids_on[0]])
        assert obs.validate_chrome_trace(tr.to_chrome_trace()) == []

    def test_outputs_bit_identical_under_a_profiler_session(
            self, tiny_model, profiler):
        """Follow mode: nobody called enable_tracing(); the engine's spans
        and the request chains record because a profiler session is on."""
        from paddle_tpu.serving import GenRequest

        cfg = tiny_model.config
        prompts = _prompts(cfg, (20, 45, 33))

        def run():
            eng = _engine(tiny_model)
            rids = [eng.add_request(GenRequest(prompt_ids=p,
                                               max_new_tokens=6))
                    for p in prompts]
            outs = {}
            while eng.has_work():
                for o in eng.step():
                    outs[o.request_id] = o.output_ids
            return rids, outs

        _, outs_off = run()
        profiler.start()
        rids, outs_on = run()
        profiler.stop()
        assert outs_on == outs_off, "a profiler session changed the tokens"
        evs = obs.profiled_events()
        spans = set(_names(evs))
        assert {"serve.step", "serve.admit", "serve.prefill",
                "serve.dispatch", "serve.decode-chunk", "serve.readback",
                "serve.absorb"} <= spans
        for rid in rids:
            phases = [e["name"] for e in evs
                      if e.get("id") == rid and e["ph"] == "n"]
            assert phases.count("first-token") == 1
            assert (phases.index("queued") < phases.index("prefill")
                    < phases.index("first-token"))
        assert obs.validate_chrome_trace(
            {"traceEvents": evs}) == []

    def test_registry_metrics_flow(self, tiny_model):
        from paddle_tpu.serving import GenRequest

        cfg = tiny_model.config
        eng = _engine(tiny_model)
        for p in _prompts(cfg, (20, 45)):
            eng.add_request(GenRequest(prompt_ids=p, max_new_tokens=4))
        while eng.has_work():
            eng.step()
        snap = obs.registry().snapshot()
        assert snap["serve.requests"]["value"] == 2
        assert snap["serve.prefill_rows{use=prompt}"]["value"] == 20 + 45
        assert snap["serve.prefill_rows{use=pad}"]["value"] > 0
        assert snap["serve.ttft_ms"]["count"] == 2
        assert "serve.queue_depth" in snap
        assert snap["serve.decode_slot_steps{use=kept}"]["value"] == 2 * 3
        assert "serve.decode_slot_steps{use=empty}" in snap
        # unlabeled: engine not owned by a router
        assert snap["serve.requests"]["labels"] == {}
        assert snap["serve.prefill_rows{use=pad}"]["labels"] == {
            "use": "pad"}

    def test_router_failover_chain_exactly_once(self, tiny_model):
        """The rid's chain spans the failover: one begin (router submit),
        reroute marks from the kill, one end (survivor's emit)."""
        from paddle_tpu.distributed.fault_tolerance.injection import (
            FaultInjector, set_injector)
        from paddle_tpu.serving import GenRequest
        from paddle_tpu.serving.router import Router

        cfg = tiny_model.config
        tr = obs.enable_tracing()
        set_injector(FaultInjector(serve_kill_round=2,
                                   serve_kill_replica=0))
        try:
            r = Router()
            r.add_replica(_engine(tiny_model))
            r.add_replica(_engine(tiny_model))
            rids = [r.submit(GenRequest(prompt_ids=p, max_new_tokens=6))
                    for p in _prompts(cfg, (30, 50, 25, 40), seed=3)]
            outs = r.run_to_completion()
        finally:
            set_injector(None)
        assert r.stats["kills"] == 1
        assert sorted(o.request_id for o in outs) == sorted(rids)
        evs = tr.events()
        for rid in rids:
            chain = [e["ph"] for e in evs
                     if e.get("id") == rid and e["ph"] in "be"]
            assert chain == ["b", "e"], \
                f"{rid}: chain {chain} not exactly-once through failover"
        rerouted = [e["id"] for e in evs
                    if e["ph"] == "n" and e["name"] == "rerouted"]
        assert rerouted, "kill produced no reroute marks"
        # registry families split per replica via the router's stamp
        snap = obs.registry().snapshot()
        assert any(k.startswith("serve.requests{replica=")
                   for k in snap)
        assert obs.validate_chrome_trace(tr.to_chrome_trace()) == []


# -- MPMD bubble cross-check -------------------------------------------------


class TestBubbleCrosscheck:
    def test_trace_agrees_with_analytic_pp2(self):
        """What the cross-check is for — every op span in its emitted
        tick, co-scheduled as the linter certified — judged free of the
        machine's load: the executor's own op stream (five traced steps of
        the threaded pipeline) is replayed with each span's duration set to
        the measured cost of its (kind, stage), and must then price to the
        analytic bubble exactly.  A span the executor dropped or put in
        another tick moves it.  The wall-clock residual itself
        (``rel_err``) is what ``bench.py --preset obs`` reports from a
        quiet machine."""
        from paddle_tpu.analysis.schedule_lint import dag_bubble_fraction
        from paddle_tpu.distributed.parallel.mpmd import (
            mpmd_bubble_crosscheck, trace_bubble_from_events)

        S, M, steps = 2, 4, 5
        tr = obs.enable_tracing(clear=True)
        r = mpmd_bubble_crosscheck(n_stages=S, n_micro=M, dim=256, mb=32,
                                   steps=steps, schedule="ZB")
        ops = [e for e in tr.events()
               if e.get("cat") == "mpmd.op" and e.get("ph") == "X"]
        assert r["n_op_spans"] > 0 and r["analytic_bubble"] > 0
        # every op of every step: the five, and the compiling one before them
        assert len(ops) == (steps + 1) * r["n_op_spans"]
        table = trace_bubble_from_events(ops, S)["cost_table"]
        assert all(c > 0 for c in table.values())

        def priced(events):
            return trace_bubble_from_events(
                [{**e, "dur": table[(e["args"]["kind"], e["args"]["stage"])]}
                 for e in events], S)["fraction"]

        analytic = dag_bubble_fraction(
            r["schedule"], S, M, cost_of=lambda k, s: table[(k, s)])
        assert priced(ops) == pytest.approx(analytic["fraction"], rel=1e-9)
        # the replay has teeth.  The victim is an op of the lighter stage of
        # a tick both stages work in: dropped, its stage's busy time falls;
        # run in a tick of its own after the last, the wall grows by its cost
        ident = lambda e: tuple(e["args"][k]
                                for k in ("tick", "stage", "kind", "micro"))
        load = {}
        for tick, stage, kind, _ in set(map(ident, ops)):
            row = load.setdefault(tick, [0.0] * S)
            row[stage] += table[(kind, stage)]
        tick, row = next((t, r) for t, r in sorted(load.items()) if all(r))
        victim = next(i for i in map(ident, ops)
                      if i[:2] == (tick, row.index(min(row))))
        dropped = [e for e in ops if ident(e) != victim]
        late = [{**e, "args": {**e["args"], "tick": max(load) + 1}}
                if ident(e) == victim else e for e in ops]
        for broken in (dropped, late):
            assert priced(broken) != pytest.approx(analytic["fraction"],
                                                   rel=1e-3)

    @pytest.mark.slow
    def test_trace_agrees_with_analytic_pp4(self):
        from paddle_tpu.distributed.parallel.mpmd import \
            mpmd_bubble_crosscheck

        r = mpmd_bubble_crosscheck(n_stages=4, n_micro=8, dim=256, mb=32,
                                   steps=5, schedule="ZB")
        assert r["rel_err"] <= 0.15, r

    def test_dag_bubble_unit_costs_match_lockstep_intuition(self):
        """With unit costs the DAG price of the ZB schedule reproduces the
        known shape: bubble shrinks as M grows at fixed S."""
        from paddle_tpu.analysis.schedule_lint import dag_bubble_fraction

        f4 = dag_bubble_fraction("ZB", 4, 4)["fraction"]
        f16 = dag_bubble_fraction("ZB", 4, 16)["fraction"]
        assert 0 < f16 < f4 < 1

    def test_trace_bubble_rejects_empty_stream(self):
        from paddle_tpu.distributed.parallel.mpmd import \
            trace_bubble_from_events

        with pytest.raises(ValueError):
            trace_bubble_from_events([], 2)

    def test_stage_kill_dumps_flight_postmortem(self, tmp_path,
                                                monkeypatch):
        """FLAGS_ft_inject_stage_kill path: the MPMD replan leaves a
        flight artifact naming the victim and the recovery."""
        import jax.numpy as jnp

        from paddle_tpu.distributed.fault_tolerance.injection import (
            FaultInjector, set_injector)
        from paddle_tpu.distributed.parallel.mpmd import MPMDPipeline

        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path))
        S, M, dim, mb = 2, 4, 32, 8
        rng = np.random.default_rng(0)
        sp = jnp.asarray(rng.normal(size=(S, dim, dim)), jnp.float32) * 0.05
        d = jnp.asarray(rng.normal(size=(M, mb, dim)), jnp.float32)
        pipe = MPMDPipeline(lambda sp, x: jnp.tanh(x @ sp[0]), S, M,
                            last_fn=lambda lp, y, _d:
                            ((y @ lp) ** 2).mean() / M,
                            first_fn=lambda fp, x: x @ fp,
                            schedule="1F1B")
        fp = jnp.asarray(rng.normal(size=(dim, dim)), jnp.float32) * 0.05
        lp = jnp.asarray(rng.normal(size=(dim, 1)), jnp.float32) * 0.05
        set_injector(FaultInjector(stage_kill_tick=1, stage_kill_stage=1))
        try:
            pipe.step(sp, fp, lp, d)
        finally:
            set_injector(None)
        path = obs.last_flight_dump()
        assert path and path.startswith(str(tmp_path))
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "stage-kill"
        assert doc["victim"] == "stage 1"
        names = [e["name"] for e in doc["events"]]
        assert "inject.stage-kill" in names
        assert "mpmd.stage-kill" in names
        assert "mpmd.replan" in names
        assert names.index("mpmd.stage-kill") < names.index("mpmd.replan")
