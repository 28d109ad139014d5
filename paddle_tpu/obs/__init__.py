"""Runtime observability: span tracer, metrics registry, flight recorder.

The static analyzers (:mod:`paddle_tpu.analysis`) predict what a run
*should* do — liveness predicts peak HBM, ``schedule_lint`` predicts the
pipeline bubble, ``overlap`` predicts exposed collective bytes.  This
package records what a run actually *did*, cheap enough to leave wired
into the runtimes:

- :mod:`.trace` — structured span tracer.  Thread-safe, monotonic-clock
  spans with categories and args, nestable, exported as Chrome/Perfetto
  ``trace_event`` JSON (open the dump in ``ui.perfetto.dev``).  Disabled
  is the default and costs one module-global read and one 24-ns call per
  call site — no allocation, no locking (``tests/test_obs.py`` pins
  both).  While a JAX profiler session is active the spans record without
  being asked and are also entered as ``TraceAnnotation``s, so they lie
  in the capture on the device trace's clock
  (``obs.profiled_events()`` reads the session's buffer afterwards).
- :mod:`.metrics` — metrics registry: counters, gauges and fixed-bucket
  histograms with p50/p95/p99, labeled families
  (``serve.decode_gap_ms{replica=0}``), snapshot-to-JSON round-trippable.
- :mod:`.flight` — flight recorder: a bounded ring buffer of recent
  events (plus span completions when tracing is on), ALWAYS on, dumped
  to a JSON postmortem artifact on every injected-fault path so chaos
  tests can assert the victim and the recovery sequence.

Naming catalogue (events, spans and metrics share one namespace scheme —
``<layer>.<noun-or-verb>``, label args carry the identity):

===========================  ====================================================
name                         producer / meaning
===========================  ====================================================
``mpmd.op``                  span cat: one F/B/W op (args tick/stage/micro/kind)
``mpmd.xfer-post``           span: ``jax.device_put`` posted (args src/dst stage)
``mpmd.xfer-due``            instant: due-tick consume of a posted transfer
``mpmd.steps``               counter {schedule,pp}: executor steps completed
``mpmd.ticks`` etc.          gauges {schedule,pp}: cumulative executor stats
                             (ticks, transfers_posted, transfer_bytes, replans)
``mpmd.stage-kill``          flight: injected stage failure (victim stage, tick)
``mpmd.replan``              flight: survivors re-plan after a stage kill
``serve.request``            async span chain: one request queued→admitted→
                             prefill(-chunk)→first-token→decode-round→emitted
``serve.step``               span: one ``Engine.step()``
``serve.admit``              span: ``_admit()`` (args admitted, waiting)
``serve.prefill`` etc.       spans: the jitted call only (``serve.prefill``
                             args bucket, n, tokens; ``serve.prefill-chunk``
                             args bucket, final, tokens; ``serve.decode-chunk``)
``serve.dispatch``           span: all of ``_dispatch_chunk`` (args k, staged,
                             live, kept, width): staging, the call, the ledger
``serve.readback``           span: the host blocked reading the token buffers
``serve.absorb``             span: the ledger walk and emits (args tokens,
                             finished)
``train.step``               span: one ``TrainStep.__call__``
``serve.queue_depth``        gauge {replica}: waiting requests after a round
``serve.requests``           counter {replica}: requests emitted
``serve.prefix_hit_blocks``  counter {replica}: prompt blocks served from cache
``serve.prefill_rows``       counter {use,replica}: rows the prefill programs
                             ran: ``prompt`` (a prompt token) or ``pad`` (the
                             bucket's padding)
``serve.decode_slot_steps``  counter {use,replica}: a decode chunk's k x
                             max_batch slot-steps: ``kept`` (inside a request's
                             budget), ``tail`` (run past it to the chunk's
                             end), ``prefilling`` (slot mid-chunked-prefill),
                             ``empty`` (no request); ``cut`` (after an eos,
                             moved from ``kept`` when the tokens are read)
``serve.decode_gap_ms``      histogram {replica}: decode-visible gap per chunk
``serve.ttft_ms``            histogram {replica}: queued→first token on the host
``serve.queue_wait_ms``      histogram {replica}: queued→dispatch of the first
                             prefill (the wait for a slot, blocks, the step)
``serve.warmup_s``           gauge {replica}: seconds of ``Engine.warmup()``
``serve.warmup_programs``    gauge {replica}: engine programs ``warmup()`` ran
``moe.steps`` etc.           counters {replica}: what the expert layers routed, summed
                             over the layers: decode steps counted, ``moe.rows``,
                             ``moe.experts_touched``, ``moe.max_expert_rows``; the
                             same of prefill calls (``moe.prefill_*``) and
                             ``mla.prefill_kilo_pairs``.  Counted on the device,
                             read inside ``serve.readback`` (``LatentKV``)
``moe.grouped_mm_programs``  counter {tm}: calls of the grouped expert product
                             traced under each row tile (``kernels/
                             grouped_matmul.py``); nothing in a compiled program
``mla.decode_programs``      counter {ring}: calls of the latent decode kernel
                             traced with each ring of block buffers
                             (``kernels/mla_attention.py``); nothing in a
                             compiled program
``cache.counters``           span: that read; args carry the running totals
``cache.latent_bytes_per_token``  gauge {replica}: one latent row, a layer
``cache.latent_blocks_live``  gauge {replica}: blocks live slots hold
``moe.route``, ``moe.experts``  spans where the model runs eagerly, named scopes
``mla.prefill_attn``,        inside jitted programs: the router, the grouped
``mla.decode_attn``          expert products, expanded and absorbed attention
``serve.kill``               flight: injected replica kill (victim replica)
``serve.reroute``            flight: a harvested request re-placed after a kill
``store.leader-elected``     flight: replica won an election (term)
``store.step-down``          flight: leader stepped down (reason)
``store.leader-kill``        flight: injected leader kill (victim replica)
``store.catch-up``           flight: restarted replica caught up from leader
``ft.lease-renew``           flight: heartbeat lease renewed (rank)
``ft.heartbeat-miss``        flight: detector saw a lease expire (rank)
``ft.epoch-bump``            flight: membership epoch published (alive/dead)
``rdv.generation-invalidated``  flight: rendezvous generation declared dead
===========================  ====================================================
"""

from .trace import (Tracer, enable_tracing, disable_tracing, tracer,
                    explicit_tracer, trace_enabled, span, instant,
                    profiled_events, validate_chrome_trace)
from .metrics import (Counter, Gauge, Histogram, Registry, registry,
                      reset_metrics)
from .flight import (FlightRecorder, flight, flight_event, dump_flight,
                     last_flight_dump)

__all__ = [
    "Tracer", "enable_tracing", "disable_tracing", "tracer",
    "explicit_tracer", "trace_enabled", "span", "instant",
    "profiled_events",
    "Counter", "Gauge", "Histogram", "Registry", "registry",
    "reset_metrics",
    "FlightRecorder", "flight", "flight_event", "dump_flight",
    "last_flight_dump",
]
