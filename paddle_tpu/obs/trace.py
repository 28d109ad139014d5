"""Structured span tracer with Chrome/Perfetto ``trace_event`` export.

Design constraints, in priority order:

1. **Disabled is free.**  Tracing is off unless :func:`enable_tracing`
   ran or a JAX profiler session is active (below); every call site goes
   through the module-level :func:`span` / :func:`instant` fast path,
   which is one global read, one ``is None`` test and one call of
   ``TraceAnnotation.is_enabled()`` (24 ns) before returning a shared
   no-op singleton — no allocation, no lock acquisition, nothing
   appended.  ``tests/test_obs.py`` pins both properties (tracemalloc
   diff == 0, poisoned-lock doesn't trip).
2. **Enabled never perturbs values.**  Spans record wall time
   (``time.perf_counter_ns``) and host-side metadata only; they never
   touch program values, so traced runs are bit-identical to untraced
   ones.  (Runtimes that *time* device work — the MPMD executor — may
   add a ``block_until_ready`` per op when tracing is on; that forces
   completion order, not values.)
3. **Thread-safe without a hot-path lock.**  Event recording is a
   single ``list.append`` (atomic under the GIL); the module lock
   guards only install/export/clear.

Export is the Chrome ``trace_event`` JSON object format
(``{"traceEvents": [...]}``), which ``ui.perfetto.dev`` and
``chrome://tracing`` open directly:

- complete events (``ph: "X"``) for spans — ``ts``/``dur`` in µs;
- instants (``ph: "i"``);
- legacy async events (``ph: "b"/"n"/"e"``, keyed by ``id`` + ``cat``)
  for request lifecycle chains that interleave across rounds;
- metadata (``ph: "M"``) naming per-stage / per-replica timeline rows.

Extra top-level keys ride along (the spec allows them): ``dump()``
attaches the metrics-registry snapshot under ``"metrics"``.

**Following the profiler.**  While a JAX profiler session is active
(``jax.profiler.start_trace``, the profiler server, a benchmark's
``--trace 1`` — whoever started it) the fast path records as if tracing
were enabled, into a buffer of the session's own, and every span is also
entered as a ``jax.profiler.TraceAnnotation`` of the same name: it lies
in the capture's ``/host:CPU`` plane on the device trace's clock, above
the device's lines.  The session's buffer stays readable after the
session stops (:func:`profiled_events`), is replaced when the next
session starts, and nothing is recorded between sessions.  A consumer
that changes what it *does* when traced (the MPMD executor's
``block_until_ready`` per op) asks :func:`explicit_tracer`, which does
not follow the profiler.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .flight import flight as _flight

__all__ = [
    "Tracer", "enable_tracing", "disable_tracing", "tracer",
    "explicit_tracer", "trace_enabled", "span", "instant",
    "profiled_events",
]

_profiling = TraceAnnotation.is_enabled    # is a profiler session active?

# guards tracer install/export/clear ONLY — the disabled fast path and the
# per-event append never acquire it (the no-lock micro-test poisons it)
_lock = threading.Lock()
_tracer: Optional["Tracer"] = None       # installed by enable_tracing()
_session: Optional["Tracer"] = None      # the current or last profiler session's
_in_session = False                      # was a session active at the last call


class _NoopSpan:
    """Shared do-nothing span; returned by the disabled fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **args):
        pass

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("_tr", "name", "cat", "tid", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 tid: Optional[int], args: Optional[dict]):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self._t0 = 0
        self._ann = None

    def set(self, **args):
        """Args known only when the work is done (counts, outcomes)."""
        self.args = {**self.args, **args} if self.args else args

    def __enter__(self):
        if _profiling():
            # the same span on the device trace's clock, for whoever opens
            # the capture; args known at entry ride along as its stats
            self._ann = TraceAnnotation(self.name, **(self.args or {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._tr._complete(self.name, self.cat, self.tid, self.args,
                           self._t0, t1)
        return False


class Tracer:
    """One event buffer; ts are µs since it was made (by
    :func:`enable_tracing`, or at the first call inside a profiler
    session)."""

    def __init__(self):
        self._origin_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        self._events: List[Dict[str, Any]] = []
        self._chains: set = set()          # lifecycle ids with an open "b"

    # -- clock ---------------------------------------------------------------

    def _ts(self, t_ns: Optional[int] = None) -> float:
        if t_ns is None:
            t_ns = time.perf_counter_ns()
        return (t_ns - self._origin_ns) / 1000.0

    def _tid(self, tid: Optional[int]) -> int:
        if tid is not None:
            return int(tid)
        return threading.get_ident() & 0x7FFFFFFF

    # -- spans / instants ------------------------------------------------------

    def span(self, name: str, cat: str = "", tid: Optional[int] = None,
             args: Optional[dict] = None) -> _Span:
        return _Span(self, name, cat, tid, args)

    def _complete(self, name, cat, tid, args, t0_ns, t1_ns):
        ev = {"name": name, "cat": cat or "default", "ph": "X",
              "ts": self._ts(t0_ns), "dur": (t1_ns - t0_ns) / 1000.0,
              "pid": self._pid, "tid": self._tid(tid)}
        if args:
            ev["args"] = args
        self._events.append(ev)          # atomic under the GIL
        _flight().record_span(name, cat, ev["dur"], args)

    def instant(self, name: str, cat: str = "", tid: Optional[int] = None,
                args: Optional[dict] = None) -> None:
        ev = {"name": name, "cat": cat or "default", "ph": "i", "s": "t",
              "ts": self._ts(), "pid": self._pid, "tid": self._tid(tid)}
        if args:
            ev["args"] = args
        self._events.append(ev)

    # -- async lifecycle chains ------------------------------------------------
    # Legacy async events (b/n/e) keyed by (cat, id): one chain per request
    # id, begun exactly once no matter how many layers see the request (the
    # router AND its engines both mark phases on the same chain).

    def lifecycle_begin(self, chain_id: str, name: str = "request",
                        cat: str = "serve.request",
                        args: Optional[dict] = None) -> bool:
        """Open the chain if this id was never begun; returns True when this
        call actually opened it (exactly-once across producers)."""
        if chain_id in self._chains:
            return False
        self._chains.add(chain_id)
        ev = {"name": name, "cat": cat, "ph": "b", "id": chain_id,
              "ts": self._ts(), "pid": self._pid, "tid": self._tid(None)}
        if args:
            ev["args"] = args
        self._events.append(ev)
        return True

    def lifecycle_mark(self, chain_id: str, phase: str,
                       cat: str = "serve.request",
                       args: Optional[dict] = None) -> None:
        ev = {"name": phase, "cat": cat, "ph": "n", "id": chain_id,
              "ts": self._ts(), "pid": self._pid, "tid": self._tid(None)}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def lifecycle_end(self, chain_id: str, name: str = "request",
                      cat: str = "serve.request",
                      args: Optional[dict] = None) -> bool:
        """Close the chain (only if it was begun and not yet closed)."""
        if chain_id not in self._chains:
            return False
        self._chains.discard(chain_id)
        ev = {"name": name, "cat": cat, "ph": "e", "id": chain_id,
              "ts": self._ts(), "pid": self._pid, "tid": self._tid(None)}
        if args:
            ev["args"] = args
        self._events.append(ev)
        return True

    # -- metadata ---------------------------------------------------------------

    def thread_name(self, tid: int, name: str) -> None:
        self._events.append({"name": "thread_name", "ph": "M",
                             "pid": self._pid, "tid": int(tid),
                             "args": {"name": name}})

    def process_name(self, name: str) -> None:
        self._events.append({"name": "process_name", "ph": "M",
                             "pid": self._pid, "tid": 0,
                             "args": {"name": name}})

    # -- export -------------------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        with _lock:
            return list(self._events)

    def clear(self) -> None:
        with _lock:
            self._events = []
            self._chains = set()

    def to_chrome_trace(self, metrics: Optional[dict] = None) -> dict:
        doc: Dict[str, Any] = {"traceEvents": self.events(),
                               "displayTimeUnit": "ms"}
        if metrics is not None:
            doc["metrics"] = metrics
        return doc

    def dump(self, path: str, metrics: Optional[dict] = None) -> str:
        doc = self.to_chrome_trace(metrics=metrics)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# -- module-level fast path ------------------------------------------------------


def enable_tracing(clear: bool = True) -> Tracer:
    """Install (or return) the process tracer.  ``clear=False`` keeps the
    existing buffer when tracing is already on."""
    global _tracer
    with _lock:
        if _tracer is None or clear:
            _tracer = Tracer()
        return _tracer


def disable_tracing() -> None:
    global _tracer
    with _lock:
        _tracer = None


def _follow() -> Optional[Tracer]:
    """The buffer of the active profiler session, or None outside one.  A
    session is noticed at the first call inside it (the buffer of the one
    before goes then) and its end at the first call after it: two sessions
    with no call between them read as one."""
    global _session, _in_session
    if not _profiling():
        if _in_session:
            _in_session = False
        return None
    if not _in_session:
        with _lock:
            if not _in_session:
                _session = Tracer()
                _in_session = True
    return _session


def tracer() -> Optional[Tracer]:
    """The live tracer, or None when nothing records: the one
    :func:`enable_tracing` installed, else the buffer of the active
    profiler session.  Hot loops read this ONCE per step and branch, so
    the disabled cost is one global read and one 24-ns call per step, not
    per op."""
    return _tracer or _follow()


def explicit_tracer() -> Optional[Tracer]:
    """The tracer :func:`enable_tracing` installed, or None: for a consumer
    that must not change what it does because someone is profiling."""
    return _tracer


def trace_enabled() -> bool:
    return _tracer is not None


def profiled_events() -> List[Dict[str, Any]]:
    """What the current or last profiler session recorded (empty if there
    was none, or if :func:`enable_tracing` took the events instead)."""
    _follow()                      # a reader after the session ends it
    s = _session
    return s.events() if s is not None else []


def span(name: str, cat: str = "", tid: Optional[int] = None,
         args: Optional[dict] = None):
    """``with obs.span("name", cat, args={...}) as sp:`` — no-op singleton
    when nothing records (no allocation, no locking).  ``sp.set(k=v)`` adds
    args known only at the end."""
    t = _tracer or _follow()
    if t is None:
        return _NOOP_SPAN
    return t.span(name, cat, tid=tid, args=args)


def instant(name: str, cat: str = "", tid: Optional[int] = None,
            args: Optional[dict] = None) -> None:
    t = _tracer or _follow()
    if t is None:
        return
    t.instant(name, cat, tid=tid, args=args)


def validate_chrome_trace(doc: dict) -> List[str]:
    """Schema check for the Chrome trace_event object format (the subset
    Perfetto's legacy JSON importer requires).  Returns a list of
    problems — empty means valid.  Used by tests and obs_gate."""
    problems: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["missing traceEvents key"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    open_chains: Dict[tuple, int] = {}
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i} not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "b", "n", "e", "M", "C"):
            problems.append(f"event {i}: unknown ph {ph!r}")
            continue
        for key in ("name", "pid"):
            if key not in ev:
                problems.append(f"event {i} ({ph}): missing {key}")
        if ph == "X":
            if "ts" not in ev or "dur" not in ev:
                problems.append(f"event {i} (X): missing ts/dur")
            elif ev["dur"] < 0:
                problems.append(f"event {i} (X): negative dur")
        elif ph in ("i", "b", "n", "e"):
            if "ts" not in ev:
                problems.append(f"event {i} ({ph}): missing ts")
        if ph in ("b", "n", "e"):
            if "id" not in ev or "cat" not in ev:
                problems.append(f"event {i} ({ph}): async without id/cat")
                continue
            key = (ev["cat"], ev["id"])
            if ph == "b":
                open_chains[key] = open_chains.get(key, 0) + 1
                if open_chains[key] > 1:
                    problems.append(f"event {i}: duplicate begin for {key}")
            elif ph == "e":
                if open_chains.get(key, 0) < 1:
                    problems.append(f"event {i}: end without begin for {key}")
                else:
                    open_chains[key] -= 1
    for key, n in open_chains.items():
        if n > 0:
            problems.append(f"async chain {key} never ended")
    return problems
