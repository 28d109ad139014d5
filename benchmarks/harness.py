"""What every runner shares: finding a cell's files by name, the device
check, the compile cache, the compile counter, the measured window with its
optional profiler trace, and the last line of output.

Nothing here names a cell, a configuration or a metric: they are files
(``workloads/``, ``configs/``, ``traffic/``, ``layer_metrics/``) and entries
of ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# host spans the runners record; the idle gaps of the device are named by them
SPANS = ("make_batch", "train_step", "read_loss", "engine.step",
         "add_request", "loadgen.sleep")


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _with_overrides(d, rehearse):
    over = d.pop("rehearse", {})
    if rehearse:
        for k, v in over.items():
            if isinstance(v, dict) and isinstance(d.get(k), dict):
                d[k] = {**d[k], **v}
            else:
                d[k] = v
    return d


def load_cell(name, rehearse=False):
    """The cell's file and the configuration and traffic files it names.  A
    ``rehearse`` group in a file holds the tiny sizes of the CPU rehearsal
    and is dropped otherwise."""
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    config = _with_overrides(load_json("configs", f"{cell['config']}.json"),
                             rehearse)
    traffic = _with_overrides(load_json("traffic", f"{cell['traffic']}.json"),
                              rehearse)
    return cell, config, traffic


def cell_metrics(cell_name):
    """``(end_to_end, per_layer)`` entries of ``BENCHMARK.json`` that this
    cell reports: those with no ``workloads`` key and those that list it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or cell_name in m["workloads"]]

    return mine(bench["end_to_end"]), mine(bench["per_layer"])


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def layer_reader(metric_name):
    """The reader of a per-layer metric: ``layer_metrics/<name>.py``, where a
    name ``quantity.variant`` (one quantity split by the end-to-end metric it
    moves) is read by ``layer_metrics/quantity.py``."""
    return load_module("layer_metrics", metric_name.split(".", 1)[0]).read


REHEARSAL = False     # set by run.py: a rehearsal prints no timing or rate


def say(**fields):
    """An earlier line of output: one JSON object, never the last line.  In
    a rehearsal, lines that carry a timing or a rate are dropped: a number
    from the CPU is never printed under the name of a device metric."""
    if REHEARSAL and ("timing" in fields or "values" in fields):
        return
    print(json.dumps(fields), flush=True)


# ----------------------------------------------------------------- device --

def require_device(chips, rehearse):
    """The devices the cell runs on.  Without ``--rehearse`` anything but a
    TPU with at least ``chips`` chips ends the run with code 2 and no
    result; with it anything but the CPU does."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit("--rehearse is for JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        print(f"the benchmark needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"the cell needs {chips} chip(s); JAX found {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def enable_cache():
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` if
    set, else at one fixed path inside the checkout.  Off on the CPU, where
    jaxlib crashes reading entries another process wrote."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts what JAX compiles: persistent-cache hits and misses, and
    backend compilations (a hit loads, a miss compiles; both stall)."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == self.MISS:
            self.misses += 1
        elif event == self.HIT:
            self.hits += 1

    def _duration(self, event, _secs, **_kw):
        if event == self.COMPILE:
            self.compiles += 1

    def snapshot(self):
        return {"hits": self.hits, "misses": self.misses,
                "compiles": self.compiles}


def peak_memory(devices):
    """Peak bytes in use on the fullest device, or None where the backend
    does not report it (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------- window --

class Window:
    """The measured window.  A runner calls ``begin()`` when set-up is over,
    ``tick()`` once per loop iteration (after a step's result reached the
    host) and ``end()`` when its last work is done.

    Without a trace the window stays open for ``seconds``.  With one it runs
    ``lead_s`` untraced, starts the profiler, lets ``settle`` iterations pass
    (starting the profiler stalls the host, and the device drains meanwhile),
    marks ``trace_s`` of steady work with the span ``bench.window``, stops
    the profiler and closes."""

    def __init__(self, seconds, trace, counter, lead_s=2.0, trace_s=3.0,
                 settle=2, keep_dir=None):
        self.seconds, self.trace, self.counter = seconds, trace, counter
        self.lead_s = min(lead_s, seconds / 2)
        self.trace_s = min(trace_s, seconds)
        self.settle = settle
        self.keep_dir = keep_dir
        self.xplane = None
        self._state = "lead" if trace else "plain"
        self._stack = contextlib.ExitStack()
        self._ticks = 0
        self.on_mark = []      # callbacks at the start and end of the mark

    def begin(self):
        self.t0 = time.perf_counter()
        self._before = self.counter.snapshot()
        return self.t0

    def marked(self):
        """Whether the traced, marked part of the window is running."""
        return self._state == "marked"

    def tick(self):
        """True while the runner should go on offering work."""
        import jax

        now = time.perf_counter()
        if self._state == "plain":
            return now - self.t0 < self.seconds
        if self._state == "lead":
            if now - self.t0 >= self.lead_s:
                self._dir = self.keep_dir or self._stack.enter_context(
                    tempfile.TemporaryDirectory(prefix="bench_trace_"))
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self._dir, profiler_options=opts)
                self._state, self._ticks = "settle", 0
            return True
        if self._state == "settle":
            self._ticks += 1
            if self._ticks >= self.settle:
                from .tracered import WINDOW_SPAN

                self._mark = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                self._mark.__enter__()
                self._t_mark = time.perf_counter()
                self._state = "marked"
                for cb in self.on_mark:
                    cb("start")
            return True
        if self._state == "marked":
            if now - self._t_mark < self.trace_s:
                return True
            for cb in self.on_mark:
                cb("end")
            self._mark.__exit__(None, None, None)
            self.mark_s = time.perf_counter() - self._t_mark
            jax.profiler.stop_trace()
            from .tracered import find_xplane

            self.xplane = find_xplane(self._dir)
            self._state = "closed"
        return False

    def end(self):
        """Close the window; returns what was compiled inside it."""
        if self._state in ("settle", "marked"):    # the runner ran out of work
            import jax

            if self._state == "marked":
                self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._state = "closed"
        after = self.counter.snapshot()
        return {k: after[k] - self._before[k] for k in after}

    def reduce(self):
        """The reduced trace, or None without one; temporary files go."""
        from . import tracered

        try:
            if self.xplane is None:
                return None
            return tracered.reduce(self.xplane, SPANS)
        finally:
            self._stack.close()


# ------------------------------------------------------------------ result --

def last_line(correct, attempted, failed, metrics, devices, memory_peak,
              busy=None, breakdown=None):
    """The contract's object, as the last line of standard output."""
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    if busy is not None:
        device["busy_s"], device["window_s"] = busy
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    print(json.dumps(out), flush=True)
