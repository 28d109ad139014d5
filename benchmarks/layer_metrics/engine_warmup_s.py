"""Seconds ``Engine.warmup()`` took, by the engine's own gauge
``serve.warmup_s``.  An earlier line gives ``serve.warmup_programs``, the
engine programs it ran, to be held against ``setup_compiles`` on the run's
``end_to_end`` line: what set-up loads or compiles beyond the engine's own
ladder."""
from benchmarks import harness


def read(red, run):
    from paddle_tpu import obs

    snap = obs.registry().snapshot()
    took = snap.get("serve.warmup_s")
    if took is None:               # an engine that keeps no such gauge
        return None
    harness.say(engine_warmup_programs=snap.get(
        "serve.warmup_programs", {}).get("value"))
    return took["value"]
