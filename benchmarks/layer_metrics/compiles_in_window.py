"""Programs compiled or loaded from the persistent cache inside the measured
window (JAX's ``/jax/compilation_cache/cache_misses`` and ``cache_hits``
events and its backend-compile durations).  Should be 0: every shape is
warmed in set-up."""


def read(red, run):
    c = run["compiled"]
    return float(max(c["compiles"], c["hits"] + c["misses"]))
