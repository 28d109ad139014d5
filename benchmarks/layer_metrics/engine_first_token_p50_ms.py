"""From the dispatch of a request's first prefill to its ``first-token`` mark
(the read-back that brought its first token to the host), over the requests
with both inside the traced window; median.  With
``engine_queue_wait_p50_ms`` and the load generator's lateness it adds up to
the time to first token the benchmark measures from outside."""
from benchmarks import harness, stats
from benchmarks.layer_metrics import _program_spans


def read(red, run):
    tied = _program_spans.tie(red)
    if tied is None:
        return None
    took = tied.elapsed_ms(_program_spans.DISPATCHED, ("first-token",))
    harness.say(**stats.describe("engine_first_token_ms", took, "ms"))
    return stats.median(took)[0]
