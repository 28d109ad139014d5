"""How long a request waits inside the engine before its first prefill is
dispatched: from the ``queued`` mark of ``add_request`` to the request's
first ``prefill`` / ``prefill-chunk`` mark, over the requests queued inside
the traced window (those still waiting at its end are left out); median.
The wait for a slot, for blocks and for the running ``step()`` to return."""
from benchmarks import harness, stats
from benchmarks.layer_metrics import _program_spans


def read(red, run):
    tied = _program_spans.tie(red)
    if tied is None:
        return None
    waits = tied.elapsed_ms(("queued",), _program_spans.DISPATCHED)
    harness.say(**stats.describe("engine_queue_wait_ms", waits, "ms"))
    return stats.median(waits)[0]
