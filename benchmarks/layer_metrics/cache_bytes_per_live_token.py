"""Cache bytes held for one live token: (live blocks x a block's bytes +
live slots x a slot's rings) / live tokens, from the cache backend's gauges
(``cache.kv_blocks_live``, ``cache.kv_bytes_per_token``,
``cache.window_slots_live``, ``cache.window_bytes_per_slot``: means over the
readbacks of the traced window) and the runner's count of the tokens the
live requests hold.  For a stack of two full layers and five window layers
it is the full layers' 5,120 B (block rounding on top) plus 3.28 MB over
the context; a window layer that fell back to a whole chain of blocks would
read seven times the first term."""
from benchmarks.layer_metrics import _swa_moe


def read(red, run):
    g = _swa_moe.gauges(red)
    live = run.get("live_kv_tokens")
    if g is None or not live:
        return None
    block = g["cache.kv_bytes_per_token"] * run["config"]["engine"].get(
        "block_size", 128)
    return (g["cache.kv_blocks_live"] * block
            + g["cache.window_slots_live"] * g["cache.window_bytes_per_slot"]
            ) / live
