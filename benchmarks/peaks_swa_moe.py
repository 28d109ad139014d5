"""Operation and byte counts of the window/full attention, sparse-expert
decoder (``configs/mimo_v2_flash_d7_serve.json``'s family), for the roofline
metrics of its cells.  ``peaks.py`` holds the chip's published peaks;
neither it nor ``peaks_mla_moe.py`` is edited.

Every count is of the mathematics at the published widths: rows times
widths, the weights of the held experts that GOT a row, a key's 192 and a
value's 128 values for every live token of a full layer, the rings of the
live slots, the (query, key) pairs INSIDE the mask.  Nothing the
implementation pads to, masks out or visits in vain is counted.
"""
from __future__ import annotations


def layer_kinds(cfg: dict):
    """``(full layers, window layers)`` of the depth as run."""
    kinds = cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]
    return kinds.count(0), kinds.count(1)


def expert_layers(cfg: dict) -> int:
    return sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])


def attention_params(cfg: dict, window: bool) -> int:
    h, d, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hk = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    hidden = cfg["hidden_size"]
    return (hidden * h * d + hidden * hk * (d + dv) + h * dv * hidden
            + (h * 2 if cfg["add_swa_attention_sink_bias" if window else
                            "add_full_attention_sink_bias"] else 0))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def non_expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Every weight a decode step reads whatever the routing: attention and
    norms of every layer, the dense layers' feed-forward, the float32 router
    (over ALL experts) of every expert layer, the final norm and the head
    (the embedding rows read are negligible)."""
    hidden = cfg["hidden_size"]
    n_full, n_window = layer_kinds(cfg)
    n_moe = expert_layers(cfg)
    n_dense = cfg["num_hidden_layers"] - n_moe
    routed = cfg["published"]["n_routed_experts"]
    return ((n_full * attention_params(cfg, False)
             + n_window * attention_params(cfg, True)
             + cfg["num_hidden_layers"] * 2 * hidden
             + n_dense * 3 * hidden * cfg["intermediate_size"]
             + hidden + hidden * cfg["vocab_size"]) * itemsize
            + n_moe * (hidden * routed + routed) * 4)


def kv_token_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Cache bytes a token a FULL layer: keys and values, published widths."""
    return (cfg["num_key_value_heads"] * (cfg["head_dim"] + cfg["v_head_dim"])
            * itemsize)


def ring_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One slot's ring of one WINDOW layer."""
    return (cfg["sliding_window"] * cfg["swa_num_key_value_heads"]
            * (cfg["head_dim"] + cfg["v_head_dim"]) * itemsize)


def decode_step_bytes(cfg: dict, experts_touched: float, live_tokens: float,
                      live_slots: float, itemsize: int = 2) -> float:
    """Bytes one decode step must read: the non-expert weights once, the
    weights of the held experts that got a row (``experts_touched``: the
    mean over the expert layers), the full layers' keys and values of every
    live token, the window layers' rings of every live slot (a slot whose
    context is shorter than the window holds less; the cell's contexts are
    all longer)."""
    n_full, n_window = layer_kinds(cfg)
    return (non_expert_bytes(cfg, itemsize)
            + expert_layers(cfg) * experts_touched * expert_params(cfg)
            * itemsize
            + live_tokens * n_full * kv_token_bytes(cfg, itemsize)
            + live_slots * n_window * ring_bytes(cfg, itemsize))


def paged_decode_attn_bytes(cfg: dict, live_tokens: float, batch: int,
                            itemsize: int = 2) -> float:
    """Bytes one call of the full layers' decode kernel must move: the live
    tokens' keys and values of one layer, the queries in and the outputs out
    for every head of every slot."""
    h = cfg["num_attention_heads"]
    return (live_tokens * kv_token_bytes(cfg, itemsize)
            + batch * h * (cfg["head_dim"] + cfg["v_head_dim"]) * itemsize)


def prefill_attn_least_s(cfg: dict, pairs: float, tokens: float,
                         window: bool, peaks: dict,
                         itemsize: int = 2) -> float:
    """Least time of one call of the prefill kernel over ``pairs`` (query,
    key) pairs inside the mask of prompts of ``tokens`` tokens: scores over
    the key's width and values over the value's for every query head, or
    the bytes of q, k, v in and o out once, whichever is longer."""
    h, d, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hk = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    flops = pairs * 2.0 * h * (d + dv)
    moved = tokens * (h * (d + dv) + hk * (d + dv)) * itemsize
    return max(flops / peaks["flops"], moved / peaks["hbm"])
