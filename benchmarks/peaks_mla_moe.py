"""Operation and byte counts of the latent-attention, sparse-expert decoder
(``configs/kimi_vl_a3b_d9_serve.json``'s family), for the roofline metrics
of its cells.  ``peaks.py`` holds the chip's published peaks and the dense
decoder's counts and is not edited.

Every count is of the mathematics: rows times widths, the weights of the
experts that GOT a row (never all of them by assumption: a step in which
some expert idles would read over 100%), the latent rows of the tokens that
are live.  Nothing the implementation pads to is counted.
"""
from __future__ import annotations


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attention_params(cfg: dict) -> int:
    h = cfg["num_attention_heads"]
    hidden, rank = cfg["hidden_size"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (hidden * h * (nope + rope)        # q
            + hidden * (rank + rope)          # kv_a
            + rank                            # the latent's norm
            + rank * h * (nope + v)           # kv_b
            + h * v * hidden)                 # o


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def non_expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Every weight a decode step reads whatever the routing: attention and
    norms of every layer, the dense layers' feed-forward, the shared experts
    and the float32 router of every expert layer, the final norm and the
    head (the embedding rows read are negligible)."""
    hidden = cfg["hidden_size"]
    n_moe, n_dense = expert_layers(cfg), cfg["first_k_dense_replace"]
    per_layer = attention_params(cfg) + 2 * hidden
    dense = 3 * hidden * cfg["intermediate_size"]
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    router = hidden * cfg["n_routed_experts"] * 4 + cfg["n_routed_experts"] * 4
    return ((cfg["num_hidden_layers"] * per_layer + n_dense * dense
             + n_moe * shared + hidden + hidden * cfg["vocab_size"]) * itemsize
            + n_moe * router)


def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Cache bytes a token a layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def decode_step_bytes(cfg: dict, experts_touched: float,
                      live_tokens: float, itemsize: int = 2) -> float:
    """Bytes one decode step must read: the non-expert weights once, the
    weights of the experts that got a row (``experts_touched``: the mean
    over the expert layers), the latent rows of every live token."""
    return (non_expert_bytes(cfg, itemsize)
            + expert_layers(cfg) * experts_touched * expert_params(cfg)
            * itemsize
            + live_tokens * cfg["num_hidden_layers"]
            * latent_row_bytes(cfg, itemsize))


def grouped_mm_least_s(cfg: dict, rows: float, experts_touched: float,
                       peaks: dict, itemsize: int = 2) -> float:
    """Least time of one expert layer's grouped products (gate and up as one
    product, then down) over ``rows`` routed rows that touch
    ``experts_touched`` experts: the larger of the products' operations over
    the peak and of the touched weights plus the rows in and out over the
    HBM rate."""
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2.0 * rows * 3 * hidden * width
    moved = (experts_touched * expert_params(cfg)
             + rows * (hidden + 2 * width + width + hidden)) * itemsize
    return max(flops / peaks["flops"], moved / peaks["hbm"])


def latent_decode_attn_bytes(cfg: dict, live_tokens: float, batch: int,
                             itemsize: int = 2) -> float:
    """Bytes one call of the latent decode kernel must move: the live
    tokens' rows of one layer, the absorbed and the rotated query in and the
    weighted latent out for every head of every slot."""
    h, rank, rope = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                     cfg["qk_rope_head_dim"])
    return (live_tokens * latent_row_bytes(cfg, itemsize)
            + batch * h * (2 * rank + rope) * itemsize)


def prefill_attn_flops(cfg: dict, pairs: float) -> float:
    """Operations of causal attention over ``pairs`` (query, key) pairs
    before the mask halves them (the sum of the prompts' squared lengths):
    scores over ``nope + rope``, values over ``v``, every head."""
    h = cfg["num_attention_heads"]
    return pairs / 2 * 2.0 * h * (cfg["qk_nope_head_dim"]
                                  + cfg["qk_rope_head_dim"]
                                  + cfg["v_head_dim"])
