"""Published peaks of one chip, and the operation and byte counts the
roofline metrics divide by.  Copied from ``bench.py`` (``CHIP_PEAKS``,
``model_flops_per_token``) so that a PR to the program cannot move them; the
originals are listed in PERF.md for a later PR to delete.

Source of the peaks: Google Cloud TPU documentation, the per-generation
"system architecture" pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect).
"""
from __future__ import annotations

PEAKS = {
    # device_kind prefix -> bf16 FLOP/s, HBM bytes/s, ICI bits/s (one chip)
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9, "ici_bits": 1600e9},
    "TPU v5e": {"flops": 197e12, "hbm": 819e9, "ici_bits": 1600e9},
}


def chip_peaks(device_kind: str) -> dict:
    """The row of the longest matching prefix; an unknown kind is an error."""
    hits = [k for k in PEAKS if device_kind.startswith(k)]
    if not hits:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[max(hits, key=len)]


def _matmul_params_per_layer(cfg: dict) -> int:
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    hidden, inter = cfg["hidden_size"], cfg["intermediate_size"]
    return (hidden * (h + 2 * hk) * d      # q, k, v
            + h * d * hidden               # o
            + 3 * hidden * inter)          # gate, up, down


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one training token of a dense decoder: 6 x the
    parameters that sit in matrix multiplications (forward 2P, backward 4P;
    the embedding is a gather and counts nothing, the untied head counts)
    plus the causal attention's score and value products (4*S*d*h forward,
    halved by the mask, tripled for forward + backward).  Recomputed
    operations do not count."""
    p = (cfg["num_hidden_layers"] * _matmul_params_per_layer(cfg)
         + cfg["hidden_size"] * cfg["vocab_size"])
    attn = (cfg["num_hidden_layers"] * 4 * seq_len * cfg["head_dim"]
            * cfg["num_attention_heads"] * 0.5)
    return 6.0 * p + 3.0 * attn


def param_count(cfg: dict) -> int:
    """Every parameter of the dense decoder, embedding and norms included."""
    hidden = cfg["hidden_size"]
    per_layer = _matmul_params_per_layer(cfg) + 2 * hidden
    emb = cfg["vocab_size"] * hidden
    head = 0 if cfg.get("tie_word_embeddings") else emb
    return cfg["num_hidden_layers"] * per_layer + emb + head + hidden


def decode_step_bytes(cfg: dict, live_kv_tokens: float,
                      weight_itemsize: int = 2, kv_itemsize: int = 2) -> float:
    """Bytes one decode step must read whatever the batch: every layer's
    weights and the head once (the embedding rows read are negligible), and
    the keys and values of every live token."""
    weights = (cfg["num_hidden_layers"] * (_matmul_params_per_layer(cfg)
                                           + 2 * cfg["hidden_size"])
               + cfg["hidden_size"] * cfg["vocab_size"]) * weight_itemsize
    kv = (live_kv_tokens * cfg["num_hidden_layers"] * 2
          * cfg["num_key_value_heads"] * cfg["head_dim"] * kv_itemsize)
    return weights + kv
