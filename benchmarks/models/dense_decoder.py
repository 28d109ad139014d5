"""Builder of the dense decoder family (pre-norm RMSNorm, rotate-half RoPE,
grouped-query attention, SwiGLU, untied head): maps a configuration file
written with the keys of the model's public ``config.json`` onto the
program's ``LlamaConfig`` / ``LlamaForCausalLM``, whose layer equations are
the same, and hands the plain reference the weights under its own names.
"""
from __future__ import annotations


def build(config: dict):
    """The seeded model, on the device(s).  ``paddle.seed`` was called by
    the runner; under ``fleet.init`` the program shards what it builds."""
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig

    if config["head_dim"] * config["num_attention_heads"] != \
            config["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden / heads; "
                         "this configuration's differs")
    if config.get("sliding_window") is not None:
        raise ValueError("the program's dense decoder has no sliding window")
    run = config["run"]
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        initializer_range=config.get("initializer_range", 0.02),
        tie_word_embeddings=config["tie_word_embeddings"],
        dtype=run["compute_dtype"], param_dtype=run.get("param_dtype"),
        recompute=False))


def _arrays(model) -> dict:
    return {n: t._data for n, t in model.named_parameters()}


def top_weights(model) -> dict:
    """Embedding, final norm and head as the reference names them.  Arrays
    are the model's own: nothing is copied."""
    p = _arrays(model)
    return {"embed": p["llama.embed_tokens"], "norm": p["llama.norm.weight"],
            "head": p["lm_head"]}


def layer_weights(model, i: int) -> dict:
    """Layer ``i`` as the reference names it.  ``qkv`` is [hidden, (h + 2 hk)
    * d] with the columns of q, then k, then v; ``gate_up`` is [hidden, 2 *
    intermediate] with gate first."""
    p = _arrays(model)
    pre = f"llama.layers.{i}."
    return {"in_norm": p[pre + "input_layernorm.weight"],
            "qkv": p[pre + "self_attn.qkv_proj"],
            "o": p[pre + "self_attn.o_proj"],
            "post_norm": p[pre + "post_attention_layernorm.weight"],
            "gate_up": p[pre + "mlp.gate_up_proj"],
            "down": p[pre + "mlp.down_proj"]}
