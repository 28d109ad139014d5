"""Builder of the window/full attention, sparse-expert decoder family
(grouped-query attention with 192-wide keys and 128-wide values, five
window layers with a sink bias to one full layer, a leading dense layer,
then sigmoid-routed experts of which this chip holds a share; untied head):
maps a configuration file written with the keys of the model's public
``config.json`` onto the program's ``SwaMoeConfig`` / ``SwaMoeForCausalLM``,
whose layer equations are the same, and hands the plain reference the
weights under its own names.

In the file ``n_routed_experts`` counts the experts HELD here (it is in
``reduced``); the router's width is ``published.n_routed_experts`` and the
held range starts at ``experts_held_first``.
"""
from __future__ import annotations


def held(config: dict):
    """``(first, count, of)``: the experts held here, of how many."""
    return (config.get("experts_held_first", 0), config["n_routed_experts"],
            config["published"]["n_routed_experts"])


def build(config: dict):
    """The seeded model, on the device.  ``paddle.seed`` was called by the
    runner."""
    from paddle_tpu.models.swa_moe import SwaMoeConfig, SwaMoeForCausalLM

    for key, want in (("attention_bias", False),
                      ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("n_shared_experts", None),
                      ("routed_scaling_factor", None)):
        if config.get(key, want) != want:
            raise ValueError(f"the program's decoder has no {key}="
                             f"{config[key]!r}")
    run, assumed = config["run"], config["assumed"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "hybrid_layer_pattern", "moe_layer_freq", "sliding_window",
            "partial_rotary_factor", "rope_theta", "swa_rope_theta",
            "attention_value_scale", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "num_experts_per_tok",
            "norm_topk_prob", "scoring_func", "topk_method", "n_group",
            "topk_group", "max_position_embeddings", "layernorm_epsilon")
    first, count, of = held(config)
    return SwaMoeForCausalLM(SwaMoeConfig(
        **{k: config[k] for k in keys}, n_routed_experts=of,
        experts_held=(first, count),
        initializer_range=assumed.get("initializer_range", 0.02),
        sink_init_std=assumed.get("sink_init_std", 1.0),
        dtype=run["compute_dtype"], param_dtype=run.get("param_dtype")))


def _arrays(model) -> dict:
    return {n: t._data for n, t in model.named_parameters()}


def top_weights(model) -> dict:
    """Embedding, final norm and head as the reference names them.  Arrays
    are the model's own: nothing is copied."""
    p = _arrays(model)
    return {"embed": p["embed_tokens"], "norm": p["norm"],
            "head": p["lm_head"]}


def layer_weights(model, i: int) -> dict:
    """Layer ``i`` as the reference names it: ``q`` [hidden, heads * D], ``k``
    [hidden, kv heads * D], ``v`` [hidden, kv heads * dv], ``o``; ``sinks``
    [heads] (float32) where the layer has the bias; a window layer carries
    the key ``window`` (an empty array: the key's presence is the mark).  A
    dense layer has ``gate_up`` / ``down`` (gate first); an expert layer
    ``router`` [hidden, ALL experts] and ``router_bias`` in float32 and the
    HELD experts' stacked ``experts_gate_up`` [held, hidden, 2 * width] /
    ``experts_down``."""
    import jax.numpy as jnp

    p = _arrays(model)
    pre = f"layers.{i}."
    w = {"in_norm": p[pre + "input_layernorm"],
         "q": p[pre + "self_attn.q_proj"], "k": p[pre + "self_attn.k_proj"],
         "v": p[pre + "self_attn.v_proj"], "o": p[pre + "self_attn.o_proj"],
         "post_norm": p[pre + "post_attention_layernorm"]}
    if pre + "self_attn.sinks" in p:
        w["sinks"] = p[pre + "self_attn.sinks"]
    if model.config.is_window(i):
        w["window"] = jnp.zeros((0,), jnp.float32)
    if pre + "mlp.gate_weight" in p:
        w.update(router=p[pre + "mlp.gate_weight"],
                 router_bias=p[pre + "mlp.e_score_correction_bias"],
                 experts_gate_up=p[pre + "mlp.w_gate_up"],
                 experts_down=p[pre + "mlp.w_down"])
    else:
        w.update(gate_up=p[pre + "mlp.gate_up_proj"],
                 down=p[pre + "mlp.down_proj"])
    return w
