"""Builder of the latent-attention, sparse-expert decoder family (MLA
without query compression; a leading dense layer, then sigmoid-routed
experts with shared ones; untied head): maps a configuration file written
with the keys of the model's public ``config.json`` onto the program's
``MlaMoeConfig`` / ``MlaMoeForCausalLM``, whose layer equations are the
same, and hands the plain reference the weights under its own names.
"""
from __future__ import annotations


def build(config: dict):
    """The seeded model, on the device.  ``paddle.seed`` was called by the
    runner."""
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM

    for key, want in (("rope_scaling", None), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"the program's decoder has no {key}="
                             f"{config[key]!r}")
    run = config["run"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "routed_scaling_factor",
            "norm_topk_prob", "scoring_func", "topk_method", "n_group",
            "topk_group", "max_position_embeddings", "rms_norm_eps",
            "rope_theta")
    return MlaMoeForCausalLM(MlaMoeConfig(
        **{k: config[k] for k in keys},
        initializer_range=config["assumed"].get("initializer_range", 0.02),
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
    """Layer ``i`` as the reference names it.  ``q`` is [hidden, heads *
    (nope + rope)], per head the no-position part first; ``kv_a`` [hidden,
    rank + rope], the latent first; ``kv_b`` [rank, heads * (nope + v)], per
    head the key part first; ``gate_up`` matrices hold the gate first.  A
    dense layer has ``gate_up`` / ``down``; an expert layer ``router``
    [hidden, experts] and ``router_bias`` in float32, the stacked
    ``experts_gate_up`` [experts, hidden, 2 * width] / ``experts_down`` and
    the shared expert's pair."""
    p = _arrays(model)
    pre = f"layers.{i}."
    w = {"in_norm": p[pre + "input_layernorm"],
         "q": p[pre + "self_attn.q_proj"],
         "kv_a": p[pre + "self_attn.kv_a_proj"],
         "kv_norm": p[pre + "self_attn.kv_a_norm"],
         "kv_b": p[pre + "self_attn.kv_b_proj"],
         "o": p[pre + "self_attn.o_proj"],
         "post_norm": p[pre + "post_attention_layernorm"]}
    if pre + "mlp.gate_weight" in p:
        w.update(router=p[pre + "mlp.gate_weight"],
                 router_bias=p[pre + "mlp.e_score_correction_bias"],
                 experts_gate_up=p[pre + "mlp.w_gate_up"],
                 experts_down=p[pre + "mlp.w_down"],
                 shared_gate_up=p[pre + "mlp.shared_gate_up"],
                 shared_down=p[pre + "mlp.shared_down"])
    else:
        w.update(gate_up=p[pre + "mlp.gate_up_proj"],
                 down=p[pre + "mlp.down_proj"])
    return w
