"""Latent-attention, sparse-expert decoder (the DeepSeek-V3 layer equations
as Kimi-VL-A3B's language model uses them: no query compression, one group
of experts) on the SERVING path.

Pre-norm decoder, RMSNorm, residual after attention and after the
feed-forward.

- **Attention (MLA).**  ``q = x W_q`` gives per head ``[q_nope | q_rope]``;
  ``[c_raw | k_r] = x W_kva``; ``c = RMSNorm(c_raw)``; per head ``[k_nope |
  v] = c W_kvb``.  RoPE on ``q_rope`` and on ``k_r`` (one vector for all
  heads), adjacent pairs rotated and stored even members first, as the
  published modeling code does.  ``k = [k_nope | k_r]``, scores ``q k^T /
  sqrt(nope + rope)``.  **The cache holds ``c`` and the rotated ``k_r``:**
  ``kv_lora_rank + qk_rope_head_dim`` values a token a layer, no heads
  (``kernels/mla_attention.py``).  Prefill EXPANDS ``k_nope`` and ``v`` from
  ``c`` and runs ordinary attention (q/k 192 wide, v 128); decode ABSORBS
  ``W_kvb`` into the query and the output and attends in latent space, so a
  decode step reads 576 values a cached token and never rebuilds a key.
- **Feed-forward.**  The first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others ``incubate.moe.DroplessMoE``.

``forward(input_ids, position_ids, cache)``: with a serving cache (the
``CacheBackend``'s: ``latent`` pools, ``block_table``, ``lengths``) one
decode step (S = 1) or one prefill chunk (S > 1); with ``init_cache()``'s
(no table) a dense prefill of whole prompts from position 0, which hands
back the rows to be cached.  Only the logits the caller can use are
computed: with ``n_valid`` in the cache (the prompts' true lengths) those at
the last valid position, ``[B, 1, vocab]``; a 163,840-row head over every
position of a 4,096-token bucket would be 1.3 GB a prompt.  Expert-layer
counts ride in the cache as ``counters`` (int32, sums over the expert
layers): ``[decode steps, rows, experts touched, largest expert's rows]``,
the same four of prefill calls, and the (query, key) pairs / 1024 of the
prompts a dense prefill attended (the square of each true length).

There is no backward: the training path (gradients through the router and
the grouped products) is ROADMAP's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..incubate.moe.dropless import DroplessMoE, scope
from ..kernels import mla_attention as mla
from ..kernels import rope as rope_mod
from ..kernels.rms_norm import rms_norm
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer, LayerList
from .moe_serving import (MOE_COUNTERS, DenseMLP, last_valid_rows,
                          moe_counts, token_validity)
from .moe_serving import raw as _raw

__all__ = ["MlaMoeConfig", "MlaMoeForCausalLM", "mla_moe_tiny_config",
           "COUNTERS"]

# what ``counters`` counts, in order (``cache_spec()["counters"]``)
COUNTERS = MOE_COUNTERS + ("mla.prefill_kilo_pairs",)


@dataclass
class MlaMoeConfig:
    """The public keys of the family's ``config.json``."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 800000.0
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    param_dtype: Optional[str] = None

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        if self.scoring_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise NotImplementedError(
                "only sigmoid scores over one group of experts")

    @property
    def pdtype(self) -> str:
        return self.param_dtype or self.dtype

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    # what ``serving.Engine.memory_plan`` reads of an attention model: the
    # cache has one "head" as wide as a latent row
    @property
    def kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return self.latent_width


def mla_moe_tiny_config(**overrides) -> MlaMoeConfig:
    """CPU-test scale: every mechanism, no width over 128."""
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, num_hidden_layers=3,
               num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=8,
               n_shared_experts=1, num_experts_per_tok=2,
               max_position_embeddings=512, dtype="float32")
    cfg.update(overrides)
    return MlaMoeConfig(**cfg)


def _rope_pairs(x, cos, sin, pos):
    """Rotate ``x [B, S, heads, d]`` at positions ``pos [B, S]``: adjacent
    pairs, the result stored even members first."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope_mod.apply_rope(x, x, cos, sin, pos)[0]


class MlaAttention(Layer):
    def __init__(self, config: MlaMoeConfig):
        super().__init__()
        self.config = config
        c = config
        h, init = c.num_attention_heads, Normal(0.0, c.initializer_range)
        param = lambda shape: self.create_parameter(              # noqa: E731
            shape, dtype=c.pdtype, default_initializer=init)
        self.q_proj = param([c.hidden_size,
                             h * (c.qk_nope_head_dim + c.qk_rope_head_dim)])
        self.kv_a_proj = param([c.hidden_size, c.latent_width])
        self.kv_a_norm = self.create_parameter(
            [c.kv_lora_rank], dtype=c.pdtype,
            default_initializer=Constant(1.0))
        self.kv_b_proj = param([c.kv_lora_rank,
                                h * (c.qk_nope_head_dim + c.v_head_dim)])
        self.o_proj = param([h * c.v_head_dim, c.hidden_size])

    def _project(self, x, cos, sin, pos):
        """``(q_nope [B,S,h,nope], q_rope [B,S,h,rope], rows [B,S,W])``:
        the queries and the rows to cache (normed latent, rotated key)."""
        c = self.config
        B, S, _ = x.shape
        h, nope, rank = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.kv_lora_rank)
        q = (x @ _raw(self.q_proj).astype(x.dtype)).reshape(B, S, h, -1)
        kva = x @ _raw(self.kv_a_proj).astype(x.dtype)
        lat = rms_norm(kva[..., :rank], _raw(self.kv_a_norm), c.rms_norm_eps)
        k_r = _rope_pairs(kva[..., None, rank:], cos, sin, pos)[:, :, 0]
        return (q[..., :nope], _rope_pairs(q[..., nope:], cos, sin, pos),
                jnp.concatenate([lat, k_r], axis=-1))

    def _kv_b(self, dtype):
        c = self.config
        w = _raw(self.kv_b_proj).astype(dtype).reshape(
            c.kv_lora_rank, c.num_attention_heads, -1)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _out(self, o):
        B, S = o.shape[:2]
        return o.reshape(B, S, -1) @ _raw(self.o_proj).astype(o.dtype)

    def forward(self, x, cos, sin, cache=None):
        """``cache``: None or ``(pool, block_table, lengths)``.  Returns the
        attention output and the rows ``[B, S, W]`` (dense) or the pool."""
        c = self.config
        x = _raw(x)
        B, S, _ = x.shape
        rank = c.kv_lora_rank
        scale = 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)
        if cache is None:
            pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            q_nope, q_rope, rows = self._project(x, cos, sin, pos)
            with scope("mla.prefill_attn", x):
                w_k, w_v = self._kv_b(x.dtype)
                lat = rows[..., :rank]
                o = mla.mla_prefill_attention(
                    q_nope, q_rope, jnp.einsum("bsr,rhn->bshn", lat, w_k),
                    rows[..., rank:], jnp.einsum("bsr,rhv->bshv", lat, w_v),
                    scale)
            return self._out(o), rows
        pool, tbl, lengths = cache
        pos = lengths[:, None] + jnp.arange(S)[None, :]
        q_nope, q_rope, rows = self._project(x, cos, sin, pos)
        w_k, w_v = self._kv_b(x.dtype)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_k)
        with scope("mla.decode_attn", x):
            if S == 1:
                pool = mla.write_latent_token(pool, tbl, lengths, rows[:, 0],
                                              rank)
                att_len = jnp.where(lengths > 0, lengths + 1, 0)
                o_lat = mla.latent_decode_attention(
                    q_lat[:, 0], q_rope[:, 0], pool, tbl, att_len,
                    scale)[:, None]
            else:
                pool = mla.write_latent_chunk(pool, tbl, lengths, rows, rank)
                o_lat = mla.latent_chunk_attention(q_lat, q_rope, pool, tbl,
                                                   lengths, scale)
        return self._out(jnp.einsum("bshr,rhv->bshv", o_lat, w_v)), pool


class MlaMoeDecoderLayer(Layer):
    def __init__(self, config: MlaMoeConfig, index: int):
        super().__init__()
        self.config = config
        c = config
        norm = lambda: self.create_parameter(                     # noqa: E731
            [c.hidden_size], dtype=c.pdtype,
            default_initializer=Constant(1.0))
        self.input_layernorm = norm()
        self.self_attn = MlaAttention(c)
        self.post_attention_layernorm = norm()
        self.is_moe = index >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, num_shared=c.n_shared_experts,
                scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob,
                dtype=c.pdtype, initializer_range=c.initializer_range)
        else:
            self.mlp = DenseMLP(c)

    def forward(self, x, cos, sin, cache=None, valid=None):
        """Returns ``(hidden, rows or pool, expert counts or None)``."""
        eps = self.config.rms_norm_eps
        x = _raw(x)
        a, kept = self.self_attn(
            rms_norm(x, _raw(self.input_layernorm), eps), cos, sin, cache)
        x = x + _raw(a)
        h = rms_norm(x, _raw(self.post_attention_layernorm), eps)
        if self.is_moe:
            y, stats = self.mlp(h, valid=valid)
            return x + _raw(y), kept, stats
        return x + _raw(self.mlp(h)), kept, None


class MlaMoeForCausalLM(Layer):
    """Decoder + untied head, served by ``serving.Engine`` through the
    ``latent`` cache kind."""

    def __init__(self, config: MlaMoeConfig, mesh=None):
        super().__init__()
        self.config = config
        c = config
        init = Normal(0.0, c.initializer_range)
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.pdtype,
            default_initializer=init)
        self.layers = LayerList([MlaMoeDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.pdtype,
            default_initializer=Constant(1.0))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size], dtype=c.pdtype,
            default_initializer=init)
        cos, sin = rope_mod.rope_freqs(c.qk_rope_head_dim,
                                       c.max_position_embeddings,
                                       c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    @property
    def n_expert_layers(self) -> int:
        return sum(layer.is_moe for layer in self.layers)

    # -- the model half of the CacheBackend seam -----------------------------

    def cache_spec(self) -> dict:
        c = self.config
        return {"kinds": ("latent",) * c.num_hidden_layers,
                "state_bytes_per_slot": 0,
                "kv_layers": c.num_hidden_layers,
                "kv_bytes_per_token_layer":
                    c.latent_width * jnp.dtype(c.dtype).itemsize,
                "latent_rank": c.kv_lora_rank, "counters": COUNTERS}

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """The cache of a dense prefill: nothing to read, the rows come back
        under ``latent``."""
        return {"latent": ()}

    def init_latent_pools(self, num_blocks: int, block_size: int = 128,
                          dtype=None):
        c = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(c.dtype)
        return tuple(mla.init_latent_pool(num_blocks, block_size,
                                          c.kv_lora_rank, c.qk_rope_head_dim,
                                          dt)
                     for _ in range(c.num_hidden_layers))

    # -- forward -------------------------------------------------------------

    def forward(self, input_ids, position_ids=None, cache=None):
        """Logits ``[B, S, vocab]``; with ``cache`` ``(logits, new_cache)``,
        and logits ``[B, 1, vocab]`` at position ``n_valid - 1`` where the
        cache says ``n_valid``."""
        c = self.config
        ids = _raw(input_ids)
        B, S = ids.shape
        x = jnp.take(_raw(self.embed_tokens), ids, axis=0).astype(c.dtype)
        cos, sin = _raw(self.rope_cos), _raw(self.rope_sin)
        paged = cache is not None and "block_table" in cache
        n_valid, valid = token_validity(cache, S)
        if paged:
            tbl, lengths = _raw(cache["block_table"]), _raw(cache["lengths"])
        kept, counts = [], jnp.zeros((3,), jnp.float32)
        for i, layer in enumerate(self.layers):
            x, k, stats = layer(
                x, cos, sin,
                cache=(_raw(cache["latent"][i]), tbl, lengths) if paged
                else None, valid=valid)
            kept.append(k)
            if stats is not None:
                counts = counts + stats
        x = rms_norm(last_valid_rows(x, n_valid), _raw(self.norm),
                     c.rms_norm_eps)
        logits = Tensor(x @ _raw(self.lm_head).astype(x.dtype))
        if cache is None:
            return logits
        if not paged:
            n = jnp.full((B,), S) if n_valid is None else n_valid
            pairs = jnp.sum(n * n // 1024, keepdims=True).astype(jnp.int32)
            return logits, {"latent": tuple(kept),
                            "counters": jnp.concatenate(
                                [moe_counts(counts, False), pairs])}
        decode = S == 1
        new_cache = {
            "latent": tuple(kept), "block_table": tbl,
            "lengths": lengths + ((lengths > 0).astype(lengths.dtype)
                                  if decode else jnp.asarray(S, lengths.dtype)),
            "counters": _raw(cache["counters"]) + jnp.concatenate(
                [moe_counts(counts, decode), jnp.zeros((1,), jnp.int32)])}
        return logits, new_cache
