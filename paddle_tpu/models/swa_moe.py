"""Window/full grouped-query attention with sparse experts (the layer
equations of MiMo-V2-Flash's ``config.json``) on the SERVING path.

Pre-norm decoder, RMSNorm, residual after attention and after the
feed-forward.  Layer ``l`` is a *full* attention layer where
``hybrid_layer_pattern[l] == 0`` and a *window* layer where it is 1.

- **Attention.**  ``q = h W_q`` ``[H, D]``, ``k = h W_k`` ``[Hk, D]``, ``v = h
  W_v`` ``[Hk, dv]``, with ``D`` 192 and ``dv`` 128, and the two kinds' own head
  counts (``num_key_value_heads`` full, ``swa_num_key_value_heads`` window).
  Rotate-half RoPE over the first ``int(D * partial_rotary_factor)`` values
  of q and k, base ``rope_theta`` (full) or ``swa_rope_theta`` (window); the
  other values pass through.  Scores ``q k^T / sqrt(D)``, causal; a window
  layer sees the ``sliding_window`` latest keys, itself included, and adds
  ``exp(b_head)`` to its softmax's denominator (the learnable sink bias:
  ``add_swa_attention_sink_bias``; ``add_full_attention_sink_bias`` for the
  full layers).  The output is scaled by ``attention_value_scale`` before
  ``W_o``.  No biases.
- **Feed-forward.**  Where ``moe_layer_freq[l] == 0`` a SwiGLU of
  ``intermediate_size``; elsewhere ``incubate.moe.DroplessMoE``: sigmoid
  scores over all ``n_routed_experts``, the top ``num_experts_per_tok`` of
  score + bias, weights normalised, no shared expert.  ``experts_held =
  (first, count)`` is this chip's share of every layer's experts under
  expert parallelism: the layer then returns the held experts' part of the
  sum, and that partial result is what goes on to the next layer.

**The cache** (``cache_spec()``): a full layer is kind ``attention``, paged
pools of K and V (``kernels/gqa_attention.py`` has the K pool's rows); a
window layer is kind ``window``, one bounded ring a slot.  ``forward(
input_ids, position_ids, cache)`` has ``MlaMoeForCausalLM.forward``'s
contract: with a serving cache (pools, rings, ``block_table``, ``lengths``)
one decode step (S = 1); with ``init_cache()``'s (no table) a dense prefill
of whole prompts from position 0, which hands back each full layer's K/V and
the rows each window layer's ring must hold; with ``n_valid`` in the cache
only the logits at the last valid position are computed and the padded tail
is routed nowhere.  ``counters`` (int32, in the cache): the eight of
``moe_serving.MOE_COUNTERS``, the rows routed to experts held elsewhere, and
the (query, key) pairs / 1024 inside the mask of ONE full and of ONE window
layer of the prompts a dense prefill attended, and the pairs / 1024 of the
block steps ``gqa_prefill_attn`` ran for them (``_run_full``, ``_run_window``:
inside / run is the share of the kernel's work that the mask keeps; the rest
is the blocks' overhang at the diagonal, the band's edge and the prompt's
end).

There is no chunked prefill (a chunk's context is its blocks; a ring has
none) and no backward.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..incubate.moe.dropless import DroplessMoE, scope
from ..kernels import gqa_attention as gqa
from ..kernels.rms_norm import rms_norm
from ..kernels.rope import _rotate_half
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer, LayerList
from .moe_serving import (MOE_COUNTERS, DenseMLP, last_valid_rows,
                          moe_counts, token_validity)
from .moe_serving import raw as _raw

__all__ = ["SwaMoeConfig", "SwaMoeForCausalLM", "swa_moe_tiny_config",
           "COUNTERS"]

# what ``counters`` counts, in order (``cache_spec()["counters"]``)
COUNTERS = MOE_COUNTERS + ("moe.rows_elsewhere",
                           "attn.prefill_kilo_pairs_full",
                           "attn.prefill_kilo_pairs_window",
                           "attn.prefill_kilo_pairs_run_full",
                           "attn.prefill_kilo_pairs_run_window")


def _period(n: int) -> Tuple[int, ...]:
    """The published pattern: layer 0 full, then five window layers to one
    full one (the full ones at 5, 11, 17, ...)."""
    return tuple(0 if i == 0 or i % 6 == 5 else 1 for i in range(n))


@dataclass
class SwaMoeConfig:
    """The public keys of the family's ``config.json``, and the share of
    the experts held here."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256
    experts_held: Optional[Tuple[int, int]] = None
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    routed_scaling_factor: Optional[float] = None
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    sink_init_std: float = 0.0
    dtype: str = "bfloat16"
    param_dtype: Optional[str] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = _period(n)
        if self.moe_layer_freq is None:
            self.moe_layer_freq = (0,) + (1,) * (n - 1)
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)[:n]
        self.moe_layer_freq = tuple(self.moe_layer_freq)[:n]
        if len(self.hybrid_layer_pattern) < n or len(self.moe_layer_freq) < n:
            raise ValueError("a layer pattern shorter than the depth")
        if self.scoring_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1:
            raise NotImplementedError(
                "only sigmoid scores over one group of experts")
        if (self.swa_num_attention_heads, self.swa_head_dim,
                self.swa_v_head_dim) != (self.num_attention_heads,
                                         self.head_dim, self.v_head_dim):
            raise NotImplementedError(
                "window layers with other query heads or widths than the "
                "full layers'")

    @property
    def pdtype(self) -> str:
        return self.param_dtype or self.dtype

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_window(self, i: int) -> bool:
        return self.hybrid_layer_pattern[i] == 1

    def kv_heads_of(self, window: bool) -> int:
        return self.swa_num_key_value_heads if window \
            else self.num_key_value_heads


def swa_moe_tiny_config(**overrides) -> SwaMoeConfig:
    """CPU-test scale: every mechanism (both layer kinds in the published
    order, a wrapped ring, a share of the experts), no width over 128."""
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, num_hidden_layers=7,
               num_attention_heads=8, num_key_value_heads=2,
               swa_num_attention_heads=8, swa_num_key_value_heads=4,
               head_dim=48, v_head_dim=32, swa_head_dim=48,
               swa_v_head_dim=32, sliding_window=16, n_routed_experts=8,
               experts_held=(0, 4), num_experts_per_tok=2,
               max_position_embeddings=1024, sink_init_std=1.0,
               dtype="float32")
    cfg.update(overrides)
    return SwaMoeConfig(**cfg)


def _rope(x, pos, theta: float, rot: int):
    """Rotate-half RoPE of ``x [B, S, heads, D]`` at positions ``pos [B,
    S]`` over the first ``rot`` values; the others pass through."""
    f = jnp.float32
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=f) / rot))
    ang = pos.astype(f)[..., None] * inv                       # [B, S, rot/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    xr = x[..., :rot].astype(f)
    out = xr * jnp.cos(ang) + _rotate_half(xr) * jnp.sin(ang)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


class SwaAttention(Layer):
    """One attention layer, full or window."""

    def __init__(self, config: SwaMoeConfig, window: bool):
        super().__init__()
        self.config, self.window = config, window
        c = config
        h, hk = c.num_attention_heads, c.kv_heads_of(window)
        init = Normal(0.0, c.initializer_range)
        param = lambda shape: self.create_parameter(              # noqa: E731
            shape, dtype=c.pdtype, default_initializer=init)
        self.q_proj = param([c.hidden_size, h * c.head_dim])
        self.k_proj = param([c.hidden_size, hk * c.head_dim])
        self.v_proj = param([c.hidden_size, hk * c.v_head_dim])
        self.o_proj = param([h * c.v_head_dim, c.hidden_size])
        self.has_sink = c.add_swa_attention_sink_bias if window \
            else c.add_full_attention_sink_bias
        if self.has_sink:
            self.sinks = self.create_parameter(
                [h], dtype="float32",
                default_initializer=Normal(0.0, c.sink_init_std)
                if c.sink_init_std else Constant(0.0))

    def _project(self, x, pos):
        c = self.config
        B, S, _ = x.shape
        theta = c.swa_rope_theta if self.window else c.rope_theta
        mm = lambda w, d: (x @ _raw(w).astype(x.dtype)).reshape(  # noqa: E731
            B, S, -1, d)
        return (_rope(mm(self.q_proj, c.head_dim), pos, theta, c.rotary_dim),
                _rope(mm(self.k_proj, c.head_dim), pos, theta, c.rotary_dim),
                mm(self.v_proj, c.v_head_dim))

    def forward(self, x, cache=None, n_valid=None):
        """``cache``: None (dense prefill from position 0), ``(k_pool,
        v_pool, block_table, lengths)`` (full) or ``(ring, lengths)``
        (window) for one decode step.  Returns the attention output and what
        the cache keeps: ``(k, v)`` of the whole prompts (full, dense), the
        ring's rows (window, dense), the pools or the ring."""
        c = self.config
        x = _raw(x)
        B, S, _ = x.shape
        scale = 1.0 / math.sqrt(c.head_dim)
        sinks = _raw(self.sinks) if self.has_sink else None
        with scope("attn.window" if self.window else "attn.full", x):
            if cache is None:
                pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
                q, k, v = self._project(x, pos)
                o = gqa.gqa_prefill_attention(
                    q, k, v, scale,
                    window=c.sliding_window if self.window else None,
                    sinks=sinks, n_valid=n_valid)
                kept = (k, v)
                if self.window:
                    n = jnp.full((B,), S) if n_valid is None else n_valid
                    kept = {"k": gqa.ring_rows(k, n, c.sliding_window),
                            "v": gqa.ring_rows(v, n, c.sliding_window)}
            else:
                lengths = cache[-1]
                q, k, v = self._project(x, lengths[:, None])
                att_len = jnp.where(lengths > 0, lengths + 1, 0)
                if self.window:
                    kept = gqa.write_ring_token(cache[0], lengths, k[:, 0],
                                                v[:, 0])
                    o = gqa.ring_decode_attention(q[:, 0], kept, att_len,
                                                  scale, sinks)
                else:
                    k_pool, v_pool, tbl, _ = cache
                    kept = gqa.write_kv_token(k_pool, v_pool, tbl, lengths,
                                              k[:, 0], v[:, 0])
                    o = gqa.gqa_paged_decode_attention(
                        q[:, 0], *kept, tbl, att_len, scale)
                o = o[:, None]
        o = (o * c.attention_value_scale).astype(x.dtype).reshape(B, S, -1)
        return o @ _raw(self.o_proj).astype(x.dtype), kept


class SwaMoeDecoderLayer(Layer):
    def __init__(self, config: SwaMoeConfig, index: int):
        super().__init__()
        self.config = config
        c = config
        norm = lambda: self.create_parameter(                     # noqa: E731
            [c.hidden_size], dtype=c.pdtype,
            default_initializer=Constant(1.0))
        self.input_layernorm = norm()
        self.is_window = c.is_window(index)
        self.self_attn = SwaAttention(c, self.is_window)
        self.post_attention_layernorm = norm()
        self.is_moe = c.moe_layer_freq[index] == 1
        if self.is_moe:
            self.mlp = DroplessMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, num_shared=c.n_shared_experts or 0,
                scale=c.routed_scaling_factor or 1.0,
                norm_topk=c.norm_topk_prob, dtype=c.pdtype,
                initializer_range=c.initializer_range, held=c.experts_held)
        else:
            self.mlp = DenseMLP(c)

    def forward(self, x, cache=None, valid=None, n_valid=None):
        """Returns ``(hidden, what the cache keeps, expert counts or
        None)``."""
        eps = self.config.layernorm_epsilon
        x = _raw(x)
        a, kept = self.self_attn(
            rms_norm(x, _raw(self.input_layernorm), eps), cache, n_valid)
        x = x + _raw(a)
        h = rms_norm(x, _raw(self.post_attention_layernorm), eps)
        if self.is_moe:
            y, stats = self.mlp(h, valid=valid)
            return x + _raw(y), kept, stats
        return x + _raw(self.mlp(h)), kept, None


def _pairs_in_mask(n, window=None):
    """(query, key) pairs a causal mask keeps of a prompt of ``n`` tokens,
    with a window the band's."""
    n = n.astype(jnp.float32)
    if window is None:
        return n * (n + 1) / 2
    w = jnp.minimum(n, window)
    return w * (w + 1) / 2 + (n - w) * window


class SwaMoeForCausalLM(Layer):
    """Decoder + untied head, served by ``serving.Engine`` through the
    ``attention`` (full layers) and ``window`` cache kinds."""

    def __init__(self, config: SwaMoeConfig, mesh=None):
        super().__init__()
        self.config = config
        c = config
        init = Normal(0.0, c.initializer_range)
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.pdtype,
            default_initializer=init)
        self.layers = LayerList([SwaMoeDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.pdtype,
            default_initializer=Constant(1.0))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size], dtype=c.pdtype,
            default_initializer=init)

    @property
    def n_expert_layers(self) -> int:
        return sum(layer.is_moe for layer in self.layers)

    def _n_layers(self, window: bool) -> int:
        return sum(layer.is_window == window for layer in self.layers)

    # -- the model half of the CacheBackend seam -----------------------------

    def cache_spec(self) -> dict:
        c = self.config
        item = jnp.dtype(c.dtype).itemsize
        width = c.head_dim + c.v_head_dim
        return {"kinds": tuple("window" if layer.is_window else "attention"
                               for layer in self.layers),
                "kv_layers": self._n_layers(False),
                "kv_bytes_per_token_layer":
                    c.num_key_value_heads * width * item,
                "state_bytes_per_slot":
                    self._n_layers(True) * c.sliding_window
                    * c.swa_num_key_value_heads * width * item,
                "kv_write_prefill": gqa.write_kv_prefill,
                "counters": COUNTERS}

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """The cache of a dense prefill: nothing to read; each full layer's
        K/V come back under ``kv``, each window layer's ring rows under
        ``window``."""
        return {"kv": (), "window": ()}

    def init_paged_pools(self, num_blocks: int, block_size: int = 128,
                         dtype=None):
        """``(k_pools, v_pools)`` of the full layers."""
        c = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(c.dtype)
        pools = [gqa.init_kv_pools(num_blocks, block_size,
                                   c.num_key_value_heads, c.head_dim,
                                   c.v_head_dim, dt)
                 for _ in range(self._n_layers(False))]
        return tuple(p[0] for p in pools), tuple(p[1] for p in pools)

    def init_window_rings(self, max_slots: int, dtype=None):
        """One ring a window layer, ``max_slots`` wide."""
        c = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(c.dtype)
        return tuple(gqa.init_ring(max_slots, c.sliding_window,
                                   c.swa_num_key_value_heads, c.head_dim,
                                   c.v_head_dim, dt)
                     for _ in range(self._n_layers(True)))

    # -- forward -------------------------------------------------------------

    def forward(self, input_ids, position_ids=None, cache=None):
        """Logits ``[B, S, vocab]``; with ``cache`` ``(logits, new_cache)``,
        and logits ``[B, 1, vocab]`` at position ``n_valid - 1`` where the
        cache says ``n_valid``."""
        c = self.config
        ids = _raw(input_ids)
        B, S = ids.shape
        x = jnp.take(_raw(self.embed_tokens), ids, axis=0).astype(c.dtype)
        paged = cache is not None and "block_table" in cache
        n_valid, valid = token_validity(cache, S)
        if paged:
            if S != 1:
                raise NotImplementedError(
                    "a prefill chunk over a window/full cache")
            tbl, lengths = _raw(cache["block_table"]), _raw(cache["lengths"])
        full, rings, counts = [], [], jnp.zeros((4,), jnp.float32)
        for layer in self.layers:
            held = None
            if paged and layer.is_window:
                held = ({n: _raw(a) for n, a in
                         cache["window"][len(rings)].items()}, lengths)
            elif paged:
                held = (_raw(cache["k"][len(full)]),
                        _raw(cache["v"][len(full)]), tbl, lengths)
            x, kept, stats = layer(x, cache=held, valid=valid,
                                   n_valid=n_valid)
            (rings if layer.is_window else full).append(kept)
            if stats is not None:
                counts = counts + (stats if stats.shape[0] == 4 else
                                   jnp.pad(stats, (0, 1)))
        x = rms_norm(last_valid_rows(x, n_valid), _raw(self.norm),
                     c.layernorm_epsilon)
        logits = Tensor(x @ _raw(self.lm_head).astype(x.dtype))
        if cache is None:
            return logits
        elsewhere = counts[3:].astype(jnp.int32)
        if not paged:
            n = jnp.full((B,), S) if n_valid is None else n_valid
            pairs = jnp.stack([
                jnp.sum(_pairs_in_mask(n)),
                jnp.sum(_pairs_in_mask(n, c.sliding_window)),
                jnp.sum(gqa.prefill_pairs_run(S, None, n)),
                jnp.sum(gqa.prefill_pairs_run(S, c.sliding_window, n))]) / 1024
            return logits, {"kv": tuple(full), "window": tuple(rings),
                            "counters": jnp.concatenate(
                                [moe_counts(counts, False), elsewhere,
                                 pairs.astype(jnp.int32)])}
        return logits, {
            "k": tuple(k for k, _ in full), "v": tuple(v for _, v in full),
            "window": tuple(rings), "block_table": tbl,
            "lengths": lengths + (lengths > 0).astype(lengths.dtype),
            "counters": _raw(cache["counters"]) + jnp.concatenate(
                [moe_counts(counts, True), elsewhere,
                 jnp.zeros((4,), jnp.int32)])}
