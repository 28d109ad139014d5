"""Llama decoder family — the flagship LLM recipe.

Counterpart of the reference's semi-auto-parallel Llama
(``test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py``:
LlamaAttentionAuto / LlamaMLPAuto / LlamaForCausalLMAuto) and the PaddleNLP
Llama-3 pretraining recipe named by ``BASELINE.json``.

TPU-native design decisions (vs the reference's Megatron-style module tree):

- **Fused projections.** One qkv matmul ``[hidden, (H + 2*Hk) * head_dim]``
  and one gate_up matmul ``[hidden, 2 * intermediate]`` — big MXU-friendly
  GEMMs instead of 3+2 smaller ones (the reference gets this from its
  fused_attention/fused_feedforward CUDA kernels; here it is just weight
  layout).
- **Parallelism by annotation.** With a mesh, weights carry GSPMD shardings
  (qkv/gate_up column-sharded over 'mp', o/down row-sharded, embedding
  vocab-sharded) — the collectives the reference codes by hand in
  ``fleet/layers/mpu/mp_layers.py`` are inserted by XLA.  Without a mesh the
  same module runs single-chip.
- **Sequence parallel** (`config.sequence_parallel`): the residual stream is
  constrained to shard the sequence dim over 'mp' between attention/MLP
  blocks — the counterpart of ``sequence_parallel_utils.py``'s
  scatter/gather pairs, again via annotation.
- **bf16-first**: params can be created directly in bfloat16
  (``config.dtype``); the optimizer keeps fp32 masters (multi_precision).
- Attention runs the Pallas flash kernel on TPU (``kernels/flash_attention``),
  the XLA reference path elsewhere; rope/rms_norm use the fused kernel lib.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..framework.dispatch import apply_op
from ..framework.tensor import Tensor
from ..kernels import flash_attention as fa_mod
from ..kernels import rope as rope_mod
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layers import Layer, LayerList
from ..distributed.mesh import ProcessMesh, get_mesh
from ..distributed.placement import Replicate, Shard
from ..distributed.api import shard_tensor

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "llama_tiny_config", "llama3_8b_config", "llama3_70b_config",
]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # None -> MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "float32"           # compute/activation dtype ("bfloat16" for TPU perf)
    # storage dtype of parameters; None -> same as ``dtype``.  Setting
    # "float32" with dtype="bfloat16" gives the standard TPU mixed-precision
    # recipe: fp32 params ARE the master weights (weights cast to bf16 at
    # each use — every matmul already does ``w.astype(hidden.dtype)``), so
    # AdamW(multi_precision) keeps no separate master copy: 1.4GB less
    # optimizer memory on the 0.7B bench model with identical numerics
    param_dtype: Optional[str] = None
    sequence_parallel: bool = False  # shard seq dim over 'mp' between blocks
    use_flash_attention: bool = True
    # ring-attention context parallelism: name of the mesh axis the sequence
    # is sharded over (e.g. "sep"); attention becomes the exact ring schedule
    # (K/V rotate via ppermute) instead of single-device flash
    context_parallel_axis: Optional[str] = None
    recompute: bool = False          # jax.checkpoint each decoder layer
    # selective remat: jax.checkpoint only the FIRST k decoder layers —
    # the application knob of analysis.autotune.remat_policy (layers are
    # homogeneous, so the policy maps "bytes to drop" to a layer count);
    # ignored when ``recompute`` is already True
    recompute_layers: Optional[int] = None
    # MoE (Qwen2-MoE / DeepSeekMoE shape, BASELINE configs[4]): >1 turns the
    # MLP into an expert-parallel MoE FFN (incubate.moe.MoELayer over 'ep')
    moe_num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_gate: str = "gshard"
    moe_aux_loss_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def pdtype(self) -> str:
        """Parameter storage dtype (see ``param_dtype``)."""
        return self.param_dtype or self.dtype


def llama_tiny_config(**overrides) -> LlamaConfig:
    """CPU-smoke scale (bench --preset tiny)."""
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=384,
               num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
               max_position_embeddings=256)
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def llama3_8b_config(**overrides) -> LlamaConfig:
    cfg = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
               max_position_embeddings=8192, rope_theta=500000.0, dtype="bfloat16")
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def llama3_70b_config(**overrides) -> LlamaConfig:
    cfg = dict(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
               num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
               max_position_embeddings=8192, rope_theta=500000.0, dtype="bfloat16")
    cfg.update(overrides)
    return LlamaConfig(**cfg)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def _raw(x):
    """Tensor-or-array -> raw jax array (cache pytrees may arrive either way)."""
    return x._data if isinstance(x, Tensor) else x


def _mesh_axis(mesh: Optional[ProcessMesh], name: str) -> Optional[int]:
    if mesh is None or name not in mesh.dim_names:
        return None
    return mesh.dim_names.index(name)


def _shard_param(p, mesh: Optional[ProcessMesh], tensor_dim: Optional[int], axis: str = "mp"):
    """Shard param dim ``tensor_dim`` over mesh axis ``axis`` (no-op without a mesh)."""
    if mesh is None:
        return p
    placements = [Replicate()] * mesh.ndim
    ax = _mesh_axis(mesh, axis)
    if ax is not None and tensor_dim is not None and p.shape[tensor_dim] % mesh.shape[ax] == 0:
        placements[ax] = Shard(tensor_dim)
    return shard_tensor(p, mesh, placements)


def _place_all_params(layer, mesh: Optional[ProcessMesh]):
    """Give every parameter WITHOUT a placement an explicit Replicate one
    (via ``shard_layer``'s default shard_fn).  Mixing mesh-committed and
    single-device-committed params in one jit fails (seen on checkpoint
    reload, where load re-commits to the saved layout); an explicit placement
    also makes dist-checkpoint dedup see them correctly."""
    if mesh is None:
        return
    from ..distributed.api import shard_layer

    shard_layer(layer, mesh)


def _batch_axes(mesh: ProcessMesh):
    axes = tuple(n for n in ("dp", "sharding") if n in mesh.dim_names) or None
    return axes[0] if isinstance(axes, tuple) and len(axes) == 1 else axes


def _hidden_shard(mesh: Optional[ProcessMesh], sequence_parallel: bool):
    """``(jax Mesh, spec)`` of the residual stream [B, S, hidden]: batch over
    'dp', optionally seq over 'mp'; None without a mesh."""
    if mesh is None:
        return None
    seq_axis = "mp" if (sequence_parallel and "mp" in mesh.dim_names) else None
    return mesh.jax_mesh, PartitionSpec(_batch_axes(mesh), seq_axis, None)


def _heads_shard(mesh: Optional[ProcessMesh]):
    """``(jax Mesh, spec)`` of q/k/v [B, S, heads, head_dim]: batch over
    'dp', heads over 'mp'; None without a mesh."""
    if mesh is None:
        return None
    return mesh.jax_mesh, PartitionSpec(
        _batch_axes(mesh), None, "mp" if "mp" in mesh.dim_names else None, None)


def _constrain_hidden(x, mesh: Optional[ProcessMesh], sequence_parallel: bool):
    """Residual-stream constraint: batch over 'dp', optionally seq over 'mp'."""
    if mesh is None:
        return x
    sharding = NamedSharding(*_hidden_shard(mesh, sequence_parallel))

    def g(h):
        if isinstance(h, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(h, sharding)
        return h  # eager: let data stay where it is

    return apply_op("sharding_constraint", g, (x,), {})


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

class LlamaRMSNorm(Layer):
    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh] = None):
        super().__init__()
        from ..nn.initializer import Constant

        self.weight = self.create_parameter(
            [config.hidden_size], dtype=config.pdtype,
            default_initializer=Constant(1.0))
        self.epsilon = config.rms_norm_eps
        self._shard = _hidden_shard(mesh, config.sequence_parallel)

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.epsilon, shard=self._shard)


def attention_fn(hidden, w_qkv, w_o, cos, sin, cfg: LlamaConfig, position_ids=None,
                 mesh=None):
    """Pure GQA attention over raw arrays: fused qkv matmul, rope, flash (or
    XLA reference) causal attention, output projection.  Shared by the
    sequential model and the pipeline model (``llama_pp``)."""
    h, hk, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    B, S, _ = hidden.shape
    qkv = hidden @ w_qkv.astype(hidden.dtype)
    q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
    q = q.reshape(B, S, h, d)
    k = k.reshape(B, S, hk, d)
    v = v.reshape(B, S, hk, d)
    q, k = rope_mod.apply_rope(q, k, cos, sin, position_ids)
    if cfg.context_parallel_axis:
        from ..distributed.parallel.context_parallel import ring_attention

        o = ring_attention(q, k, v, mesh=mesh,
                           axis_name=cfg.context_parallel_axis, causal=True)
    elif cfg.use_flash_attention:
        o = fa_mod.flash_attention(q, k, v, causal=True,
                                   shard=_heads_shard(mesh))
    else:
        rep = h // hk
        o = fa_mod._attention_reference(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            True, None, 1.0 / math.sqrt(d))
    return o.reshape(B, S, h * d) @ w_o.astype(hidden.dtype)


def cached_attention_fn(hidden, w_qkv, w_o, k_cache, v_cache, cos, sin, offset,
                        cfg: LlamaConfig):
    """Incremental GQA attention with a KV cache (the ``use_cache`` path).

    ``hidden``: the S-token chunk at absolute positions ``offset..offset+S``
    (S = prompt length at prefill, 1 per decode step).  Writes the chunk's K/V
    into the cache at ``offset`` (``dynamic_update_slice``; offset may be a
    traced scalar so one compiled program serves every decode step), then
    attends against the cache: the decode-MHA Pallas kernel for S=1, the
    absolute-causal XLA path otherwise.  Reference role:
    ``block_multi_head_attention_kernel.cu`` / ``masked_multihead_attention``.
    """
    from ..kernels import decode_attention as da

    h, hk, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    B, S, _ = hidden.shape
    qkv = hidden @ w_qkv.astype(hidden.dtype)
    q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
    q = q.reshape(B, S, h, d)
    k = k.reshape(B, S, hk, d)
    v = v.reshape(B, S, hk, d)
    pos = offset + jnp.arange(S)[None, :]  # [1, S] broadcasts over batch
    pos = jnp.broadcast_to(pos, (B, S))
    q, k = rope_mod.apply_rope(q, k, cos, sin, pos)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, offset, 0, 0))
    if S == 1:
        o = da.masked_multihead_attention(q, k_cache, v_cache, offset + 1)
    else:
        o = da.cached_attention_reference(q, k_cache, v_cache, offset)
    out = o.reshape(B, S, h * d) @ w_o.astype(hidden.dtype)
    return out, k_cache, v_cache


def paged_attention_fn(hidden, w_qkv, w_o, k_pool, v_pool, block_table,
                       lengths, cos, sin, cfg: LlamaConfig):
    """Single-token GQA attention over serving-layout paged KV pools
    (``[NB, Hk, bs, D]``; see ``kernels/decode_attention.py``).

    Per-sequence positions come from ``lengths`` (continuous batching mixes
    ragged sequences in one batch, unlike the dense path's shared offset).
    The new token's K/V is appended to each sequence's current block before
    attending. Reference role: ``block_multi_head_attention_kernel.cu``.
    """
    from ..kernels import decode_attention as da

    h, hk, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    B, S, _ = hidden.shape
    qkv = hidden @ w_qkv.astype(hidden.dtype)
    q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
    q = q.reshape(B, S, h, d)
    k = k.reshape(B, S, hk, d)
    v = v.reshape(B, S, hk, d)
    pos = lengths[:, None]  # this token's absolute position per sequence
    q, k = rope_mod.apply_rope(q, k, cos, sin, pos)
    k_pool, v_pool = da.write_paged_token(
        k_pool, v_pool, block_table, lengths,
        k.astype(k_pool.dtype), v.astype(v_pool.dtype))
    att_len = jnp.where(lengths > 0, lengths + 1, 0)  # 0 = inactive slot
    o = da.paged_decode_attention(q, k_pool, v_pool, block_table, att_len)
    out = o.reshape(B, S, h * d) @ w_o.astype(hidden.dtype)
    return out, k_pool, v_pool


def paged_chunk_attention_fn(hidden, w_qkv, w_o, k_pool, v_pool, block_table,
                             lengths, cos, sin, cfg: LlamaConfig):
    """Multi-token chunk GQA attention over paged KV pools (chunked prefill
    and prefix-cache suffix prefill; see ``serving.Engine``).

    ``hidden`` is an S-token chunk at absolute positions
    ``lengths[b]..lengths[b]+S-1``; ``lengths`` is the block-aligned context
    already resident in the pools.  Unlike the S=1 path there is no
    ``lengths > 0`` inactive-slot convention — every row is an active chunk
    (the scheduler dispatches chunks one sequence at a time), so a fresh
    prompt legitimately starts at context 0.  The chunk's K/V is scattered
    into its table-mapped blocks first, then one gather attends context +
    chunk causally.
    """
    from ..kernels import decode_attention as da

    h, hk, d = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
    B, S, _ = hidden.shape
    qkv = hidden @ w_qkv.astype(hidden.dtype)
    q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
    q = q.reshape(B, S, h, d)
    k = k.reshape(B, S, hk, d)
    v = v.reshape(B, S, hk, d)
    pos = lengths[:, None] + jnp.arange(S)[None, :]
    q, k = rope_mod.apply_rope(q, k, cos, sin, pos)
    k_pool, v_pool = da.write_paged_chunk(
        k_pool, v_pool, block_table, lengths,
        k.astype(k_pool.dtype), v.astype(v_pool.dtype))
    o = da.paged_chunk_attention(q, k_pool, v_pool, block_table, lengths)
    out = o.reshape(B, S, h * d) @ w_o.astype(hidden.dtype)
    return out, k_pool, v_pool


def _emit_active(name: str):
    """The fusion transformer's substituted callable for a seam, or None.

    Activation is scoped (``TransformPlan.apply()`` / ``emit.activate``) and
    every activated site has already passed interpret bit-identity plus
    registry admission — outside such a scope every seam runs its stock jnp
    path unchanged."""
    from ..kernels import emit

    return emit.active(name)


def mlp_fn(hidden, w_gate_up, w_down, intermediate_size: int):
    """Pure SwiGLU MLP over raw arrays with fused gate_up matmul."""
    fused = _emit_active("fuse_swiglu_mlp")
    if fused is not None:
        return fused(hidden, w_gate_up, w_down,
                     intermediate_size=intermediate_size)
    gu = hidden @ w_gate_up.astype(hidden.dtype)
    gate, up = jnp.split(gu, [intermediate_size], axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down.astype(hidden.dtype)


class LlamaAttention(Layer):
    """GQA attention with fused qkv and rope; flash attention on TPU.

    Reference: ``semi_auto_parallel_llama_model.py`` LlamaAttentionAuto +
    ``phi/kernels/gpu/flash_attn_kernel.cu:587`` semantics (causal, GQA).
    """

    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh]):
        super().__init__()
        self.config = config
        h, d = config.num_attention_heads, config.head_dim
        hk = config.kv_heads
        init = Normal(0.0, config.initializer_range)
        self.qkv_proj = self.create_parameter(
            [config.hidden_size, (h + 2 * hk) * d], dtype=config.pdtype, default_initializer=init)
        self.o_proj = self.create_parameter(
            [h * d, config.hidden_size], dtype=config.pdtype, default_initializer=init)
        _shard_param(self.qkv_proj, mesh, 1)
        _shard_param(self.o_proj, mesh, 0)
        self._mesh = mesh  # threaded to ring_attention (context parallel)

    def forward(self, x, cos, sin, position_ids=None, cache=None):
        cfg = self.config

        if isinstance(cache, tuple) and len(cache) == 4:
            # paged serving cache: (k_pool, v_pool, block_table, lengths)
            k_p, v_p, tbl, lengths = cache

            def attn_paged(hidden, w_qkv, w_o, kp, vp):
                if hidden.shape[1] > 1:  # chunked prefill over paged pools
                    return paged_chunk_attention_fn(hidden, w_qkv, w_o, kp, vp,
                                                    tbl, lengths, _raw(cos), _raw(sin), cfg)
                return paged_attention_fn(hidden, w_qkv, w_o, kp, vp,
                                          tbl, lengths, _raw(cos), _raw(sin), cfg)

            out, nk, nv = apply_op(
                "block_multihead_attention", attn_paged,
                (x, self.qkv_proj, self.o_proj, Tensor(k_p), Tensor(v_p)),
                {}, num_outputs=3)
            return out, (nk._data, nv._data)

        if cache is not None:
            k_c, v_c, offset = cache

            def attn_cached(hidden, w_qkv, w_o, kc, vc, cos_t, sin_t):
                return cached_attention_fn(hidden, w_qkv, w_o, kc, vc, cos_t, sin_t,
                                           offset, cfg)

            out, nk, nv = apply_op(
                "masked_multihead_attention", attn_cached,
                (x, self.qkv_proj, self.o_proj, Tensor(k_c), Tensor(v_c), cos, sin),
                {}, num_outputs=3)
            return out, (nk._data, nv._data)

        mesh = self._mesh

        def attn(hidden, w_qkv, w_o, cos_t, sin_t):
            return attention_fn(hidden, w_qkv, w_o, cos_t, sin_t, cfg,
                                position_ids, mesh=mesh)

        return apply_op("scaled_dot_product_attention", attn,
                        (x, self.qkv_proj, self.o_proj, cos, sin), {})


class LlamaMLP(Layer):
    """SwiGLU MLP with fused gate_up (reference LlamaMLPAuto + fused swiglu)."""

    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh]):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.gate_up_proj = self.create_parameter(
            [config.hidden_size, 2 * config.intermediate_size], dtype=config.pdtype,
            default_initializer=init)
        self.down_proj = self.create_parameter(
            [config.intermediate_size, config.hidden_size], dtype=config.pdtype,
            default_initializer=init)
        _shard_param(self.gate_up_proj, mesh, 1)
        _shard_param(self.down_proj, mesh, 0)
        self.intermediate_size = config.intermediate_size

    def forward(self, x):
        inter = self.intermediate_size

        def mlp(hidden, w_gu, w_d):
            return mlp_fn(hidden, w_gu, w_d, inter)

        return apply_op("swiglu_mlp", mlp, (x, self.gate_up_proj, self.down_proj), {})


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh]):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config, mesh)
        self.self_attn = LlamaAttention(config, mesh)
        self.post_attention_layernorm = LlamaRMSNorm(config, mesh)
        if config.moe_num_experts > 1:
            from ..incubate.moe import MoELayer

            self.mlp = MoELayer(
                config.hidden_size, config.intermediate_size, config.moe_num_experts,
                top_k=config.moe_top_k, capacity_factor=config.moe_capacity_factor,
                gate=config.moe_gate, mesh=mesh, dtype=config.dtype)
        else:
            self.mlp = LlamaMLP(config, mesh)
        self._is_moe = config.moe_num_experts > 1
        self._mesh = mesh
        self._sp = config.sequence_parallel

    def forward(self, x, cos, sin, position_ids=None, cache=None):
        """MoE configs return ``(x, aux_loss)`` so the router's load-balancing
        loss flows FUNCTIONALLY through jit/checkpoint boundaries; dense
        configs return just ``x``.  With ``cache`` (a ``(k, v, offset)``
        triple of raw arrays) the layer runs incrementally and appends the
        updated ``(k, v)`` pair to its return value."""
        if cache is not None:
            h, new_kv = self.self_attn(self.input_layernorm(x), cos, sin,
                                       position_ids, cache=cache)
        else:
            h = self.self_attn(self.input_layernorm(x), cos, sin, position_ids)
            new_kv = None
        fused_arn = (None if (self._is_moe or cache is not None)
                     else _emit_active("fuse_add_rms_norm"))
        if fused_arn is not None:
            # residual add + post-attention RMSNorm in one emitted kernel
            # (cast-epilogue site); the summed stream and its norm leave
            # VMEM exactly once
            ln = self.post_attention_layernorm
            eps = ln.epsilon

            def add_norm(xx, hh, wn):
                return fused_arn(xx, hh, wn, epsilon=eps)

            x, normed = apply_op("fuse_add_rms_norm", add_norm,
                                 (x, h, ln.weight), {}, num_outputs=2)
            x = _constrain_hidden(x, self._mesh, self._sp)
            h = self.mlp(normed)
            aux = None
        else:
            x = x + h
            x = _constrain_hidden(x, self._mesh, self._sp)
            if self._is_moe:
                h, aux = self.mlp.forward_with_aux(self.post_attention_layernorm(x))
            else:
                h = self.mlp(self.post_attention_layernorm(x))
                aux = None
        x = x + h
        x = _constrain_hidden(x, self._mesh, self._sp)
        if new_kv is not None:
            if self._is_moe:
                return x, aux, new_kv
            return x, new_kv
        if self._is_moe:
            return x, aux
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh] = None):
        super().__init__()
        self.config = config
        mesh = mesh if mesh is not None else get_mesh()
        self._mesh = mesh
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.pdtype,
            default_initializer=Normal(0.0, config.initializer_range))
        _shard_param(self.embed_tokens, mesh, 0)  # vocab-parallel
        self.layers = LayerList([LlamaDecoderLayer(config, mesh)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config, mesh)
        cos, sin = rope_mod.rope_freqs(config.head_dim, config.max_position_embeddings,
                                       config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Zero KV cache: ``{"kv": ((k, v), ...) per layer, "offset": int32}``.

        ``max_len`` is rounded up to a multiple of 128 so the decode-MHA
        Pallas kernel's block shapes always apply (extra slots are never
        attended — the length mask covers them).
        """
        cfg = self.config
        max_len = (max_len + 127) // 128 * 128
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        shape = (batch_size, max_len, cfg.kv_heads, cfg.head_dim)
        kv = tuple((jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                   for _ in range(cfg.num_hidden_layers))
        return {"kv": kv, "offset": jnp.asarray(0, jnp.int32)}

    def init_paged_pools(self, num_blocks: int, block_size: int = 128, dtype=None):
        """Serving-layout paged KV pools per layer: ``[NB, Hk, bs, D]``
        (block 0 reserved as the trash block for inactive slots)."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        shape = (num_blocks, cfg.kv_heads, block_size, cfg.head_dim)
        return (tuple(jnp.zeros(shape, dt) for _ in range(cfg.num_hidden_layers)),
                tuple(jnp.zeros(shape, dt) for _ in range(cfg.num_hidden_layers)))

    def forward(self, input_ids, position_ids=None, cache=None,
                skip_final_norm: bool = False):
        """Returns the final hidden states; for MoE configs returns
        ``(hidden, aux_loss_total)``.  With ``cache`` (from :meth:`init_cache`)
        runs incrementally and additionally returns the updated cache.

        ``skip_final_norm`` (non-cache path only) returns the PRE-norm hidden
        states so a caller owning the norm-prologue fusion (RMSNorm + lm_head
        in one emitted kernel) can apply ``self.norm``'s weight itself."""
        x = F.embedding(input_ids, self.embed_tokens)
        if self.config.pdtype != self.config.dtype:
            # fp32-stored params, bf16 compute: enter the compute dtype here;
            # every weight use downstream casts via ``.astype(hidden.dtype)``
            x = x.astype(self.config.dtype)
        x = _constrain_hidden(x, self._mesh, self.config.sequence_parallel)
        cos, sin = self.rope_cos, self.rope_sin
        is_moe = self.config.moe_num_experts > 1
        aux_total = None
        if cache is not None and "block_table" in cache:
            # paged serving cache (continuous batching; serving.Engine):
            # {"k": (pool per layer...), "v": (...), "block_table", "lengths"}
            tbl = _raw(cache["block_table"])
            lengths = _raw(cache["lengths"])
            new_k, new_v = [], []
            for layer, k_p, v_p in zip(self.layers, cache["k"], cache["v"]):
                out = layer(x, cos, sin,
                            cache=(_raw(k_p), _raw(v_p), tbl, lengths))
                *rest, kv = out
                x, aux_total = self._merge_aux(rest[0] if len(rest) == 1 else tuple(rest),
                                               aux_total, is_moe)
                new_k.append(kv[0])
                new_v.append(kv[1])
            seq = input_ids.shape[1]
            if seq > 1:  # chunk prefill: every row is an active chunk
                new_lengths = lengths + jnp.asarray(seq, lengths.dtype)
            else:        # decode: lengths == 0 marks an inactive slot
                new_lengths = lengths + (lengths > 0).astype(lengths.dtype)
            new_cache = {"k": tuple(new_k), "v": tuple(new_v),
                         "block_table": tbl,
                         "lengths": new_lengths}
            if is_moe:
                return self.norm(x), aux_total, new_cache
            return self.norm(x), new_cache
        if cache is not None:
            offset = _raw(cache["offset"])
            new_kv = []
            for layer, (k_c, v_c) in zip(self.layers, cache["kv"]):
                out = layer(x, cos, sin, cache=(_raw(k_c), _raw(v_c), offset))
                *rest, kv = out
                x, aux_total = self._merge_aux(rest[0] if len(rest) == 1 else tuple(rest),
                                               aux_total, is_moe)
                new_kv.append(kv)
            seq = input_ids.shape[1]
            new_cache = {"kv": tuple(new_kv),
                         "offset": offset + jnp.asarray(seq, jnp.int32)}
            if is_moe:
                return self.norm(x), aux_total, new_cache
            return self.norm(x), new_cache
        rl = self.config.recompute_layers
        if self.config.recompute or rl:
            from ..distributed.fleet.recompute import recompute as _rc
            for i, layer in enumerate(self.layers):
                if self.config.recompute or (rl is not None and i < rl):
                    out = _rc(layer, x, cos, sin, position_ids)
                else:
                    out = layer(x, cos, sin, position_ids)
                x, aux_total = self._merge_aux(out, aux_total, is_moe)
        else:
            for layer in self.layers:
                out = layer(x, cos, sin, position_ids)
                x, aux_total = self._merge_aux(out, aux_total, is_moe)
        if is_moe:
            return self.norm(x), aux_total
        if skip_final_norm:
            return x
        return self.norm(x)

    @staticmethod
    def _merge_aux(out, aux_total, is_moe):
        if not is_moe:
            return out, None
        x, aux = out
        return x, aux if aux_total is None else aux_total + aux


class LlamaForCausalLM(Layer):
    """Decoder + LM head + shifted-CE loss (reference LlamaForCausalLMAuto +
    ``LlamaPretrainingCriterion``)."""

    def __init__(self, config: LlamaConfig, mesh: Optional[ProcessMesh] = None):
        super().__init__()
        self.config = config
        mesh = mesh if mesh is not None else get_mesh()
        self._mesh = mesh
        self.llama = LlamaModel(config, mesh)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = self.create_parameter(
                [config.hidden_size, config.vocab_size], dtype=config.pdtype,
                default_initializer=Normal(0.0, config.initializer_range))
            _shard_param(self.lm_head, mesh, 1)
        _place_all_params(self, mesh)

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        return self.llama.init_cache(batch_size, max_len, dtype)

    def init_paged_pools(self, num_blocks: int, block_size: int = 128,
                         dtype=None):
        return self.llama.init_paged_pools(num_blocks, block_size, dtype)

    def cache_spec(self) -> dict:
        """The model half of the serving tier's ``CacheBackend`` seam:
        per-layer cache kinds plus the byte quantities a backend needs to
        account a sequence's cache without knowing the model."""
        cfg = self.config
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return {"kinds": ("attention",) * cfg.num_hidden_layers,
                "state_bytes_per_slot": 0,
                "kv_layers": cfg.num_hidden_layers,
                "kv_bytes_per_token_layer":
                    2 * cfg.kv_heads * cfg.head_dim * itemsize}

    def forward(self, input_ids, position_ids=None, cache=None):
        """Returns logits; with ``cache`` returns ``(logits, new_cache)``
        (the reference's ``use_cache=True`` contract)."""
        fused_head = (None if (cache is not None
                               or self.config.moe_num_experts > 1)
                      else _emit_active("fuse_rms_norm_head"))
        out = self.llama(input_ids, position_ids, cache=cache,
                         skip_final_norm=fused_head is not None)
        new_cache = None
        if cache is not None:
            *out_rest, new_cache = out
            out = out_rest[0] if len(out_rest) == 1 else tuple(out_rest)
        if self.config.moe_num_experts > 1:
            x, self._moe_aux = out  # consumed by compute_loss in the SAME trace
        else:
            x = out
            self._moe_aux = None
        w = self.lm_head

        if fused_head is not None:
            # norm-prologue site: final RMSNorm + vocab projection in one
            # emitted kernel; ``x`` is pre-norm (skip_final_norm above)
            norm = self.llama.norm
            eps = norm.epsilon
            if w is None:
                def head_tied_fused(hidden, wn, e):
                    return fused_head(hidden, wn, e, epsilon=eps,
                                      transpose=True)

                logits = apply_op("lm_head", head_tied_fused,
                                  (x, norm.weight, self.llama.embed_tokens), {})
            else:
                def head_fused(hidden, wn, wh):
                    return fused_head(hidden, wn, wh, epsilon=eps,
                                      transpose=False)

                logits = apply_op("lm_head", head_fused,
                                  (x, norm.weight, w), {})
        elif w is None:
            emb = self.llama.embed_tokens

            def head_tied(hidden, e):
                return hidden @ e.T.astype(hidden.dtype)

            logits = apply_op("lm_head", head_tied, (x, emb), {})
        else:
            def head(hidden, wh):
                return hidden @ wh.astype(hidden.dtype)

            logits = apply_op("lm_head", head, (x, w), {})
        if cache is not None:
            return logits, new_cache
        return logits

    def compute_loss(self, logits, labels, ignore_index: int = -100):
        """Next-token CE in fp32 over (possibly vocab-sharded) logits — the
        ParallelCrossEntropy role.  Uses the no-gather
        ``c_softmax_with_cross_entropy`` pattern (one-hot contraction instead
        of take_along_axis) so mp-sharded logits are never all-gathered."""
        from ..distributed.parallel.mp_layers import _ce_no_gather

        lb_full = labels._data if isinstance(labels, Tensor) else jnp.asarray(labels)

        def ce(lg):
            lg = lg[:, :-1, :]
            lb = lb_full[:, 1:]
            nll = _ce_no_gather(lg, lb)
            mask = (lb != ignore_index).astype(jnp.float32)
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        loss = apply_op("cross_entropy", ce, (logits,), {})
        if self.config.moe_num_experts > 1 and getattr(self, "_moe_aux", None) is not None:
            # the routers' load-balancing total from THIS forward (threaded
            # functionally through the decoder chain; forward and compute_loss
            # must run in the same trace, which TrainStep's loss_fn does)
            loss = loss + self.config.moe_aux_loss_weight * self._moe_aux
        return loss

    # ------------------------------------------------------------------
    # generation (the reference's model.generate / llm inference loop over
    # block_multi_head_attention + masked_multihead_attention kernels)
    # ------------------------------------------------------------------

    def _build_generate_pure(self, B, P, max_new, do_sample, temperature, top_k,
                             top_p, eos):
        """Pure fn (params, buffers, ids[B,P], key) -> ids[B, P+max_new]:
        prefill with cache, then ``lax.scan`` over single-token decode steps —
        ONE compiled program for the whole generation."""
        from ..jit import functional_call

        model = self
        total = P + max_new
        neg_inf = -1e30

        def sample_next(logits, key, done):
            if do_sample:
                lg = logits / max(temperature, 1e-6)
                if top_k and top_k > 0:
                    kth = jnp.sort(lg, axis=-1)[:, -int(top_k)][:, None]
                    lg = jnp.where(lg < kth, neg_inf, lg)
                if top_p < 1.0:
                    srt = jnp.sort(lg, axis=-1)[:, ::-1]
                    probs = jax.nn.softmax(srt, axis=-1)
                    csum = jnp.cumsum(probs, axis=-1)
                    keep = (csum - probs) < top_p  # always keeps the top token
                    thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
                    lg = jnp.where(lg < thresh, neg_inf, lg)
                tok = jax.random.categorical(key, lg, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            tok = tok.astype(jnp.int32)
            if eos is not None:
                tok = jnp.where(done, jnp.asarray(eos, jnp.int32), tok)
            return tok

        def step(params, buffers, ids_chunk, cache):
            logits, cache = functional_call(model, params, buffers, ids_chunk, cache=cache)
            return logits[:, -1, :].astype(jnp.float32), cache

        def pure(params, buffers, ids, key):
            cache = model.init_cache(B, total)
            last, cache = step(params, buffers, ids, cache)
            key, sub = jax.random.split(key)
            done = jnp.zeros((B,), bool)
            tok = sample_next(last, sub, done)
            if eos is not None:
                done = done | (tok == eos)

            def body(carry, _):
                cache, tok, done, key = carry
                last, cache = step(params, buffers, tok[:, None], cache)
                key, sub = jax.random.split(key)
                nxt = sample_next(last, sub, done)
                if eos is not None:
                    ndone = done | (nxt == eos)
                else:
                    ndone = done
                return (cache, nxt, ndone, key), nxt

            if max_new > 1:
                _, toks = jax.lax.scan(body, (cache, tok, done, key), None,
                                       length=max_new - 1)
                gen = jnp.concatenate([tok[:, None], jnp.swapaxes(toks, 0, 1)], axis=1)
            else:
                gen = tok[:, None]
            return jnp.concatenate([ids, gen], axis=1)

        return pure

    def generate(self, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None):
        """Autoregressive generation (greedy or temperature/top-k/top-p
        sampling).  Returns ``[B, P + max_new_tokens]`` int32 ids; sequences
        that hit ``eos_token_id`` are padded with it.  Compiled once per
        (shape, sampling-config) signature."""
        from ..framework import random as rnd

        ids = jnp.asarray(_raw(input_ids), jnp.int32)
        B, P = ids.shape
        sig = (B, P, int(max_new_tokens), bool(do_sample), float(temperature),
               int(top_k), float(top_p), eos_token_id)
        fns = getattr(self, "_generate_fns", None)
        if fns is None:
            fns = self._generate_fns = {}
        fn = fns.get(sig)
        if fn is None:
            fn = fns[sig] = jax.jit(self._build_generate_pure(*sig))
        params = {n: p._data for n, p in self.named_parameters()}
        buffers = {n: b._data for n, b in self.named_buffers()}
        return Tensor(fn(params, buffers, ids, rnd.next_key()))

    def export_generate(self, path: str, batch_size: int, prompt_len: int,
                        max_new_tokens: int, eos_token_id: Optional[int] = None):
        """AOT-export a greedy-decode program as a ``jit.save``-style artifact
        (``.jaxir`` + ``.pdiparams`` + ``.pdmodel.json``) so
        ``paddle_tpu.jit.load`` / ``inference.Predictor`` can serve generation
        (the reference's exported-inference-program + AnalysisPredictor flow)."""
        import json

        from jax import export as jax_export

        from ..framework.io import save as _save

        pure = self._build_generate_pure(batch_size, prompt_len, int(max_new_tokens),
                                         False, 1.0, 0, 1.0, eos_token_id)

        def g(params, buffers, ids):
            return pure(params, buffers, ids, jax.random.key(0))

        params = {n: p._data for n, p in self.named_parameters()}
        buffers = {n: b._data for n, b in self.named_buffers()}
        ids_struct = jax.ShapeDtypeStruct((batch_size, prompt_len), jnp.int32)
        exported = jax_export.export(jax.jit(g))(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), buffers),
            ids_struct)
        with open(path + ".jaxir", "wb") as f:
            f.write(exported.serialize())
        _save({"params": {k: np.asarray(v) for k, v in params.items()},
               "buffers": {k: np.asarray(v) for k, v in buffers.items()}},
              path + ".pdiparams")
        with open(path + ".pdmodel.json", "w") as f:
            json.dump({"inputs": [{"shape": [batch_size, prompt_len], "dtype": "int32"}],
                       "format": "jax.export.stablehlo"}, f)
