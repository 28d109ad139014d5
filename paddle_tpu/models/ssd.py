"""SSD (state-space duality) decoder family — the O(1)-cache LLM recipe.

Counterpart of the Mamba-2-style selective state-space models: each mixer
layer is a linear recurrence whose *training* path is the duality's chunked
scan (``kernels/ssd_scan``: intra-chunk matmul form + inter-chunk state
carry, MXU-native) and whose *decode* path carries a fixed-size per-layer
recurrent state — per-token cost and cache bytes constant in context length,
the counterfactual to attention's linear KV growth that the serving tier's
``RecurrentState`` cache backend (``serving/cache_backend.py``) exists for.

Decode state per mixer layer and sequence (all fp32):

    S   [nh, N, P]   inter-chunk state at the last chunk boundary
    xb  [nh, L, P]   \
    bb  [nh, L, N]    | zero-initialized intra-chunk buffers holding the
    cb  [nh, L, N]    | partial current chunk (rows past the in-chunk
    lab [nh, L]      /  offset stay exactly zero)

Decode recomputes the CURRENT chunk's matmul form over the buffer each step
(O(L(L+N)P) per token — constant in T) instead of running a per-token
recurrence, because zero rows are exact no-ops in the chunk matmuls: the
decode step therefore reproduces the full-sequence forward BIT-FOR-BIT at
every position (enforced by ``tests/test_ssd.py``), the property the engine's
eviction/replay and the serve-vs-generate parity tests lean on.

Hybrid stacks: ``config.layer_types`` mixes ``"ssd"`` mixer blocks with
``"attention"`` Llama decoder blocks (reused wholesale from ``models.llama``)
— a sequence's cache then holds paged KV blocks for the attention layers AND
constant-size states for the SSD layers, which is exactly the per-layer
split the ``CacheBackend`` seam models.

Single-chip recipe: the SSD family does not carry GSPMD shardings yet (the
mixers are trivially 'mp'-shardable over heads; see ROADMAP).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.dispatch import apply_op
from ..framework.tensor import Tensor
from ..kernels import rope as rope_mod
from ..kernels import ssd_scan as ssd_mod
from ..kernels.ssd_scan import ssd_chunk_outputs, ssd_chunk_state
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer, LayerList
from .llama import (LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM,
                    LlamaRMSNorm, _raw)

__all__ = [
    "SSDConfig", "SSDModel", "SSDForCausalLM",
    "ssd_tiny_config", "ssd_tiny_hybrid_config", "ssd_8b_config",
]


@dataclass
class SSDConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008   # hybrid attention blocks' MLP width
    num_hidden_layers: int = 32
    num_heads: int = 32
    state_size: int = 64             # N: recurrent state rows per head
    chunk_size: int = 64             # L: the duality chunk (and decode buffer)
    num_key_value_heads: Optional[int] = None  # hybrid attention blocks
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    param_dtype: Optional[str] = None
    # per-layer kinds ("ssd" | "attention"); None -> all ssd
    layer_types: Optional[Tuple[str, ...]] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    # attention-config aliases: the serving tier's plan arithmetic and the
    # hybrid blocks address heads through the Llama field names
    @property
    def num_attention_heads(self) -> int:
        return self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_heads

    @property
    def pdtype(self) -> str:
        return self.param_dtype or self.dtype

    @property
    def types(self) -> Tuple[str, ...]:
        if self.layer_types is None:
            return ("ssd",) * self.num_hidden_layers
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {"ssd", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        return tuple(self.layer_types)

    def attn_config(self) -> LlamaConfig:
        """The Llama-block config the hybrid attention layers reuse."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_attention_heads=self.num_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            initializer_range=self.initializer_range, dtype=self.dtype,
            param_dtype=self.param_dtype)


def ssd_tiny_config(**overrides) -> SSDConfig:
    """CPU-smoke scale (tests/test_ssd.py)."""
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=384,
               num_hidden_layers=2, num_heads=4, state_size=16, chunk_size=16,
               num_key_value_heads=2, max_position_embeddings=256)
    cfg.update(overrides)
    return SSDConfig(**cfg)


def ssd_tiny_hybrid_config(**overrides) -> SSDConfig:
    """Tiny hybrid stack: one SSD mixer + one attention block."""
    cfg = dict(layer_types=("ssd", "attention"))
    cfg.update(overrides)
    return ssd_tiny_config(**cfg)


def ssd_8b_config(**overrides) -> SSDConfig:
    """Llama-3-8B-comparable shape for footprint arithmetic (PERF.md)."""
    cfg = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_heads=64, state_size=128,
               chunk_size=128, max_position_embeddings=65536,
               dtype="bfloat16")
    cfg.update(overrides)
    return SSDConfig(**cfg)


# ---------------------------------------------------------------------------
# pure mixer math (raw arrays; shared by train / prefill / decode paths)
# ---------------------------------------------------------------------------

def ssd_project(hidden, w_in, dt_bias, cfg: SSDConfig, n_valid=None):
    """Fused input projection of the mixer: one matmul producing the gate
    ``z``, scan input ``x``, state projections ``B``/``C`` and the per-head
    decay logit ``dt`` (``la = -softplus(dt + dt_bias) <= 0``).

    With ``n_valid``, positions at or past it are zeroed in ``x``/``B``/``C``
    and get ``la = 0`` (decay 1) — an EXACT no-op on the recurrence, so a
    zero-padded prefill is bit-identical to the unpadded computation (see
    ``kernels/ssd_scan.ssd_chunk_outputs``).
    """
    nh, P, N = cfg.num_heads, cfg.head_dim, cfg.state_size
    B, S, _ = hidden.shape
    proj = hidden @ w_in.astype(hidden.dtype)
    z, xp, bp, cp, dt = jnp.split(
        proj, [nh * P, 2 * nh * P, 2 * nh * P + nh * N,
               2 * nh * P + 2 * nh * N], axis=-1)
    x = xp.reshape(B, S, nh, P).astype(jnp.float32)
    bm = bp.reshape(B, S, nh, N).astype(jnp.float32)
    cm = cp.reshape(B, S, nh, N).astype(jnp.float32)
    la = -jax.nn.softplus(dt.astype(jnp.float32)
                          + dt_bias.astype(jnp.float32)[None, None, :])
    if n_valid is not None:
        ok = (jnp.arange(S) < n_valid)[None, :, None]
        x = jnp.where(ok[..., None], x, 0.0)
        bm = jnp.where(ok[..., None], bm, 0.0)
        cm = jnp.where(ok[..., None], cm, 0.0)
        la = jnp.where(ok, la, 0.0)
    return x, bm, cm, la, z


def _to_g(t):
    """[B, S, nh, K] -> [B*nh, S, K] (heads are independent recurrences)."""
    B, S, nh, K = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * nh, S, K)


def _from_g(t, B, nh):
    G, S, K = t.shape
    return t.reshape(B, nh, S, K).transpose(0, 2, 1, 3)


def _pad_t(t, Sp):
    S = t.shape[1]
    if S == Sp:
        return t
    pad = [(0, 0)] * t.ndim
    pad[1] = (0, Sp - S)
    return jnp.pad(t, pad)


def _finish(y, x, z, d, w_out, hidden_dtype, B, S, cfg):
    """Skip + gate + output projection — one shared expression so train,
    prefill and decode produce bit-identical tokens."""
    nh, P = cfg.num_heads, cfg.head_dim
    y = y + d.astype(jnp.float32)[None, None, :, None] * x
    y = y.reshape(B, S, nh * P).astype(hidden_dtype)
    y = y * jax.nn.silu(z)
    return y @ w_out.astype(hidden_dtype)


def ssd_mixer_fn(hidden, w_in, dt_bias, d, w_out, cfg: SSDConfig,
                 n_valid=None):
    """Full-sequence mixer (training / no-cache forward): chunked scan over
    the whole sequence via the Pallas kernel when enabled, else the jnp
    reference (bit-identical either way)."""
    B, S, _ = hidden.shape
    nh, L = cfg.num_heads, cfg.chunk_size
    x, bm, cm, la, z = ssd_project(hidden, w_in, dt_bias, cfg, n_valid)
    Sp = -(-S // L) * L
    xg = _to_g(_pad_t(x, Sp))
    bg = _to_g(_pad_t(bm, Sp))
    cg = _to_g(_pad_t(cm, Sp))
    lg = _to_g(_pad_t(la, Sp)[..., None])[..., 0]
    enabled, interpret = ssd_mod.fused_enabled()
    if enabled:
        yg, _s = ssd_mod.ssd_scan(xg, bg, cg, lg, chunk=L,
                                  interpret=interpret)
    else:
        yg, _s = ssd_mod.ssd_scan_reference(xg, bg, cg, lg, chunk=L)
    y = _from_g(yg, B, nh)[:, :S]
    return _finish(y, x, z, d, w_out, hidden.dtype, B, S, cfg)


def _scan_capture(xg, bg, cg, lg, L):
    """Chunked scan that also stacks the state AFTER each chunk — the
    prefill path needs the boundary state feeding the decode buffers.  Same
    per-chunk helper calls and shapes as ``ssd_scan_reference``, so ``y`` is
    bit-identical to the training path."""
    G, Sp, P = xg.shape
    N = bg.shape[-1]
    nc = Sp // L

    def per_g(carry, inp):
        xx, bb, cc, ll = inp

        def step(s, ci):
            xc, bc, cc_, lc = ci
            y = ssd_chunk_outputs(s, xc, bc, cc_, lc)
            s2 = ssd_chunk_state(s, xc, bc, lc)
            return s2, (y, s2)

        _sf, (ys, states) = jax.lax.scan(
            step, jnp.zeros((N, P), jnp.float32),
            (xx.reshape(nc, L, P), bb.reshape(nc, L, N),
             cc.reshape(nc, L, N), ll.reshape(nc, L)))
        return carry, (ys.reshape(Sp, P), states)

    _, (y, states) = jax.lax.scan(per_g, 0, (xg, bg, cg, lg))
    return y, states                       # [G, Sp, P], [G, nc, N, P]


def ssd_mixer_prefill_fn(hidden, w_in, dt_bias, d, w_out, cfg: SSDConfig,
                         n_valid):
    """Prefill with decode-state capture: outputs for every position PLUS
    the decode cache after ``n_valid`` tokens — the boundary state at the
    last full chunk and the partial chunk's rows as zero-padded buffers.

    ``n_valid`` may be traced (the engine's bucketed programs share one
    compile across prompt lengths); the boundary/buffer extraction is a
    dynamic slice at ``(n_valid // L) * L``.
    """
    B, S, _ = hidden.shape
    nh, P, N, L = cfg.num_heads, cfg.head_dim, cfg.state_size, cfg.chunk_size
    x, bm, cm, la, z = ssd_project(hidden, w_in, dt_bias, cfg, n_valid)
    Sp = -(-S // L) * L
    xg = _to_g(_pad_t(x, Sp))
    bg = _to_g(_pad_t(bm, Sp))
    cg = _to_g(_pad_t(cm, Sp))
    lg = _to_g(_pad_t(la, Sp)[..., None])[..., 0]
    yg, states = _scan_capture(xg, bg, cg, lg, L)
    G = B * nh
    nc_v = n_valid // L
    states0 = jnp.concatenate(
        [jnp.zeros((G, 1, N, P), jnp.float32), states], axis=1)
    s_b = jax.lax.dynamic_slice(
        states0, (0, nc_v, 0, 0), (G, 1, N, P))[:, 0]
    # partial-chunk buffers: rows [nc_v*L, nc_v*L + L) of the (zero-extended)
    # projections — exactly zero past n_valid, exactly empty when n_valid is
    # chunk-aligned (the slice then lands entirely in the extension)
    ext = lambda t: jnp.concatenate(          # noqa: E731
        [t, jnp.zeros((G, L) + t.shape[2:], jnp.float32)], axis=1)
    start = nc_v * L
    xb = jax.lax.dynamic_slice(ext(xg), (0, start, 0), (G, L, P))
    bb = jax.lax.dynamic_slice(ext(bg), (0, start, 0), (G, L, N))
    cb = jax.lax.dynamic_slice(ext(cg), (0, start, 0), (G, L, N))
    lab = jax.lax.dynamic_slice(ext(lg[..., None]), (0, start, 0),
                                (G, L, 1))[..., 0]
    state = {
        "s": s_b.reshape(B, nh, N, P),
        "xb": xb.reshape(B, nh, L, P),
        "bb": bb.reshape(B, nh, L, N),
        "cb": cb.reshape(B, nh, L, N),
        "lab": lab.reshape(B, nh, L),
    }
    y = _from_g(yg, B, nh)[:, :S]
    return _finish(y, x, z, d, w_out, hidden.dtype, B, S, cfg), state


def ssd_decode_step(state, xt, bt, ct, lt, j, active, L: int):
    """One decode token against the fixed-size state: write the token's
    projections at in-chunk row ``j``, recompute the chunk's matmul form,
    take row ``j``, and fold the chunk into ``S`` when it fills.

    ``state``: the per-layer dict above, batched [B, nh, ...];
    ``xt``/``bt``/``ct``/``lt``: this token's projections [B, nh, ...];
    ``j``: [B] in-chunk offsets (= context_len % L); ``active``: [B] bool —
    inactive slots hold every array bit-exactly (the engine's masked-slot
    convention).  Heads run through one ``lax.scan`` so every chunk matmul
    has the SAME unbatched [L, ...] shapes as the training scan — the
    decode-vs-full bit-parity contract.
    """
    B, nh, N, P = state["s"].shape
    G = B * nh
    s = state["s"].reshape(G, N, P)
    xb = state["xb"].reshape(G, L, P)
    bb = state["bb"].reshape(G, L, N)
    cb = state["cb"].reshape(G, L, N)
    lab = state["lab"].reshape(G, L)
    xg = xt.reshape(G, P)
    bg = bt.reshape(G, N)
    cg = ct.reshape(G, N)
    lg = lt.reshape(G)
    jg = jnp.repeat(j.astype(jnp.int32), nh)
    ag = jnp.repeat(active, nh)

    def per_g(carry, inp):
        sg, xbg, bbg, cbg, labg, xt_, bt_, ct_, lt_, j_, a_ = inp
        xb2 = jax.lax.dynamic_update_slice(xbg, xt_[None, :], (j_, 0))
        bb2 = jax.lax.dynamic_update_slice(bbg, bt_[None, :], (j_, 0))
        cb2 = jax.lax.dynamic_update_slice(cbg, ct_[None, :], (j_, 0))
        lab2 = jax.lax.dynamic_update_slice(labg, lt_[None], (j_,))
        y_all = ssd_chunk_outputs(sg, xb2, bb2, cb2, lab2)
        yj = jax.lax.dynamic_slice(y_all, (j_, 0), (1, P))[0]
        fold = j_ == (L - 1)
        s2 = jnp.where(fold, ssd_chunk_state(sg, xb2, bb2, lab2), sg)
        xb3 = jnp.where(fold, jnp.zeros_like(xb2), xb2)
        bb3 = jnp.where(fold, jnp.zeros_like(bb2), bb2)
        cb3 = jnp.where(fold, jnp.zeros_like(cb2), cb2)
        lab3 = jnp.where(fold, jnp.zeros_like(lab2), lab2)
        return carry, (yj,
                       jnp.where(a_, s2, sg), jnp.where(a_, xb3, xbg),
                       jnp.where(a_, bb3, bbg), jnp.where(a_, cb3, cbg),
                       jnp.where(a_, lab3, labg))

    _, (y, s1, xb1, bb1, cb1, lab1) = jax.lax.scan(
        per_g, 0, (s, xb, bb, cb, lab, xg, bg, cg, lg, jg, ag))
    new_state = {
        "s": s1.reshape(B, nh, N, P),
        "xb": xb1.reshape(B, nh, L, P),
        "bb": bb1.reshape(B, nh, L, N),
        "cb": cb1.reshape(B, nh, L, N),
        "lab": lab1.reshape(B, nh, L),
    }
    return y.reshape(B, nh, P), new_state


def ssd_mixer_decode_fn(hidden, w_in, dt_bias, d, w_out, cfg: SSDConfig,
                        state, j, active):
    """Single-token mixer over the recurrent state (decode path)."""
    B, S, _ = hidden.shape
    x, bm, cm, la, z = ssd_project(hidden, w_in, dt_bias, cfg)
    y, new_state = ssd_decode_step(
        state, x[:, 0], bm[:, 0], cm[:, 0], la[:, 0], j, active,
        cfg.chunk_size)
    out = _finish(y[:, None], x, z, d, w_out, hidden.dtype, B, S, cfg)
    return out, new_state


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

class SSDMixer(Layer):
    """The selective state-space mixer (z | x | B | C | dt fused in_proj)."""

    def __init__(self, config: SSDConfig):
        super().__init__()
        self.config = config
        nh, P, N = config.num_heads, config.head_dim, config.state_size
        init = Normal(0.0, config.initializer_range)
        self.in_proj = self.create_parameter(
            [config.hidden_size, 2 * nh * P + 2 * nh * N + nh],
            dtype=config.pdtype, default_initializer=init)
        # dt_bias -3 puts the initial per-token decay near exp(-softplus(-3))
        # ~ 0.95 — long enough memory for the recurrence to be non-trivial
        self.dt_bias = self.create_parameter(
            [nh], dtype="float32", default_initializer=Constant(-3.0))
        self.d_skip = self.create_parameter(
            [nh], dtype="float32", default_initializer=Constant(1.0))
        self.out_proj = self.create_parameter(
            [nh * P, config.hidden_size], dtype=config.pdtype,
            default_initializer=init)

    def forward(self, x, state=None, n_valid=None, j=None, active=None):
        cfg = self.config
        if state is None:
            def mix(h, wi, db, ds, wo):
                return ssd_mixer_fn(h, wi, db, ds, wo, cfg, n_valid)

            return apply_op("ssd_mixer", mix,
                            (x, self.in_proj, self.dt_bias, self.d_skip,
                             self.out_proj), {})
        # cache paths run inside functional_call/jit (tape off): raw jnp
        h = _raw(x)
        args = (h, _raw(self.in_proj), _raw(self.dt_bias),
                _raw(self.d_skip), _raw(self.out_proj), cfg)
        if h.shape[1] > 1:
            out, new_state = ssd_mixer_prefill_fn(
                *args, h.shape[1] if n_valid is None else n_valid)
        else:
            out, new_state = ssd_mixer_decode_fn(
                *args, {k: _raw(v) for k, v in state.items()}, j, active)
        return Tensor(out), new_state

    def init_state(self, batch_size: int):
        cfg = self.config
        nh, P, N, L = (cfg.num_heads, cfg.head_dim, cfg.state_size,
                       cfg.chunk_size)
        z = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        return {"s": z(batch_size, nh, N, P), "xb": z(batch_size, nh, L, P),
                "bb": z(batch_size, nh, L, N), "cb": z(batch_size, nh, L, N),
                "lab": z(batch_size, nh, L)}


class SSDBlock(Layer):
    """Pre-norm mixer block (Mamba-style: no separate MLP — the mixer's
    gate is the nonlinearity)."""

    def __init__(self, config: SSDConfig, acfg: LlamaConfig):
        super().__init__()
        self.norm = LlamaRMSNorm(acfg)
        self.mixer = SSDMixer(config)

    def forward(self, x, state=None, n_valid=None, j=None, active=None):
        out = self.mixer(self.norm(x), state=state, n_valid=n_valid, j=j,
                         active=active)
        if state is not None:
            h, new_state = out
            return x + h, new_state
        return x + out


class SSDModel(Layer):
    def __init__(self, config: SSDConfig, mesh=None):
        super().__init__()
        self.config = config
        acfg = config.attn_config()
        self._acfg = acfg
        self.embed_tokens = self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.pdtype,
            default_initializer=Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            LlamaDecoderLayer(acfg, None) if kind == "attention"
            else SSDBlock(config, acfg)
            for kind in config.types])
        self.norm = LlamaRMSNorm(acfg)
        if any(k == "attention" for k in config.types):
            cos, sin = rope_mod.rope_freqs(
                acfg.head_dim, config.max_position_embeddings,
                config.rope_theta)
            self.register_buffer("rope_cos", Tensor(cos), persistable=False)
            self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        else:
            self.rope_cos = self.rope_sin = None

    # -- caches -------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Dense generation cache: per-ssd-layer recurrent state dicts plus
        dense (k, v) pairs for any hybrid attention layers.  Only the
        attention share grows with ``max_len`` — a pure SSD stack's cache is
        constant-size."""
        cfg = self.config
        max_len = (max_len + 127) // 128 * 128
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        acfg = self._acfg
        kv_shape = (batch_size, max_len, acfg.kv_heads, acfg.head_dim)
        ssd_states = tuple(layer.mixer.init_state(batch_size)
                           for layer, kind in zip(self.layers, cfg.types)
                           if kind == "ssd")
        kv = tuple((jnp.zeros(kv_shape, dt), jnp.zeros(kv_shape, dt))
                   for kind in cfg.types if kind == "attention")
        return {"ssd": ssd_states, "kv": kv,
                "offset": jnp.asarray(0, jnp.int32)}

    def init_paged_pools(self, num_blocks: int, block_size: int = 128,
                         dtype=None):
        """Paged KV pools for the HYBRID attention layers only (empty tuple
        pair for a pure SSD stack)."""
        cfg = self.config
        acfg = self._acfg
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        n_attn = sum(1 for k in cfg.types if k == "attention")
        shape = (num_blocks, acfg.kv_heads, block_size, acfg.head_dim)
        return (tuple(jnp.zeros(shape, dt) for _ in range(n_attn)),
                tuple(jnp.zeros(shape, dt) for _ in range(n_attn)))

    def init_recurrent_slots(self, max_batch: int):
        """Serving-slot state arrays: one decode-state dict per SSD layer,
        batched over ``max_batch`` slots (the RecurrentState backend's
        device residency)."""
        return tuple(layer.mixer.init_state(max_batch)
                     for layer, kind in zip(self.layers, self.config.types)
                     if kind == "ssd")

    # -- forward ------------------------------------------------------------

    def forward(self, input_ids, position_ids=None, cache=None):
        cfg = self.config
        x = F.embedding(input_ids, self.embed_tokens)
        if cfg.pdtype != cfg.dtype:
            x = x.astype(cfg.dtype)
        cos, sin = self.rope_cos, self.rope_sin
        types = cfg.types
        L = cfg.chunk_size
        if cache is None:
            for layer, kind in zip(self.layers, types):
                if kind == "attention":
                    x = layer(x, cos, sin, position_ids)
                else:
                    x = layer(x)
            return self.norm(x)
        if "block_table" in cache:
            # serving decode (S == 1, continuous batching): paged pools for
            # attention layers, slot-state arrays for ssd layers
            tbl = _raw(cache["block_table"])
            lengths = _raw(cache["lengths"])
            j = lengths % jnp.asarray(L, lengths.dtype)
            active = lengths > 0
            new_ssd, new_k, new_v = [], [], []
            si = ai = 0
            for layer, kind in zip(self.layers, types):
                if kind == "attention":
                    out = layer(x, cos, sin, cache=(
                        _raw(cache["k"][ai]), _raw(cache["v"][ai]),
                        tbl, lengths))
                    x, kv = out
                    new_k.append(kv[0])
                    new_v.append(kv[1])
                    ai += 1
                else:
                    x, st = layer(x, state=cache["ssd"][si], j=j,
                                  active=active)
                    new_ssd.append(st)
                    si += 1
            new_lengths = lengths + active.astype(lengths.dtype)
            new_cache = {"ssd": tuple(new_ssd), "k": tuple(new_k),
                         "v": tuple(new_v), "block_table": tbl,
                         "lengths": new_lengths}
            return self.norm(x), new_cache
        # dense generate cache: prefill (S > 1, from offset 0) or decode
        offset = _raw(cache["offset"])
        S = input_ids.shape[1]
        n_valid = cache.get("n_valid")
        if n_valid is not None:
            n_valid = _raw(n_valid)
        B = _raw(input_ids).shape[0]
        j = jnp.broadcast_to(offset % jnp.asarray(L, jnp.int32), (B,))
        active = jnp.ones((B,), bool)
        new_ssd, new_kv = [], []
        si = ai = 0
        for layer, kind in zip(self.layers, types):
            if kind == "attention":
                k_c, v_c = cache["kv"][ai]
                out = layer(x, cos, sin,
                            cache=(_raw(k_c), _raw(v_c), offset))
                x, kv = out
                new_kv.append(kv)
                ai += 1
            else:
                x, st = layer(x, state=cache["ssd"][si], n_valid=n_valid,
                              j=j, active=active)
                new_ssd.append(st)
                si += 1
        new_cache = {"ssd": tuple(new_ssd), "kv": tuple(new_kv),
                     "offset": offset + jnp.asarray(S, jnp.int32)}
        return self.norm(x), new_cache


class SSDForCausalLM(Layer):
    """SSD decoder + LM head; the serving tier's second model family."""

    def __init__(self, config: SSDConfig, mesh=None):
        super().__init__()
        self.config = config
        self.ssd = SSDModel(config, mesh)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = self.create_parameter(
                [config.hidden_size, config.vocab_size], dtype=config.pdtype,
                default_initializer=Normal(0.0, config.initializer_range))

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        return self.ssd.init_cache(batch_size, max_len, dtype)

    def init_paged_pools(self, num_blocks: int, block_size: int = 128,
                         dtype=None):
        return self.ssd.init_paged_pools(num_blocks, block_size, dtype)

    def init_recurrent_slots(self, max_batch: int):
        return self.ssd.init_recurrent_slots(max_batch)

    def cache_spec(self):
        """The model half of the ``CacheBackend`` seam: per-layer cache
        kinds plus the byte quantities a backend needs to account a
        sequence's cache without knowing the model."""
        return ssd_cache_spec(self.config)

    def forward(self, input_ids, position_ids=None, cache=None):
        out = self.ssd(input_ids, position_ids, cache=cache)
        new_cache = None
        if cache is not None:
            x, new_cache = out
        else:
            x = out
        w = self.lm_head
        if w is None:
            emb = self.ssd.embed_tokens

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
        """Next-token CE in fp32 (same no-gather contraction as llama)."""
        from ..distributed.parallel.mp_layers import _ce_no_gather

        lb_full = labels._data if isinstance(labels, Tensor) \
            else jnp.asarray(labels)

        def ce(lg):
            lg = lg[:, :-1, :]
            lb = lb_full[:, 1:]
            nll = _ce_no_gather(lg, lb)
            mask = (lb != ignore_index).astype(jnp.float32)
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        return apply_op("cross_entropy", ce, (logits,), {})

    # generation: prefill-with-cache + lax.scan decode — the llama builder
    # is cache-shape agnostic (it only calls init_cache and forward), so the
    # SSD family reuses it verbatim
    _build_generate_pure = LlamaForCausalLM._build_generate_pure
    generate = LlamaForCausalLM.generate


def ssd_cache_spec(cfg: SSDConfig) -> dict:
    """``cache_spec`` from the config alone — pure arithmetic, so capacity
    planning (PERF tables, ``tests/test_ssd.py``) can price full-scale
    configs without instantiating their parameters."""
    nh, P, N, L = (cfg.num_heads, cfg.head_dim, cfg.state_size,
                   cfg.chunk_size)
    # one slot's decode state is fp32: S [nh,N,P] + the intra-chunk buffers
    # xb [nh,L,P], bb/cb [nh,L,N], lab [nh,L]
    state_slot = 4 * nh * (N * P + L * P + 2 * L * N + L)
    kinds = cfg.types
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {"kinds": kinds,
            "state_bytes_per_slot": state_slot * sum(
                1 for k in kinds if k == "ssd"),
            "kv_layers": sum(1 for k in kinds if k == "attention"),
            "kv_bytes_per_token_layer":
                2 * cfg.kv_heads * cfg.head_dim * itemsize}
