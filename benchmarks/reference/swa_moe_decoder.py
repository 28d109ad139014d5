"""Plain reference of the window/full attention, sparse-expert decoder
(MiMo-V2-Flash's ``config.json``) in float32 ``jax.numpy`` at the highest
matmul precision.  No kernel, no cache, no batching, nothing imported from
the program; the norm, the SwiGLU, the router and the token check are
``reference/mla_moe_decoder.py``'s (the two families route alike).

Pre-norm decoder, RMSNorm, residual after attention and after the
feed-forward.  Layer ``l`` is full where ``hybrid_layer_pattern[l] == 0`` and
window where it is 1 (ASSUMED: the config gives the list, not which value
means which; five in six are 1 and the model is described as five window
layers to one full).

- Attention: ``q = h W_q`` [H, D], ``k = h W_k`` [Hk, D], ``v = h W_v`` [Hk, dv];
  ``Hk`` is ``num_key_value_heads`` (full) or ``swa_num_key_value_heads``
  (window); query head ``g`` reads KV head ``g // (H / Hk)``.  Rotate-half RoPE
  over the first ``int(D * partial_rotary_factor)`` values of q and k
  (ASSUMED: the leading values, half-rotation), base ``rope_theta`` (full)
  or ``swa_rope_theta`` (window).  ``s_ij = q_i k_j / sqrt(D)`` for ``j <= i``,
  and in a window layer only ``i - j < sliding_window`` (ASSUMED: the window
  counts the query's own position).  Full: ``p = softmax(s)``.  Window (with
  ``add_swa_attention_sink_bias``): ``p_ij = exp(s_ij) / (sum_j' exp(s_ij') +
  exp(b_head))`` (ASSUMED: the learnable sink enters the denominator only).
  ``o_i = attention_value_scale * sum_j p_ij v_j``; ``x += concat(o) W_o``.
  Attention runs in QUERY BLOCKS (``lax.map``): one block's scores exist at a
  time, and a window layer's block reads only the keys its band can reach.
- Feed-forward: where ``moe_layer_freq[l] == 0`` a SwiGLU of
  ``intermediate_size``; elsewhere ``s = sigmoid(h W_r)`` over ALL experts, the
  ``k`` largest of ``s + bias`` chosen, weights ``s_i / sum_chosen s``, no
  scaling, no shared expert, and ``y = sum over the chosen experts HELD HERE of
  w_e E_e(h)``: the same share of the experts as the configuration
  (``experts_held_first``, ``n_routed_experts`` of
  ``published.n_routed_experts``).  What the absent experts would add is left
  out and the partial result goes on, as in the program.  The loop runs over
  the held EXPERTS, each applied to every token and weighted by 0 where it
  was not chosen (static shapes, one expert's float32 weights at a time).
- The multi-token-prediction layers are not in ``config`` and are not run.

It also holds the comparison that decides ``correct`` for a serving cell.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import mla_moe_decoder as base

F32 = jnp.float32
_low, _rms_norm, _swiglu = base._low, base._rms_norm, base._swiglu


def _rope(x, theta, rot):
    """x: [S, heads, D]; positions 0..S-1; half-rotation over the first
    ``rot`` values."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]       # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


def attention(x, w, *, heads, kv_heads, d, dv, rot, theta, window,
              value_scale, lowp=None, block=128):
    """One sequence ``x`` [S, hidden] (S a multiple of ``block`` or under
    it).  ``window``: None for a full layer."""
    s = x.shape[0]
    rep = heads // kv_heads
    q = _low(_rope((x @ w["q"]).reshape(s, heads, d), theta, rot), lowp)
    k = _low(_rope((x @ w["k"]).reshape(s, kv_heads, d), theta, rot), lowp)
    v = _low((x @ w["v"]).reshape(s, kv_heads, dv), lowp)
    q = q.reshape(s, kv_heads, rep, d)
    sinks = w["sinks"].reshape(kv_heads, rep, 1, 1) if "sinks" in w else None
    block = min(block, s)
    assert s % block == 0, "pad the sequence to a multiple of the block"
    # keys a query block can reach: all before its end, or its band
    span = s if window is None else min(s, -(-(window - 1) // block) * block
                                        + block)

    def one(i):
        q0 = i * block
        k0 = 0 if window is None else jnp.maximum(q0 + block - span, 0)
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, 0)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
        sc = jnp.einsum("qgrd,tgd->grqt", qb, kb) / math.sqrt(d)
        qi = q0 + jnp.arange(block)[:, None]
        kj = k0 + jnp.arange(span)[None, :]
        seen = kj <= qi if window is None else (kj <= qi) & (qi - kj < window)
        sc = jnp.where(seen, sc, -jnp.inf)
        m = jnp.max(sc, -1, keepdims=True)
        if sinks is not None:
            m = jnp.maximum(m, sinks)
        p = jnp.exp(sc - m)
        den = jnp.sum(p, -1, keepdims=True)
        if sinks is not None:
            den = den + jnp.exp(sinks - m)
        return jnp.einsum("grqt,tgd->qgrd", _low(p / den, lowp), vb)

    o = jax.lax.map(one, jnp.arange(s // block)).reshape(s, heads * dv)
    return (value_scale * o) @ w["o"]


def experts(x, w, *, top_k, norm_topk, first, lowp=None):
    """The held experts' part of the routed sum, and the routing gap."""
    weights, gap = base.route(x, w["router"], w["router_bias"], top_k=top_k,
                              scale=1.0, norm_topk=norm_topk)
    n = w["experts_gate_up"].shape[0]

    def one(y, args):
        gate_up, down, w_e = args
        return y + w_e[:, None] * _swiglu(x, gate_up.astype(F32),
                                          down.astype(F32), lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["experts_gate_up"], w["experts_down"],
                         weights[:, first:first + n].T))
    return y, gap


def layer(h, w, *, attn, kinds, moe, eps, lowp=None):
    """One decoder layer over one sequence ``h`` [S, hidden] in float32;
    ``w`` as ``models.swa_moe_decoder.layer_weights`` names it, any dtype
    (the stacked experts are upcast one at a time).  Returns the new hidden
    states and the routing gap per token (``inf`` for a dense layer)."""
    big = ("experts_gate_up", "experts_down")
    w = {k: v if k in big else v.astype(F32) for k, v in w.items()}
    kind = kinds["window" if "window" in w else "full"]
    h = h + attention(_rms_norm(h, w["in_norm"], eps), w, lowp=lowp,
                      **attn, **kind)
    x = _rms_norm(h, w["post_norm"], eps)
    if "router" in w:
        y, gap = experts(x, w, lowp=lowp, **moe)
    else:
        y, gap = _swiglu(x, w["gate_up"], w["down"]), jnp.full(
            h.shape[:1], jnp.inf, F32)
    return h + y, gap


def _dims(config):
    d = config["head_dim"]
    return dict(
        attn=dict(heads=config["num_attention_heads"], d=d,
                  dv=config["v_head_dim"],
                  rot=int(d * config["partial_rotary_factor"]),
                  value_scale=config["attention_value_scale"]),
        kinds={"full": dict(kv_heads=config["num_key_value_heads"],
                            theta=config["rope_theta"], window=None),
               "window": dict(kv_heads=config["swa_num_key_value_heads"],
                              theta=config["swa_rope_theta"],
                              window=config["sliding_window"])},
        moe=dict(top_k=config["num_experts_per_tok"],
                 norm_topk=config["norm_topk_prob"],
                 first=config.get("experts_held_first", 0)),
        eps=config["layernorm_epsilon"])


def forward(config, top, layers, ids):
    """Logits [S, vocab] of one sequence ``ids`` [S], and the smallest
    routing gap of every token over the expert layers."""
    dims = _dims(config)
    with jax.default_matmul_precision("highest"):
        h = top["embed"].astype(F32)[jnp.asarray(ids)]
        gap = jnp.full(h.shape[:1], jnp.inf, F32)
        for w in layers:
            h, g = layer(h, w, **dims)
            gap = jnp.minimum(gap, g)
        h = _rms_norm(h, top["norm"].astype(F32), dims["eps"])
        return h @ top["head"].astype(F32), gap


class TokenChecker(base.TokenChecker):
    """``reference/mla_moe_decoder.py``'s teacher-forced check (its docstring
    has the method and the reasons for ``ULPS`` and ``FLIP_SHARE``) over this
    family's layers.  Two things differ.  A sequence is padded to the next
    multiple of ``STEP`` positions and not to the traffic's longest (17,408
    here): a check costs what its request was long.  And a routing flip
    moves the logits only where it moves a HELD expert in or out, one flip
    in sixteen at this share, so fewer positions come out over the limit
    than in a layer that holds every expert; the quarter set aside is kept
    as it is until the chip's readings say how far it can shrink (PERF.md
    section 6)."""

    STEP = 2048

    def __init__(self, config, pad_len, out_len, lowp=None):
        self.pad_len, self.out_len = pad_len, out_len
        self._longest = pad_len
        dims = _dims(config)
        self._layer = jax.jit(functools.partial(layer, lowp=lowp, **dims))
        self._embed = jax.jit(lambda e, ids: e.astype(F32)[ids])
        eps = dims["eps"]

        @jax.jit
        def head(h, pos, norm, w):
            x = _rms_norm(h[pos], norm.astype(F32), eps)
            return x @ w.astype(F32)

        self._head = head

    def worst_gap_ulps(self, top, layer_weights, n_layers, prompt, output):
        need = len(prompt) + len(output)
        self.pad_len = min(self._longest, -(-need // self.STEP) * self.STEP)
        return super().worst_gap_ulps(top, layer_weights, n_layers, prompt,
                                      output)
