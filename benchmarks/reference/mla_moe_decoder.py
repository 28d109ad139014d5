"""Plain reference of the latent-attention, sparse-expert decoder (the
language model of Kimi-VL-A3B, which has DeepSeek-V3's layer equations
without query compression) in float32 ``jax.numpy`` at the highest matmul
precision.  No kernel, no cache, no batching, nothing imported from the
program.

Pre-norm decoder, RMSNorm, residual after attention and after the
feed-forward.

- Attention (MLA, ``q_lora_rank`` null): ``q = x W_q`` gives per head
  ``[q_nope | q_rope]``; ``[c_raw | k_r] = x W_kva``; ``c = RMSNorm(c_raw)``;
  per head ``[k_nope | v] = c W_kvb``.  RoPE on ``q_rope`` and on ``k_r`` (one
  vector shared by all heads), ``k = [k_nope | k_r]``, scores
  ``q k^T / sqrt(nope + rope)``, causal softmax, ``o = concat_h(P v) W_o``.
  Attention is always EXPANDED here (keys and values rebuilt from ``c``);
  the program's decode path absorbs ``W_kvb`` into the query and the output
  instead, which is the same mathematics in another order.
- RoPE pairs: the published modeling code rotates ADJACENT pairs
  ``(2i, 2i+1)`` by ``pos * theta^(-2i/d)`` and leaves the result
  de-interleaved (even members first, then odd).  The same is done here.
  Scores are invariant to the order the rotated members are stored in, as
  long as queries and keys use the same one.
- Layers below ``first_k_dense_replace``: SwiGLU of ``intermediate_size``.
- Other layers: ``s = sigmoid(x W_g)`` (float32); the ``k`` largest of
  ``s + b`` are chosen (``b`` the selection bias; one group, so no group
  step); weights ``s_i / sum_chosen s`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``y = sum_i w_i E_i(x) + S(x)``, each ``E_i`` a
  SwiGLU of ``moe_intermediate_size``, ``S`` one SwiGLU of ``n_shared_experts``
  times that.  Nothing is dropped.  Departure from a textbook loop over each
  token's chosen experts: the loop runs over the EXPERTS (``lax.scan``), each
  applied to every token and weighted by 0 where it was not chosen, so that
  shapes are static and one expert's float32 weights exist at a time.

It also holds the comparison that decides ``correct`` for a serving cell.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, d]; positions 0..S-1; adjacent pairs rotated, result
    stored even members first."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]       # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _low(x, lowp):
    """``x`` rounded to the dtype ``lowp`` and back (None: as it is).  Only
    the precision readings use it: what the comparison reads when a product's
    operands carry fewer bits than the configuration states."""
    return x if lowp is None else x.astype(lowp).astype(F32)


def _swiglu(x, gate_up, down, lowp=None):
    gate, up = jnp.split(_low(x, lowp) @ _low(gate_up, lowp), 2, -1)
    return _low(jax.nn.silu(gate) * up, lowp) @ _low(down, lowp)


def attention(x, w, *, heads, nope, rope, v_dim, eps, theta, lowp=None):
    """Expanded latent attention over one sequence ``x`` [S, hidden]."""
    s = x.shape[0]
    rank = w["kv_norm"].shape[0]
    q = (x @ w["q"]).reshape(s, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    kva = x @ w["kv_a"]
    c = _rms_norm(kva[:, :rank], w["kv_norm"], eps)
    k_r = _rope(kva[:, None, rank:], theta)[:, 0]                # [S, rope]
    kv = (c @ w["kv_b"]).reshape(s, heads, nope + v_dim)
    k_nope, v = _low(kv[..., :nope], lowp), _low(kv[..., nope:], lowp)
    q_nope, q_rope, k_r = (_low(t, lowp) for t in (q_nope, q_rope, k_r))
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(args):
        qn, qr, kn, vh = args                   # [S, nope] [S, rope] ...
        sc = (qn @ kn.T + qr @ k_r.T) / math.sqrt(nope + rope)
        sc = jnp.where(causal, sc, -jnp.inf)
        return _low(jax.nn.softmax(sc, -1), lowp) @ vh

    o = jax.lax.map(head, tuple(t.transpose(1, 0, 2)
                                for t in (q_nope, q_rope, k_nope, v)))
    return o.transpose(1, 0, 2).reshape(s, heads * v_dim) @ w["o"]


def route(x, router, bias, *, top_k, scale, norm_topk):
    """``(weights [T, E], gap [T])``: the dense matrix of routing weights
    (0 where an expert was not chosen) and, per token, how far the last
    chosen selection score lies above the first one left out."""
    s = jax.nn.sigmoid(x @ router)
    sel = s + bias
    ranked = jnp.sort(sel, -1)[:, ::-1]
    chosen = sel >= ranked[:, top_k - 1:top_k]
    w = jnp.where(chosen, s, 0.0)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * scale, ranked[:, top_k - 1] - ranked[:, top_k]


def experts(x, w, *, top_k, scale, norm_topk, lowp=None):
    weights, gap = route(x, w["router"], w["router_bias"], top_k=top_k,
                         scale=scale, norm_topk=norm_topk)

    def one(y, args):
        gate_up, down, w_e = args
        return y + w_e[:, None] * _swiglu(x, gate_up.astype(F32),
                                          down.astype(F32), lowp), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["experts_gate_up"], w["experts_down"], weights.T))
    return y + _swiglu(x, w["shared_gate_up"].astype(F32),
                       w["shared_down"].astype(F32)), gap


def layer(h, w, *, attn, moe, eps, lowp=None):
    """One decoder layer over one sequence ``h`` [S, hidden] in float32;
    ``w`` as ``models.mla_moe_decoder.layer_weights`` names it, any dtype
    (the stacked experts are upcast one at a time).  Returns the new hidden
    states and the routing gap per token (``inf`` for a dense layer)."""
    big = ("experts_gate_up", "experts_down", "shared_gate_up", "shared_down")
    w = {k: v if k in big else v.astype(F32) for k, v in w.items()}
    h = h + attention(_rms_norm(h, w["in_norm"], eps), w, eps=eps,
                      lowp=lowp, **attn)
    x = _rms_norm(h, w["post_norm"], eps)
    if "router" in w:
        y, gap = experts(x, w, lowp=lowp, **moe)
    else:
        y, gap = _swiglu(x, w["gate_up"], w["down"]), jnp.full(
            h.shape[:1], jnp.inf, F32)
    return h + y, gap


def _dims(config):
    return dict(
        attn=dict(heads=config["num_attention_heads"],
                  nope=config["qk_nope_head_dim"],
                  rope=config["qk_rope_head_dim"],
                  v_dim=config["v_head_dim"], theta=config["rope_theta"]),
        moe=dict(top_k=config["num_experts_per_tok"],
                 scale=config["routed_scaling_factor"],
                 norm_topk=config["norm_topk_prob"]),
        eps=config["rms_norm_eps"])


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


class TokenChecker:
    """Teacher-forced check of what a server emitted: the reference's logits
    at every output position of a request, from a full forward pass over
    prompt + output, one layer's weights upcast at a time (one expert's at a
    time inside an expert layer).

    Logits and not token equality: with random weights the logits are nearly
    flat and the largest changes on rounding.  An emitted token passes when
    its reference logit lies within ``ULPS`` bf16 units in the last place
    (at the magnitude of the reference's largest logit there) of that
    largest logit.  The reason for 16 is the dense decoder's: the server's
    bf16 logits carry an error of a few ulps each after the layers' bf16
    roundings, and an argmax over them can pick any token whose true logit
    is within twice that error of the top.

    Routing is a discontinuity, and at these widths it is met all the time.
    The program computes router scores in float32, as the published code
    does, but from hidden states that carry its bf16 roundings; the
    reference routes by its own scores and never sees the program's choice.
    Where the sixth and the seventh selection score of a token lie closer
    than the program's error, the two choose differently, one expert of six
    is then another, and that token's logits differ by tens of ulps although
    neither side is wrong.  Measured on the chip at the published widths
    (my chip runs, PR 35; 56 requests, 10,491 positions; PERF.md section 6):
    positions over the limit had routing gaps up to 5.2e-3 (in units of the
    sigmoid score), so the program's scores are off by a few 1e-3 by the
    ninth layer; 51-59% of all tokens have a gap under 1.5e-3 in some expert
    layer and 91-95% one under 5e-3, so no margin on the gap leaves
    positions to judge; and 3-12% of a request's positions (6.1% of all) do
    come out over the limit, by up to 106 ulps, while the rest read 0-6.
    What is done: the ``FLIP_SHARE`` of a request's positions with the
    LARGEST gaps (a quarter: twice the most that was measured) is set
    aside, whatever their routing, and the largest gap among the rest is
    the reading.  The two readings the limit lies between: the program as it
    is reads at most 1.5 ulps over those 56 requests; the reference with the
    operands of its attention and expert products rounded to float8 (e4m3)
    puts 89-98% of the positions over the limit and reads 66-97 ulps (8
    requests): 16 leaves a factor of eleven below and of four above.  A
    wrong cache row, position, mask or expert moves the logits of every
    later position by whole units and fails likewise.  A fault that touches
    under a quarter of the positions is the CPU tests' to find, which
    compare every position's logits in float32.  Every run prints, on the
    line ``{"phase": "routing", ...}``, how many positions were over the
    limit, the routing gaps they had and how common such gaps are.
    """

    ULPS = 16
    FLIP_SHARE = 0.25

    def __init__(self, config, pad_len, out_len, lowp=None):
        self.pad_len, self.out_len = pad_len, out_len
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
        """Largest (top logit - emitted token's logit) in bf16 ulps over the
        output positions of one request, the ``FLIP_SHARE`` of them with the
        largest gaps set aside.  Padding sits after the sequence, where the
        causal mask keeps it from every position that is read."""
        p, n = len(prompt), len(output)
        ids = np.zeros((self.pad_len,), np.int32)
        ids[:p] = prompt
        ids[p:p + n] = output
        pos = np.zeros((self.out_len,), np.int32)
        pos[:n] = np.arange(p - 1, p + n - 1)
        with jax.default_matmul_precision("highest"):
            h = self._embed(top["embed"], jnp.asarray(ids))
            route_gap = jnp.full((self.pad_len,), jnp.inf, F32)
            for i in range(n_layers):
                h, g = self._layer(h, layer_weights(i))
                route_gap = jnp.minimum(route_gap, g)
            logits = np.asarray(self._head(h, jnp.asarray(pos), top["norm"],
                                           top["head"]))[:n]
        route_gap = np.asarray(route_gap)
        best = logits.max(-1)
        got = logits[np.arange(n), np.asarray(output)]
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 2.0 ** -126)))
                      - 7)
        gaps = (best - got) / ulp
        kept = max(1, n - int(self.FLIP_SHARE * n))
        reading = float(np.sort(gaps)[kept - 1])
        over = gaps > self.ULPS
        own = route_gap[pos[:n]]
        print(json.dumps({
            "phase": "routing", "positions": n, "over_limit": int(over.sum()),
            "set_aside": n - kept, "reading_ulps": reading,
            "largest_ulps": float(gaps.max()),
            # what routing gaps the positions over the limit had, and how
            # common such gaps are among all tokens of the sequence
            "route_gaps_over_limit": [float(g) for g in
                                      np.sort(own[over])[::-1][:4]],
            "tokens_with_gap_under": {
                str(m): float((route_gap[:p + n] < m).mean())
                for m in (1.5e-3, 5e-3)}}), flush=True)
        return reading
