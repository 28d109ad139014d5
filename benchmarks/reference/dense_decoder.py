"""Plain reference of the dense decoder (Mistral-7B's equations: pre-norm
RMSNorm, rotate-half RoPE over the whole head, grouped-query causal
attention, SwiGLU, untied head) in float32 ``jax.numpy`` at the highest
matmul precision.  No kernel, no cache, no batching, nothing imported from
the program.  One departure from a textbook version: attention runs one
key/value group at a time (``lax.map``), so the [S, S] scores of all heads
never sit in memory together.

It also holds the two comparisons that decide ``correct``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, d]; positions 0..S-1; rotate-half."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]       # [S, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(h, w, *, heads, kv_heads, head_dim, eps, theta):
    """One decoder layer over one sequence ``h`` [S, hidden] in float32;
    ``w`` as ``models.dense_decoder.layer_weights`` names it, any dtype."""
    w = {k: v.astype(F32) for k, v in w.items()}
    s = h.shape[0]
    x = _rms_norm(h, w["in_norm"], eps)
    qkv = x @ w["qkv"]
    q, k, v = jnp.split(qkv, [heads * head_dim,
                              (heads + kv_heads) * head_dim], -1)
    q = _rope(q.reshape(s, heads, head_dim), theta)
    k = _rope(k.reshape(s, kv_heads, head_dim), theta)
    v = v.reshape(s, kv_heads, head_dim)
    rep = heads // kv_heads
    qg = q.reshape(s, kv_heads, rep, head_dim).transpose(1, 2, 0, 3)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qh, kh, vh = args                       # [rep, S, d], [S, d], [S, d]
        sc = jnp.einsum("rsd,td->rst", qh, kh) / math.sqrt(head_dim)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("rst,td->rsd", jax.nn.softmax(sc, -1), vh)

    o = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(s, heads * head_dim)
    h = h + o @ w["o"]
    x = _rms_norm(h, w["post_norm"], eps)
    gate, up = jnp.split(x @ w["gate_up"], 2, -1)
    return h + (jax.nn.silu(gate) * up) @ w["down"]


def _dims(config):
    return dict(heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"], eps=config["rms_norm_eps"],
                theta=config["rope_theta"])


def next_token_loss(config, top, layers, ids):
    """Mean next-token cross-entropy of ``ids`` [B, S] (every position but
    the last predicts its successor), one sequence at a time."""
    dims = _dims(config)

    @jax.jit
    def total(top, layers, ids):
        def one(seq):
            h = top["embed"].astype(F32)[seq]
            for w in layers:
                h = layer(h, w, **dims)
            h = _rms_norm(h, top["norm"].astype(F32), dims["eps"])
            logp = jax.nn.log_softmax(h[:-1] @ top["head"].astype(F32), -1)
            return -jnp.take_along_axis(logp, seq[1:, None], -1).sum()

        return jax.lax.map(one, ids).sum() / (ids.shape[0]
                                              * (ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        return float(total(top, layers, jnp.asarray(ids)))


# bf16 keeps 8 significant bits.  The program computes in bf16 and the
# reference in float32, and the loss is a mean over thousands of tokens of
# values near ln(vocab), so rounding errors largely cancel: what is left is
# a small bias, far under one part in 2**8 of the loss.  On the chip at
# Mistral-7B's widths the two differed by 1.4e-6 and 4.0e-5 of the loss on
# two seeds (PR 25); 2**-11 = 4.9e-4 leaves a factor of twelve.  A step
# computed in a lower precision than bf16 (fp8: 3-4 bits) or with part of the
# mathematics left out moves the loss by more than that.
TRAIN_LOSS_REL_TOL = 2.0 ** -11


def loss_agrees(got, want):
    return math.isfinite(got) and abs(got - want) <= TRAIN_LOSS_REL_TOL * abs(want)


class TokenChecker:
    """Teacher-forced check of what a server emitted: the reference's logits
    at every output position of a request, from a full forward pass over
    prompt + output, one layer's weights upcast at a time.

    Logits and not token equality: with random weights the logits are nearly
    flat and the largest changes on rounding.  An emitted token passes when
    its reference logit lies within ``ULPS`` bf16 units in the last place
    (at the magnitude of the reference's largest logit there) of that
    largest logit: the server's bf16 logits carry an error of a few ulps
    each after ``num_hidden_layers`` bf16 layers, and an argmax over them
    can pick any token whose true logit is within twice that error of the
    top.  A wrong cache row, position or mask moves logits by whole units.
    """

    ULPS = 16

    def __init__(self, config, pad_len, out_len):
        self.pad_len, self.out_len = pad_len, out_len
        dims = _dims(config)
        self._layer = jax.jit(functools.partial(layer, **dims))
        self._embed = jax.jit(lambda e, ids: e.astype(F32)[ids])
        eps = dims["eps"]

        @jax.jit
        def head(h, pos, norm, w):
            x = _rms_norm(h[pos], norm.astype(F32), eps)
            return x @ w.astype(F32)

        self._head = head

    def worst_gap_ulps(self, top, layer_weights, n_layers, prompt, output):
        """Largest (top logit - emitted token's logit) in bf16 ulps over the
        output positions of one request.  Padding sits after the sequence,
        where the causal mask keeps it from every position that is read."""
        p, n = len(prompt), len(output)
        ids = np.zeros((self.pad_len,), np.int32)
        ids[:p] = prompt
        ids[p:p + n] = output
        pos = np.zeros((self.out_len,), np.int32)
        pos[:n] = np.arange(p - 1, p + n - 1)
        with jax.default_matmul_precision("highest"):
            h = self._embed(top["embed"], jnp.asarray(ids))
            for i in range(n_layers):
                h = self._layer(h, layer_weights(i))
            logits = np.asarray(self._head(h, jnp.asarray(pos), top["norm"],
                                           top["head"]))[:n]
        best = logits.max(-1)
        got = logits[np.arange(n), np.asarray(output)]
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 2.0 ** -126)))
                      - 7)
        return float(((best - got) / ulp).max())
