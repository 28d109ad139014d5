"""What the sparse-expert decoders of the SERVING path share
(``models/mla_moe.py``, ``models/swa_moe.py``): the dense SwiGLU of the
leading layers, which tokens of a call are real, the row a prefill's logits
are computed at, and the expert layers' counter vector.

The counter vector rides in the cache (int32, sums over the expert layers):
``[decode steps, rows, experts touched, largest expert's rows]``, then the
same four of prefill calls.  Rows are those of the experts held here.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..kernels.swiglu import swiglu
from ..nn.initializer import Normal
from ..nn.layers import Layer

__all__ = ["MOE_COUNTERS", "DenseMLP", "raw", "token_validity",
           "last_valid_rows", "moe_counts"]

# what the first eight entries of a model's ``counters`` count, in order
MOE_COUNTERS = ("moe.steps", "moe.rows", "moe.experts_touched",
                "moe.max_expert_rows", "moe.prefill_calls",
                "moe.prefill_rows", "moe.prefill_experts_touched",
                "moe.prefill_max_expert_rows")


def raw(x):
    return x._data if isinstance(x, Tensor) else x


class DenseMLP(Layer):
    """SwiGLU of ``intermediate_size``, gate and up as one matrix (gate
    first)."""

    def __init__(self, config):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.gate_up_proj = self.create_parameter(
            [config.hidden_size, 2 * config.intermediate_size],
            dtype=config.pdtype, default_initializer=init)
        self.down_proj = self.create_parameter(
            [config.intermediate_size, config.hidden_size],
            dtype=config.pdtype, default_initializer=init)

    def forward(self, x):
        x = raw(x)
        return swiglu(x @ raw(self.gate_up_proj).astype(x.dtype)) \
            @ raw(self.down_proj).astype(x.dtype)


def token_validity(cache, S: int):
    """``(n_valid [B] or None, valid [B, S] bool or None)`` of a call with
    ``cache``: the prompts' true lengths where the cache gives them
    (``n_valid``: the padded tail is routed nowhere), the live slots of a
    decode step (length 0 = inactive)."""
    n_valid = None if cache is None or cache.get("n_valid") is None \
        else raw(cache["n_valid"]).reshape(-1)
    valid = None
    if cache is not None and "block_table" in cache and S == 1:
        valid = (raw(cache["lengths"]) > 0)[:, None]
    if n_valid is not None:
        valid = jnp.arange(S)[None, :] < n_valid[:, None]
    return n_valid, valid


def last_valid_rows(x, n_valid):
    """``x [B, S, hidden]`` at position ``n_valid - 1`` (``[B, 1, hidden]``):
    the one row a prompt's head is computed at; ``x`` itself without
    ``n_valid``."""
    if n_valid is None:
        return x
    return jnp.take_along_axis(x, (n_valid - 1)[:, None, None], axis=1)


def moe_counts(stats, decode: bool):
    """The eight ``MOE_COUNTERS`` increments (int32) of one call whose
    expert layers' ``[rows, experts touched, largest expert's rows]`` sum to
    ``stats``: in the decode places or in the prefill places."""
    inc = jnp.concatenate([jnp.ones((1,), jnp.int32),
                           stats[:3].astype(jnp.int32)])
    zero = jnp.zeros((4,), jnp.int32)
    return jnp.concatenate([inc, zero] if decode else [zero, inc])
