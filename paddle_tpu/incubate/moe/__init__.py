"""Mixture-of-Experts with expert parallelism.

Counterpart of the reference's MoE stack
(``incubate/distributed/models/moe/moe_layer.py:119-190`` —
``global_scatter``/``global_gather`` all-to-all dispatch — and ``moe/gate/``:
naive/switch/gshard gates; SPMD rules ``phi/infermeta/spmd_rules/
moe_gate_dispatch.cc``/``moe_combine.cc``).

TPU-native design (GShard-style einsum dispatch instead of host-driven
scatter/gather):

- expert weights are STACKED ``[E, ...]`` and sharded over the 'ep' mesh axis;
- routing builds a ``[tokens, E, capacity]`` dispatch mask + combine weights;
- ``einsum('tec,td->ecd')`` moves tokens into per-expert capacity slots —
  when tokens are dp-sharded and experts ep-sharded, GSPMD lowers this to the
  all-to-all the reference issues explicitly;
- the per-expert FFN is ONE batched matmul over ``[E, C, d]`` (MXU-friendly);
- ``einsum('tec,ecd->td')`` combines expert outputs back to token order.

An explicit ``shard_map``+``lax.all_to_all`` path (``dispatch_all_to_all``)
is provided as the eager/manual counterpart of global_scatter/global_gather.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ...framework.dispatch import apply_op
from ...framework.random import next_key
from ...framework.tensor import Tensor
from ...nn.initializer import Normal
from ...nn.layers import Layer
from ...distributed.mesh import ProcessMesh, get_mesh
from ...distributed.placement import Replicate, Shard
from ...distributed.api import shard_tensor

__all__ = ["MoELayer", "top_k_gating", "dispatch_all_to_all"]


def top_k_gating(logits, top_k: int, capacity: int, gate_type: str = "gshard",
                 rng_key=None):
    """Route tokens to experts (reference ``moe/gate/{naive,switch,gshard}_gate.py``).

    logits: [T, E] fp32.  Returns (combine [T,E,C], dispatch bool [T,E,C],
    aux_loss scalar).

    - 'naive'  : plain softmax top-k, no capacity-aware aux loss (aux = 0)
    - 'switch' : top-1 with load-balancing aux loss (Switch Transformer)
    - 'gshard' : top-2, load-balancing aux loss, 2nd expert kept
                 probabilistically by its gate weight (GShard paper)
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    if gate_type == "switch":
        top_k = 1
    elif gate_type == "gshard":
        top_k = 2

    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [T, k]

    if gate_type == "gshard" and rng_key is not None:
        # keep the 2nd expert with prob proportional to its (renormalized) gate
        keep2 = jax.random.uniform(rng_key, (T,)) < (2.0 * gate_vals[:, 1]
                                                     / jnp.maximum(gate_vals[:, 0] + gate_vals[:, 1], 1e-9))
        gate_vals = gate_vals.at[:, 1].set(jnp.where(keep2, gate_vals[:, 1], 0.0))

    # load-balancing auxiliary loss (Switch/GShard): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                                # mean prob per expert
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux_loss = jnp.sum(me * ce) * E if gate_type in ("switch", "gshard") else jnp.zeros((), jnp.float32)

    # capacity assignment: position of each token in its expert's queue,
    # priority by token order (reference: position_in_expert)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    denom = jnp.maximum(jnp.sum(gate_vals, axis=1, keepdims=True), 1e-9)
    gate_norm = gate_vals / denom
    # running queue length per expert ACROSS slots, so a 2nd-choice arrival
    # never reuses a capacity position a 1st-choice arrival already holds
    base = jnp.zeros((E,), jnp.int32)
    for slot in range(gate_vals.shape[1]):
        idx = gate_idx[:, slot]                                  # [T]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)           # [T, E]
        # exclude tokens already dropped (gate zeroed)
        mask = mask * (gate_vals[:, slot] > 0).astype(jnp.int32)[:, None]
        pos = base[None, :] + jnp.cumsum(mask, axis=0) - 1       # queue position per expert
        pos_tok = jnp.sum(pos * mask, axis=1)                    # this token's position
        fits = (pos_tok < capacity) & (jnp.sum(mask, axis=1) > 0)
        onehot_cap = jax.nn.one_hot(jnp.clip(pos_tok, 0, capacity - 1), capacity,
                                    dtype=jnp.float32)           # [T, C]
        sel = mask.astype(jnp.float32) * fits[:, None].astype(jnp.float32)
        contrib = sel[:, :, None] * onehot_cap[:, None, :]       # [T, E, C]
        combine = combine + gate_norm[:, slot][:, None, None] * contrib
        dispatch = dispatch | (contrib > 0)
        base = base + jnp.sum(mask, axis=0)
    return combine, dispatch, aux_loss


def dispatch_all_to_all(expert_inputs, mesh: ProcessMesh, axis_name: str = "ep"):
    """Manual EP dispatch (reference ``global_scatter``, moe_layer.py:119).

    ``expert_inputs [E, C, d]`` sharded over 'ep' on the CAPACITY dim (each
    device holds its local tokens' slots for every expert).  Returns the same
    global values resharded over the EXPERT dim (each device holds the full
    capacity of its own experts) — one ``lax.all_to_all`` inside ``shard_map``
    over the ep axis, exactly the collective the reference's
    ``global_scatter`` issues through NCCL.  The inverse direction
    (``global_gather``) is the same call with the in/out specs swapped.
    """
    ep = mesh.get_dim_size(axis_name)
    E, C = expert_inputs.shape[0], expert_inputs.shape[1]
    if E % ep != 0:
        raise ValueError(f"num_experts {E} not divisible by ep degree {ep}")
    if C % ep != 0:
        raise ValueError(f"capacity {C} not divisible by ep degree {ep}")

    def body(x):
        # local [E, C/ep, d]: send expert-chunk j to device j, gather own
        # experts' slots from everyone -> local [E/ep, C, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=1, tiled=True)

    from ...framework.shard_map_compat import shard_map

    fn = shard_map(body, mesh=mesh.jax_mesh,
                   in_specs=PartitionSpec(None, axis_name),
                   out_specs=PartitionSpec(axis_name),
                   axis_names={axis_name})
    return fn(expert_inputs)


class MoELayer(Layer):
    """Expert-parallel MoE FFN block (reference ``MoELayer``, moe_layer.py:119).

    gate: 'naive' | 'switch' | 'gshard'.  'switch' forces top-1 and 'gshard'
    top-2 routing (matching the reference gates); capacity is sized from the
    EFFECTIVE top_k.  Experts are bias-free SwiGLU FFNs (the Qwen2-MoE /
    DeepSeekMoE expert shape) stacked [E, ...] and sharded over 'ep'; routing
    runs in fp32.

    ``forward`` returns the expert-mixed output; the load-balancing aux loss
    of that forward is ALSO returned by :meth:`forward_with_aux` — use that
    form inside traced/recompute regions so the aux value flows functionally.
    ``self.aux_loss`` mirrors the last forward's aux for logging; after a
    compiled step it may hold a dead tracer — consume it in the same trace
    (the reference adds it to the loss inside the training step too).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25, gate: str = "gshard",
                 mesh: Optional[ProcessMesh] = None, axis_name: str = "ep",
                 dtype=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        if gate == "switch":
            top_k = 1
        elif gate == "gshard":
            top_k = 2
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate_type = gate
        self.axis_name = axis_name
        mesh = mesh if mesh is not None else get_mesh()
        self._mesh = mesh

        init = Normal(0.0, 0.02)
        # router stays fp32 (routing numerics); experts follow the model dtype
        self.gate_weight = self.create_parameter([d_model, num_experts], dtype="float32",
                                                 default_initializer=init)
        self.w_gate_up = self.create_parameter([num_experts, d_model, 2 * d_hidden],
                                               dtype=dtype, default_initializer=init)
        self.w_down = self.create_parameter([num_experts, d_hidden, d_model],
                                            dtype=dtype, default_initializer=init)
        if mesh is not None and axis_name in mesh.dim_names:
            ax = mesh.dim_names.index(axis_name)
            ep = mesh.shape[ax]
            if ep > 1:
                if num_experts % ep != 0:
                    raise ValueError(
                        f"num_experts={num_experts} not divisible by {axis_name} "
                        f"degree {ep}; expert parallelism would be silently disabled")
                placements = [Replicate()] * mesh.ndim
                placements[ax] = Shard(0)
                for p in (self.w_gate_up, self.w_down):
                    shard_tensor(p, mesh, placements)
        self.aux_loss = Tensor(jnp.zeros((), jnp.float32))

    def _capacity(self, T: int) -> int:
        cap = int(math.ceil(self.capacity_factor * self.top_k * T / self.num_experts))
        return max(cap, 1)

    def forward_with_aux(self, x):
        """Returns (out, aux_loss) — both flow through the functional chain,
        safe under jit / jax.checkpoint boundaries."""
        d = self.d_model
        dh = self.d_hidden
        gate_type = self.gate_type
        top_k = self.top_k
        mesh = self._mesh
        axis = self.axis_name
        rng = next_key() if gate_type == "gshard" else None

        def moe(xd, wg, w_gu, w_dn):
            shape = xd.shape
            tokens = xd.reshape(-1, d)
            T = tokens.shape[0]
            cap = self._capacity(T)
            logits = tokens.astype(jnp.float32) @ wg.astype(jnp.float32)
            combine, dispatch, aux = top_k_gating(logits, top_k, cap, gate_type, rng)
            # dispatch into per-expert capacity slots ([E, C, d]); GSPMD emits
            # the dp<->ep all-to-all here when both axes are active
            expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(xd.dtype), tokens)
            if (mesh is not None and axis in mesh.dim_names
                    and mesh.get_dim_size(axis) > 1 and isinstance(expert_in, jax.core.Tracer)):
                expert_in = jax.lax.with_sharding_constraint(
                    expert_in, jax.sharding.NamedSharding(mesh.jax_mesh, PartitionSpec(axis)))
            # bias-free SwiGLU experts, one batched matmul pair over [E, C, .]
            gu = jnp.einsum("ecd,edh->ech", expert_in, w_gu.astype(xd.dtype))
            gate_act, up = jnp.split(gu, [dh], axis=-1)
            h = jax.nn.silu(gate_act) * up
            expert_out = jnp.einsum("ech,ehd->ecd", h, w_dn.astype(xd.dtype))
            out = jnp.einsum("tec,ecd->td", combine.astype(xd.dtype), expert_out)
            return out.reshape(shape), aux

        out, aux = apply_op("moe_dispatch", moe,
                            (x, self.gate_weight, self.w_gate_up, self.w_down),
                            {}, num_outputs=2)
        # logging mirror: ONLY in eager — a traced value would be a dead
        # tracer after the compiled step (an attractive nuisance; recipes must
        # thread the returned aux through the loss, as LlamaForCausalLM does)
        if not isinstance(aux._data, jax.core.Tracer):
            self.aux_loss = aux
        return out, aux

    def forward(self, x):
        out, _ = self.forward_with_aux(x)
        return out

from .dropless import (DroplessMoE, dropless_experts,  # noqa: E402
                       sigmoid_topk_route)

__all__ += ["DroplessMoE", "dropless_experts", "sigmoid_topk_route"]
