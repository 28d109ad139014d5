"""Optimizer base + the standard family.

Reference: ``python/paddle/optimizer/optimizer.py:127`` (Optimizer base),
``adamw.py``, ``adam.py``, ``sgd.py``, ``momentum.py``...

TPU-native design: each optimizer defines a pure functional core
(``_init_slot`` / ``_update``) over jax arrays.  The eager ``step()`` runs ONE
jitted XLA program over the whole parameter pytree (not a launch per param —
the eager counterpart of the reference's fused/multi-tensor optimizer
kernels).  The same functional core is reused by ``paddle_tpu.jit``'s compiled
train step and by the distributed sharding wrappers (ZeRO states shard along
the mesh simply by sharding the state pytree).

Master weights: with bf16/fp16 params, fp32 master copies are kept in the
state (reference ``multi_precision`` behavior) — essential on TPU where
training dtype is bf16.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Parameter, Tensor
from ..nn.clip import ClipGradBase
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad", "RMSProp",
           "Adadelta", "Adamax", "Lamb", "NAdam", "RAdam", "ASGD"]


def _is_float(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.floating)


def _wus_partition_spec(shape, n, axis_name):
    """Weight-update-sharding spec: shard the first dim divisible by the
    mesh axis size, else stay replicated (tiny/odd leaves aren't worth a
    collective)."""
    from jax.sharding import PartitionSpec

    for d, size in enumerate(shape):
        if size > 0 and size % n == 0:
            return PartitionSpec(*([None] * d + [axis_name]))
    return PartitionSpec()


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        if parameters is None:
            from ..static.graph import current_builder

            if current_builder() is None:
                raise ValueError("parameters must be provided (eager mode, like the reference)")
            # static-graph mode: minimize(loss) collects the Program's
            # trainable slots (reference static behavior)
            parameters = []
        self._parameter_list = list(parameters)
        self._lr = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        if weight_decay is None:
            self._weight_decay = 0.0
        elif isinstance(weight_decay, (int, float)):
            self._weight_decay = float(weight_decay)
        else:  # L2Decay object
            self._weight_decay = float(getattr(weight_decay, "_coeff", getattr(weight_decay, "coeff", 0.0)))
        self._step_count = 0
        self._state: Optional[List[Dict[str, jax.Array]]] = None
        self._jitted_update = None
        self._wus: Optional[tuple] = None  # (jax Mesh, axis name) — shard_update()
        self._wus_overlap = False          # gather at head of next step, not tail
        self._wus_buckets = 4              # layer groups per head-of-step gather
        self._remat_policy = None          # set_remat_policy() — read by TrainStep

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return self._lr()
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._lr = scheduler

    @property
    def _learning_rate(self):
        return self._lr

    # -- functional core (override in subclasses) -----------------------------
    def _init_slots(self, p: jax.Array) -> Dict[str, jax.Array]:
        return {}

    def _update(self, p32, g32, slots, lr, step):
        """Return (new_p32, new_slots). Pure function of arrays."""
        raise NotImplementedError

    def _decoupled_decay(self) -> bool:
        return False  # AdamW overrides

    def _fused_leaf(self, p32, g32, slots, lr, step, apply_decay, out_dtype,
                    interpret, sharding=None):
        """Optional single-pass fused kernel for one leaf's update (weight
        decay + moments + step + model-dtype cast in one HBM pass).  Returns
        ``(p32_new, slots_new, p_out)`` or None to use the reference
        expressions.  Adam/AdamW override (``kernels/adamw.py``).
        ``sharding``: the parameter's own, where it is known."""
        return None

    # -- cross-replica sharded weight update (ZeRO-1, arXiv:2004.13336) --------
    def shard_update(self, mesh=None, axis: Optional[str] = None,
                     overlap_gather: bool = False, gather_buckets: int = 4):
        """Shard the weight update across the data-parallel mesh axis.

        The optimizer slots (m/v/master) and the whole update computation are
        constrained to shard along ``axis``; the updated model-dtype params
        are constrained back to replicated, which GSPMD materializes as an
        all-gather.  Per-replica update traffic drops to 1/N and the slot
        HBM footprint drops to 1/N per chip.  Bit-exact: the update is
        purely elementwise, so each replica computes the identical IEEE ops
        on its slice and the all-gather moves bits unchanged
        (tests/test_fused_adamw.py asserts exact equality on the CPU mesh).

        ``overlap_gather=True`` moves the all-gather off the update's tail:
        ``functional()``'s update returns params still *sharded*, and the
        consumer (``jit.TrainStep``) re-gathers them at the head of the next
        step in ``gather_buckets`` layer groups, so bucket k+1's gather
        rides behind bucket k's forward compute instead of serializing
        after the update.  Same all-gather, different schedule position —
        bits are unchanged (the gather is a data movement).  The eager
        ``step()`` path ignores the flag (eager Tensors must stay
        replicated between calls).

        ``mesh`` may be a ``ProcessMesh``, a jax ``Mesh`` or None (use the
        global mesh).  ``axis`` defaults to ``'dp'`` when present, else the
        first mesh axis.  Pass ``mesh=False`` to disable.
        """
        if mesh is False:
            self._wus = None
            self._wus_overlap = False
            self._jitted_update = None
            return self
        if mesh is None:
            from ..distributed.mesh import get_mesh

            mesh = get_mesh()
            if mesh is None:
                raise ValueError("shard_update: no mesh given and no global mesh set")
        jm = getattr(mesh, "jax_mesh", mesh)
        if axis is None:
            axis = "dp" if "dp" in jm.shape else tuple(jm.shape)[0]
        if axis not in jm.shape:
            raise ValueError(f"shard_update: axis {axis!r} not in mesh axes {tuple(jm.shape)}")
        self._wus = (jm, axis)
        self._wus_overlap = bool(overlap_gather)
        self._wus_buckets = max(1, int(gather_buckets))
        self._jitted_update = None  # retrace with constraints
        return self

    def set_remat_policy(self, policy):
        """Attach a rematerialization policy to this optimizer's train step.

        ``jit.TrainStep`` reads it the same way it reads ``_wus``: the loss
        is wrapped in ``jax.checkpoint`` before ``value_and_grad``.
        ``policy`` is ``None``/"off" (disable), "full" (save nothing —
        classic remat), the name of a ``jax.checkpoint_policies`` member
        (e.g. "dots_saveable"), or a policy callable.  This is the knob
        ``analysis.autotune`` plans choose; model-level selective remat
        (``LlamaConfig.recompute_layers``) composes independently."""
        self._remat_policy = policy
        return self

    def _wus_overlap_active(self) -> bool:
        """Whether the functional update should leave params sharded for a
        head-of-next-step gather.  ``OVERLAP_GATE_INJECT=serialize`` forces
        the sequential tail-gather path regardless of ``overlap_gather`` —
        the injection hook ``scripts/overlap_gate.sh`` uses to prove the
        gate fails when overlap is lost."""
        if os.environ.get("OVERLAP_GATE_INJECT", "") == "serialize":
            return False
        return self._wus is not None and self._wus_overlap

    def _wus_constrain(self, x, replicate: bool = False):
        if self._wus is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        mesh, axis = self._wus
        spec = (PartitionSpec() if replicate
                else _wus_partition_spec(x.shape, mesh.shape[axis], axis))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    # -- state ----------------------------------------------------------------
    def _ensure_state(self):
        if self._state is None:
            self._state = []
            for p in self._parameter_list:
                slots = self._init_slots(p._data)
                if self._multi_precision and _is_float(p.dtype) and p._data.dtype != jnp.float32:
                    slots["master"] = p._data.astype(jnp.float32)
                self._state.append(slots)

    def _build_update_fn(self):
        wd = self._weight_decay
        decoupled = self._decoupled_decay()
        no_decay = [getattr(p, "no_weight_decay", False) or p.ndim <= 1 and decoupled and getattr(self, "_decay_matrices_only", False)
                    for p in self._parameter_list]
        from ..kernels.adamw import fused_enabled

        fused_on, interpret = fused_enabled()
        shardings = [getattr(p._data, "sharding", None)
                     for p in self._parameter_list]
        # fused + shard_update compose for both kernel modes: interpret
        # discharges to plain HLO (GSPMD partitions it), and the compiled
        # Mosaic custom call routes through shard_map in Adam._fused_leaf
        # (GSPMD has no partitioning rule for the custom call, so the
        # per-shard world is entered explicitly).

        def update_all(params, grads, states, lr, step):
            new_params, new_states = [], []
            for i, (p, g, s) in enumerate(zip(params, grads, states)):
                if g is None:
                    new_params.append(p)
                    new_states.append(s)
                    continue
                p32 = s.get("master", p.astype(jnp.float32) if p.dtype != jnp.float32 else p)
                g32 = self._wus_constrain(g.astype(jnp.float32))
                p32 = self._wus_constrain(p32)
                slots = {k: self._wus_constrain(v) for k, v in s.items() if k != "master"}
                res = None
                if fused_on:
                    res = self._fused_leaf(p32, g32, slots, lr, step,
                                           apply_decay=not no_decay[i],
                                           out_dtype=p.dtype, interpret=interpret,
                                           sharding=shardings[i])
                if res is not None:
                    p32_new, slots_new, p_out = res
                else:
                    if wd and not decoupled and not no_decay[i]:
                        g32 = g32 + wd * p32
                    if wd and decoupled and not no_decay[i]:
                        p32 = p32 * (1.0 - lr * wd)
                    p32_new, slots_new = self._update(p32, g32, slots, lr, step)
                    p_out = p32_new.astype(p.dtype)
                if "master" in s:
                    slots_new["master"] = p32_new
                # slots stay sharded across steps; params all-gather back
                slots_new = {k: self._wus_constrain(v) for k, v in slots_new.items()}
                new_params.append(self._wus_constrain(p_out, replicate=True))
                new_states.append(slots_new)
            return new_params, new_states

        return jax.jit(update_all)

    # -- eager step ------------------------------------------------------------
    @property
    def _param_groups(self):
        return self._parameter_list

    def step(self):
        self._ensure_state()
        if self._jitted_update is None:
            self._jitted_update = self._build_update_fn()
        params = [p._data for p in self._parameter_list]
        grads = [p._grad for p in self._parameter_list]

        if self._grad_clip is not None:
            pg = self._grad_clip(list(zip(self._parameter_list, grads)))
            grads = [g for _, g in pg]

        self._step_count += 1
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)
        new_params, new_state = self._jitted_update(params, grads, self._state, lr, step)
        for p, np_ in zip(self._parameter_list, new_params):
            p._data = np_
        self._state = new_state

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static.graph import current_builder

        builder = current_builder()
        if builder is not None:
            # static mode: attach the training directive to the Program;
            # Executor.run compiles fwd+bwd+update into one XLA program
            builder.set_optimizer(self, loss)
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- serialization ---------------------------------------------------------
    def state_dict(self) -> dict:
        self._ensure_state()
        out = {"step": self._step_count, "slots": []}
        for s in self._state:
            out["slots"].append({k: np.asarray(v) for k, v in s.items()})
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state: dict):
        self._step_count = state.get("step", 0)
        slots = state.get("slots")
        if slots is not None:
            self._state = [{k: jnp.asarray(v) for k, v in s.items()} for s in slots]
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])

    def sharded_state_dict(self) -> dict:
        """Like ``state_dict`` but slot values stay live (possibly
        ZeRO-1-sharded) jax Arrays — no all-gather onto the host.  Feed
        to ``distributed.checkpoint.save_state_dict`` / the resharding
        planner instead of ``state_dict`` when ``shard_update`` is on."""
        self._ensure_state()
        out = {"step": self._step_count,
               "slots": [dict(s) for s in self._state]}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def state_specs(self):
        """The layout ``shard_update`` imposes on each optimizer slot, for
        the resharding planner: ``(mesh, axis, [{slot: PartitionSpec}])``
        aligned with ``self._state``; ``None`` when updates are not
        sharded (everything replicated)."""
        if self._wus is None:
            return None
        mesh, axis = self._wus
        n = mesh.shape[axis]
        self._ensure_state()
        specs = [{k: _wus_partition_spec(np.shape(v), n, axis)
                  for k, v in s.items()} for s in self._state]
        return mesh, axis, specs

    # -- functional interface for jit/pjit trainers ----------------------------
    def functional(self):
        """Returns (init_fn, update_fn) over pytrees for the compiled path.

        init_fn(params_pytree) -> state_pytree
        update_fn(params, grads, state, lr, step) -> (new_params, new_state)
        Dtype policy matches the eager path: fp32 math + master weights.
        """
        self_ref = self
        wd = self._weight_decay
        decoupled = self._decoupled_decay()
        from ..kernels.adamw import fused_enabled

        fused_on, interpret = fused_enabled()  # composes with _wus, see _build_update_fn
        overlap = self._wus_overlap_active()

        shardings = []   # per flat leaf, read off the concrete params below

        def init_fn(params):
            shardings[:] = [getattr(p, "sharding", None)
                            for p in jax.tree.leaves(params)]

            def per_leaf(p):
                slots = self_ref._init_slots(p)
                if self_ref._multi_precision and _is_float(p.dtype) and p.dtype != jnp.float32:
                    slots["master"] = p.astype(jnp.float32)
                return slots

            return jax.tree.map(per_leaf, params)

        def update_fn(params, grads, state, lr, step):
            def per_leaf(p, g, s, sharding):
                p32 = s.get("master", p.astype(jnp.float32) if p.dtype != jnp.float32 else p)
                g32 = self_ref._wus_constrain(g.astype(jnp.float32))
                p32 = self_ref._wus_constrain(p32)
                slots = {k: self_ref._wus_constrain(v) for k, v in s.items() if k != "master"}
                res = None
                if fused_on:
                    res = self_ref._fused_leaf(p32, g32, slots, lr, step,
                                               apply_decay=True,
                                               out_dtype=p.dtype, interpret=interpret,
                                               sharding=sharding)
                if res is not None:
                    p32_new, slots_new, p_out = res
                else:
                    if wd and not decoupled:
                        g32 = g32 + wd * p32
                    if wd and decoupled:
                        p32 = p32 * (1.0 - lr * wd)
                    p32_new, slots_new = self_ref._update(p32, g32, slots, lr, step)
                    p_out = p32_new.astype(p.dtype)
                if "master" in s:
                    slots_new["master"] = p32_new
                slots_new = {k: self_ref._wus_constrain(v) for k, v in slots_new.items()}
                # overlap: leave params sharded — TrainStep re-gathers them at
                # the head of the next step, bucketed behind the forward
                return self_ref._wus_constrain(p_out, replicate=not overlap), slots_new

            flat_p, treedef = jax.tree.flatten(params)
            flat_g = treedef.flatten_up_to(grads)
            flat_s = treedef.flatten_up_to(state)
            leaf_sh = (shardings if len(shardings) == len(flat_p)
                       else [None] * len(flat_p))
            outs = [per_leaf(p, g, s, sh)
                    for p, g, s, sh in zip(flat_p, flat_g, flat_s, leaf_sh)]
            new_p = treedef.unflatten([o[0] for o in outs])
            new_s = treedef.unflatten([o[1] for o in outs])
            return new_p, new_s

        return init_fn, update_fn


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)

    def _update(self, p32, g32, slots, lr, step):
        return p32 - lr * g32, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None, use_nesterov=False,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_slots(self, p):
        return {"velocity": jnp.zeros(p.shape, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        v = self._momentum * slots["velocity"] + g32
        if self._nesterov:
            p_new = p32 - lr * (g32 + self._momentum * v)
        else:
            p_new = p32 - lr * v
        return p_new, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08, parameters=None,
                 weight_decay=None, grad_clip=None, lazy_mode=False, multi_precision=True,
                 use_multi_tensor=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _init_slots(self, p):
        return {"m": jnp.zeros(p.shape, jnp.float32), "v": jnp.zeros(p.shape, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = b1 * slots["m"] + (1 - b1) * g32
        v = b2 * slots["v"] + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        p_new = p32 - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return p_new, {"m": m, "v": v}

    def _fused_leaf(self, p32, g32, slots, lr, step, apply_decay, out_dtype,
                    interpret, sharding=None):
        if type(self)._update is not Adam._update:
            return None  # NAdam/RAdam override the math — no fused kernel
        if set(slots) != {"m", "v"} or p32.dtype != jnp.float32:
            return None
        import functools

        from ..kernels.adamw import adamw_update

        kernel = functools.partial(
            adamw_update,
            beta1=self._beta1, beta2=self._beta2, epsilon=self._epsilon,
            weight_decay=self._weight_decay, decoupled=self._decoupled_decay(),
            apply_decay=apply_decay, out_dtype=out_dtype, interpret=interpret)
        # GSPMD has no partitioning rule for the Mosaic custom call, so the
        # per-shard world is entered explicitly (kernels.per_shard): the
        # update is purely elementwise, so each device runs the kernel on
        # its own shard, bit-exact vs the unsharded kernel
        # (tests/test_fused_adamw.py).  Under ZeRO-1 the shard is the slot's;
        # otherwise it is the parameter's own layout.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..kernels import per_shard

        shard = None
        if self._wus is not None:
            mesh, axis = self._wus
            spec = _wus_partition_spec(p32.shape, mesh.shape[axis], axis)
            if spec != P():   # replicated leaves fall through
                shard = (mesh, spec)
        if (shard is None and not interpret
                and isinstance(sharding, NamedSharding)
                and sharding.mesh.size > 1):
            shard = (sharding.mesh, sharding.spec)
        p_new, m, v, p_out = per_shard(kernel, shard, 4, 2)(
            p32, g32, slots["m"], slots["v"], lr, step)
        return p_new, {"m": m, "v": v}, p_out


class AdamW(Adam):
    """Decoupled weight decay (reference ``python/paddle/optimizer/adamw.py``)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08, parameters=None,
                 weight_decay=0.01, lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision, name=name)
        self._apply_decay_param_fun = apply_decay_param_fun
        if apply_decay_param_fun is not None:
            for p in self._parameter_list:
                if not apply_decay_param_fun(p.name):
                    p.no_weight_decay = True

    def _decoupled_decay(self):
        return True


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {"moment": jnp.full(p.shape, self._init_acc, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        mom = slots["moment"] + jnp.square(g32)
        p_new = p32 - lr * g32 / (jnp.sqrt(mom) + self._epsilon)
        return p_new, {"moment": mom}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0, centered=False,
                 parameters=None, weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _init_slots(self, p):
        s = {"mean_square": jnp.zeros(p.shape, jnp.float32), "momentum": jnp.zeros(p.shape, jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros(p.shape, jnp.float32)
        return s

    def _update(self, p32, g32, slots, lr, step):
        ms = self._rho * slots["mean_square"] + (1 - self._rho) * jnp.square(g32)
        out = {"mean_square": ms}
        if self._centered:
            mg = self._rho * slots["mean_grad"] + (1 - self._rho) * g32
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * slots["momentum"] + lr * g32 / denom
        out["momentum"] = mom
        return p32 - mom, out


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._epsilon = epsilon
        self._rho = rho

    def _init_slots(self, p):
        return {"avg_sq_grad": jnp.zeros(p.shape, jnp.float32), "avg_sq_update": jnp.zeros(p.shape, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        rho, eps = self._rho, self._epsilon
        asg = rho * slots["avg_sq_grad"] + (1 - rho) * jnp.square(g32)
        update = g32 * jnp.sqrt(slots["avg_sq_update"] + eps) / jnp.sqrt(asg + eps)
        asu = rho * slots["avg_sq_update"] + (1 - rho) * jnp.square(update)
        return p32 - lr * update, {"avg_sq_grad": asg, "avg_sq_update": asu}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"m": jnp.zeros(p.shape, jnp.float32), "inf_norm": jnp.zeros(p.shape, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = b1 * slots["m"] + (1 - b1) * g32
        u = jnp.maximum(b2 * slots["inf_norm"], jnp.abs(g32))
        t = step.astype(jnp.float32)
        p_new = p32 - lr / (1 - b1 ** t) * m / (u + eps)
        return p_new, {"m": m, "inf_norm": u}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-06, parameters=None, grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, multi_precision, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, p):
        return {"m": jnp.zeros(p.shape, jnp.float32), "v": jnp.zeros(p.shape, jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = b1 * slots["m"] + (1 - b1) * g32
        v = b2 * slots["v"] + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + self._lamb_wd * p32
        w_norm = jnp.linalg.norm(p32)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p32 - lr * trust * r, {"m": m, "v": v}


class NAdam(Adam):
    def _update(self, p32, g32, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = b1 * slots["m"] + (1 - b1) * g32
        v = b2 * slots["v"] + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        m_bar = b1 * m_hat + (1 - b1) * g32 / (1 - b1 ** t)
        return p32 - lr * m_bar / (jnp.sqrt(v_hat) + eps), {"m": m, "v": v}


class RAdam(Adam):
    def _update(self, p32, g32, slots, lr, step):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        m = b1 * slots["m"] + (1 - b1) * g32
        v = b2 * slots["v"] + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        rho_inf = 2.0 / (1 - b2) - 1
        rho_t = rho_inf - 2 * t * (b2 ** t) / (1 - b2 ** t)
        m_hat = m / (1 - b1 ** t)

        def rect_update():
            r = jnp.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf) / ((rho_inf - 4) * (rho_inf - 2) * rho_t))
            v_hat = jnp.sqrt(v / (1 - b2 ** t))
            return p32 - lr * r * m_hat / (v_hat + eps)

        p_new = jnp.where(rho_t > 5.0, rect_update(), p32 - lr * m_hat)
        return p_new, {"m": m, "v": v}


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)

    def _update(self, p32, g32, slots, lr, step):
        return p32 - lr * g32, slots


class Rprop(Optimizer):
    """Resilient backpropagation (reference ``optimizer/rprop.py``):
    per-weight step sizes grown/shrunk by the sign agreement of successive
    gradients; only the gradient SIGN is used."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._lr_min, self._lr_max = learning_rate_range
        self._eta_neg, self._eta_pos = etas

    def _init_slots(self, p):
        return {"prev_grad": jnp.zeros(p.shape, jnp.float32),
                "step_size": jnp.full(p.shape, float(self._learning_rate
                                                     if isinstance(self._learning_rate, (int, float))
                                                     else 0.001), jnp.float32)}

    def _update(self, p32, g32, slots, lr, step):
        sign = jnp.sign(g32 * slots["prev_grad"])
        scale = jnp.where(sign > 0, self._eta_pos,
                          jnp.where(sign < 0, self._eta_neg, 1.0))
        step_size = jnp.clip(slots["step_size"] * scale, self._lr_min, self._lr_max)
        # on sign flip: no move this step, zero the stored grad (classic Rprop-)
        g_eff = jnp.where(sign < 0, 0.0, g32)
        p_new = p32 - step_size * jnp.sign(g_eff)
        return p_new, {"prev_grad": g_eff, "step_size": step_size}


class LBFGS(Optimizer):
    """Limited-memory BFGS with strong-Wolfe line search (reference
    ``optimizer/lbfgs.py``; torch-style closure API).

    Host-driven (each iteration re-evaluates the closure), like the
    reference: ``opt.step(closure)`` where ``closure()`` recomputes the loss
    with gradients.
    """

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, name=None):
        super().__init__(learning_rate, parameters, None, None, True, name)
        self._max_iter = max_iter
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history = history_size
        self._line_search = line_search_fn
        self._s: list = []
        self._y: list = []

    def _flat_params(self):
        import numpy as _np

        return _np.concatenate([_np.asarray(p._data).ravel()
                                for p in self._parameter_list])

    def _flat_grads(self):
        import numpy as _np

        return _np.concatenate([
            (_np.asarray(p._grad).ravel() if p._grad is not None
             else _np.zeros(p.size, _np.float32))
            for p in self._parameter_list])

    def _assign(self, flat):
        import numpy as _np

        off = 0
        for p in self._parameter_list:
            n = p.size
            p._data = jnp.asarray(flat[off:off + n].reshape(p.shape),
                                  p._data.dtype)
            off += n

    def _direction(self, g):
        import numpy as _np

        q = g.copy()
        alphas = []
        for s, y in zip(reversed(self._s), reversed(self._y)):
            rho = 1.0 / max(float(y @ s), 1e-10)
            a = rho * (s @ q)
            alphas.append((a, rho, s, y))
            q -= a * y
        if self._y:
            y_last, s_last = self._y[-1], self._s[-1]
            q *= float(s_last @ y_last) / max(float(y_last @ y_last), 1e-10)
        for a, rho, s, y in reversed(alphas):
            b = rho * (y @ q)
            q += s * (a - b)
        return -q

    def step(self, closure=None):
        import numpy as _np

        if closure is None:
            raise ValueError("LBFGS.step needs a closure re-evaluating the loss")
        loss = closure()
        f = float(_np.asarray(loss._data if hasattr(loss, "_data") else loss))
        g = self._flat_grads().astype(_np.float64)
        x = self._flat_params().astype(_np.float64)
        lr = float(self.get_lr())

        for _ in range(self._max_iter):
            if _np.max(_np.abs(g)) <= self._tol_grad:
                break
            d = self._direction(g)
            # backtracking Armijo line search (strong-Wolfe optional)
            t = lr
            gtd = float(g @ d)
            if gtd > -1e-16:  # not a descent direction: reset memory
                self._s.clear()
                self._y.clear()
                d = -g
                gtd = float(g @ d)
            ok = False
            for _ls in range(20):
                self._assign((x + t * d).astype(_np.float32))
                self.clear_grad()
                new_loss = closure()
                f_new = float(_np.asarray(new_loss._data
                                          if hasattr(new_loss, "_data") else new_loss))
                if f_new <= f + 1e-4 * t * gtd:
                    ok = True
                    break
                t *= 0.5
            if not ok:
                self._assign(x.astype(_np.float32))
                break
            g_new = self._flat_grads().astype(_np.float64)
            x_new = x + t * d
            self._s.append(x_new - x)
            self._y.append(g_new - g)
            if len(self._s) > self._history:
                self._s.pop(0)
                self._y.pop(0)
            if _np.max(_np.abs(x_new - x)) <= self._tol_change:
                x, g, f = x_new, g_new, f_new
                break
            x, g, f = x_new, g_new, f_new
        self._assign(x.astype(_np.float32))
        self.clear_grad()
        self._step_count += 1
        return f
