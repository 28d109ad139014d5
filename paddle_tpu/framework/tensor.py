"""The eager Tensor.

Counterpart of the reference's ``paddle::Tensor`` / ``phi::DenseTensor``
(``paddle/phi/api/include/tensor.h:82``, ``phi/core/dense_tensor.h:37``) plus its
``AutogradMeta`` (``eager/autograd_meta.h:61``).  The storage is a ``jax.Array``
(a PJRT buffer on TPU); autograd metadata lives directly on the Tensor.  All op
math goes through jnp/lax so the same Tensor code path works eagerly AND under
``jax.jit`` tracing (where ``_data`` holds a tracer).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from .device import current_device


def _to_jax_array(data, dtype=None, place=None):
    if isinstance(data, Tensor):
        data = data._data
    if type(data).__name__ == "LazyArray" and hasattr(data, "_concrete"):
        # deferred fragment output (jit.subgraph) re-wrapped outside dispatch:
        # keep it lazy unless a dtype change forces a recorded cast
        if dtype is not None:
            return data.astype(dtype_mod.convert_dtype(dtype))
        return data
    if isinstance(data, jax.Array) or isinstance(data, jax.core.Tracer):
        arr = data
        if dtype is not None:
            arr = arr.astype(dtype_mod.convert_dtype(dtype))
        return arr
    np_dtype = dtype_mod.convert_dtype(dtype) if dtype is not None else None
    arr = np.asarray(data, dtype=np_dtype)
    if arr.dtype == np.float64 and dtype is None:
        arr = arr.astype(np.float32)  # default dtype policy: fp32, like the reference
    if arr.dtype == np.int64 and dtype is None:
        arr = arr.astype(np.int32)  # int32 is the fast lane on TPU
    return jnp.asarray(arr)


class Tensor:
    """Eager tensor with optional autograd tape metadata."""

    __slots__ = (
        "_data",
        "stop_gradient",
        "_grad",
        "_grad_node",
        "_out_index",
        "_hooks",
        "name",
        "persistable",
        "_dist_attr",
        "__weakref__",
    )

    # make Tensor win against np arrays in mixed arithmetic
    __array_priority__ = 100

    def __init__(self, data, dtype=None, place=None, stop_gradient: bool = True, name: Optional[str] = None):
        self._data = _to_jax_array(data, dtype, place)
        if type(self._data).__name__ == "LazyArray":
            # register with the fragment recorder so a flush substitutes the
            # concrete value into THIS tensor's storage too
            import weakref

            self._data._tensors.append(weakref.ref(self))
        self.stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._out_index = 0
        self._hooks = []
        self.name = name or ""
        self.persistable = False
        self._dist_attr = None  # (ProcessMesh, placements) for dist tensors

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self):
        devs = getattr(self._data, "devices", None)
        if callable(devs):
            try:
                return next(iter(self._data.devices()))
            except Exception:
                return current_device()
        return current_device()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        self._grad = None if value is None else (value._data if isinstance(value, Tensor) else jnp.asarray(value))

    def _accumulate_grad(self, g):
        self._grad = g if self._grad is None else self._grad + g

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from . import autograd

        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self._grad is not None:
            self._grad = jnp.zeros_like(self._grad)
        else:
            self._grad = None

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(handle_self):
                if hook in self._hooks:
                    self._hooks.remove(hook)

        return _Handle()

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        t.name = self.name
        t._dist_attr = self._dist_attr
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .dispatch import apply_op

        return apply_op("clone", lambda x: x + jnp.zeros((), dtype=x.dtype), (self,), {})

    # -- conversion ---------------------------------------------------------
    def numpy(self) -> np.ndarray:
        # a writable copy, matching the reference's Tensor.numpy() semantics
        return np.array(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def astype(self, dtype) -> "Tensor":
        from .dispatch import apply_op

        d = dtype_mod.convert_dtype(dtype)
        return apply_op("cast", lambda x: x.astype(d), (self,), {})

    def cast(self, dtype) -> "Tensor":
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        # supports .to(dtype) / .to(device) / .to(device, dtype)
        dtype = kwargs.get("dtype")
        for a in args:
            if isinstance(a, str) and a.split(":")[0] in ("cpu", "gpu", "tpu"):
                continue  # single-process eager: data already lives on the active device
            else:
                dtype = a
        return self.astype(dtype) if dtype is not None else self

    def cpu(self):
        return Tensor(np.asarray(self._data), stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    # -- misc dunders -------------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_flag = f", stop_gradient={self.stop_gradient}"
        try:
            data_str = str(np.asarray(self._data))
        except Exception:
            data_str = f"<traced {self._data}>"
        return f"Tensor(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}{grad_flag},\n       {data_str})"

    def __bool__(self):
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    # -- indexing (ops installed later, these are structural) ---------------
    def __getitem__(self, idx):
        from .dispatch import apply_op

        idx = _unwrap_index(idx)
        return apply_op("getitem", lambda x: x[idx], (self,), {})

    def __setitem__(self, idx, value):
        from .dispatch import apply_op

        idx = _unwrap_index(idx)
        if isinstance(value, Tensor):
            out = apply_op(
                "setitem",
                lambda x, v: x.at[idx].set(v.astype(x.dtype)),
                (self, value),
                {},
            )
        else:
            out = apply_op("setitem", lambda x: x.at[idx].set(value), (self,), {})
        # rebind in place so the python object keeps identity (reference setitem
        # is in-place; grads flow through the functional scatter above)
        inplace_rebind_(self, out)

    def _set_data(self, value):
        """Raw in-place storage swap (optimizer updates, loading weights)."""
        self._data = value._data if isinstance(value, Tensor) else value

    def set_value(self, value):
        arr = _to_jax_array(value, dtype=self.dtype)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        arr = arr.astype(self.dtype)
        if self._dist_attr is not None:
            # keep the dist placement: loading weights must not silently
            # collapse a sharded parameter onto one device
            import jax as _jax

            from ..distributed.placement import named_sharding

            mesh, placements = self._dist_attr
            arr = _jax.device_put(arr, named_sharding(mesh, placements, arr.ndim))
        self._data = arr

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    # dist metadata (semi-auto parallel)
    @property
    def process_mesh(self):
        return self._dist_attr[0] if self._dist_attr else None

    @property
    def placements(self):
        return self._dist_attr[1] if self._dist_attr else None

    def is_dist(self) -> bool:
        return self._dist_attr is not None


def inplace_rebind_(t: "Tensor", out: "Tensor") -> "Tensor":
    """Give ``t`` the identity of ``out`` (in-place op semantics) without
    corrupting the tape: the grad node of ``out`` may hold ``t`` as an input,
    so ``t``'s OLD identity is snapshotted into a fresh Tensor first."""
    node = out._grad_node
    if node is not None and any(inp is t for inp in node.inputs):
        old = Tensor(t._data, stop_gradient=t.stop_gradient)
        old._grad_node = t._grad_node
        old._out_index = t._out_index
        old._hooks = t._hooks
        node.inputs = [old if inp is t else inp for inp in node.inputs]
    t._data = out._data
    t._grad_node = out._grad_node
    t._out_index = out._out_index
    t.stop_gradient = out.stop_gradient
    return t


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._data
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(np.asarray(idx))
    return idx


class Parameter(Tensor):
    """Trainable tensor (reference: ``EagerParamBase``). stop_gradient defaults False."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "is_distributed", "no_weight_decay")

    def __init__(self, data, dtype=None, name=None, trainable: bool = True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.no_weight_decay = False
        self.persistable = True

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """``paddle.to_tensor`` equivalent."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


def _install_device_methods():
    """paddle.Tensor device-surface methods the reference exposes: ``cuda``
    maps to the accelerator (PJRT default device), ``ndimension`` aliases
    ``dim``."""

    def cuda(self, device_id=None, blocking=True):
        import jax

        devs = jax.devices()
        target = devs[device_id or 0]
        return Tensor(jax.device_put(self._data, target))

    def ndimension(self):
        return self._data.ndim

    if not hasattr(Tensor, "cuda"):
        Tensor.cuda = cuda
    if not hasattr(Tensor, "ndimension"):
        Tensor.ndimension = ndimension


_install_device_methods()
