"""Fused RMSNorm.

Counterpart of the reference's ``fused_rms_norm`` (``phi/kernels/fusion/gpu``,
Python API ``incubate/nn/functional/fused_rms_norm.py``).  On TPU a Pallas
kernel keeps the row statistics in VMEM; on CPU the jnp form is used (XLA
fuses it anyway — the Pallas version exists to guarantee the fusion and to
keep fp32 statistics under bf16 inputs).

The Pallas forward carries an analytic custom VJP (pallas_call itself does not
support reverse-mode autodiff): with g = dy*w, x_hat = x*rsqrt(var+eps),

    dx = r * (g - x_hat * mean(g * x_hat))
    dw = sum_rows(dy * x_hat)

computed in fp32 by XLA (bandwidth-bound elementwise + reduction — XLA fuses
it; the win of the Pallas kernel is the fwd's guaranteed single HBM pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry


def _rms_norm_ref(x, weight=None, epsilon=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def _rms_norm_fwd_kernel_call(x, w, epsilon, block_rows: int = 256, interpret: bool = False):
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = orig_shape[-1]
    xr = x.reshape(-1, d)
    n = xr.shape[0]
    if n % block_rows != 0:
        block_rows = _largest_divisor(n, block_rows)

    def kernel(x_ref, w_ref, o_ref):
        xb = x_ref[...].astype(jnp.float32)
        var = jnp.mean(jnp.square(xb), axis=-1, keepdims=True)
        out = xb * jax.lax.rsqrt(var + epsilon) * w_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
        name="rms_norm",
    )(xr, w)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_pallas(x, w, epsilon, interpret=False):
    return _rms_norm_fwd_kernel_call(x, w, epsilon, interpret=interpret)


def _rms_fwd_rule(x, w, epsilon, interpret):
    return _rms_norm_fwd_kernel_call(x, w, epsilon, interpret=interpret), (x, w)


def _rms_bwd_rule(epsilon, interpret, res, dy):
    x, w = res
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + epsilon)
    x_hat = x32 * r
    g = dy32 * w32
    dx = r * (g - x_hat * jnp.mean(g * x_hat, axis=-1, keepdims=True))
    dw = jnp.sum(dy32 * x_hat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms_norm_pallas.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def _largest_divisor(n, cap):
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def rms_norm(x, weight=None, epsilon: float = 1e-6, interpret: bool = False,
             shard=None):
    """``shard``: ``(jax Mesh, PartitionSpec of x)`` where ``x`` is laid out
    over several devices — the kernel then runs per shard (rows are
    independent; the last dim must be whole)."""
    from . import per_shard, use_pallas

    kernel_ok = x.shape[-1] % 128 == 0
    if interpret and not kernel_ok:
        raise ValueError(
            f"rms_norm(interpret=True) requires last dim % 128 == 0; got {x.shape[-1]}")
    if (use_pallas() or interpret) and kernel_ok:
        registry.ensure_admitted("rms_norm")
        w = weight if weight is not None else jnp.ones((x.shape[-1],), x.dtype)
        return per_shard(
            lambda x, w: _rms_norm_pallas(x, w, epsilon, interpret),
            shard, 1, 1)(x, w)
    return _rms_norm_ref(x, weight, epsilon)


def _registry_example():
    sds = jax.ShapeDtypeStruct
    return (lambda x, w: _rms_norm_fwd_kernel_call(x, w, 1e-6),
            (sds((64, 256), jnp.bfloat16), sds((256,), jnp.bfloat16)))


registry.register(
    "rms_norm", _registry_example,
    presets=("tiny", "small", "base", "longctx", "moe", "ocr"),
    description="fused RMSNorm forward: row statistics kept in VMEM")
