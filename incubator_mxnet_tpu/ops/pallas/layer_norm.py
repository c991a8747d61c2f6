"""Fused LayerNorm pallas kernel (one HBM pass: stats + normalize + affine).

The XLA path (_raw.layer_norm) already fuses decently; this kernel guarantees
the single-pass schedule on TPU and keeps the reduction in fp32 regardless of
input dtype. Backward uses the closed-form layernorm VJP in XLA (cheap, and
XLA fuses it into the surrounding backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["layer_norm"]


def _vspec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:] + b_ref[:]).astype(o_ref.dtype)


def _ln_fwd_impl(x2, gamma, beta, eps, interpret, block_r):
    rows, d = x2.shape
    g2 = gamma.reshape(1, d).astype(jnp.float32)
    b2 = beta.reshape(1, d).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(rows // block_r,),
        in_specs=[_vspec((block_r, d), lambda i: (i, 0)),
                  _vspec((1, d), lambda i: (0, 0)),
                  _vspec((1, d), lambda i: (0, 0))],
        out_specs=_vspec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name="layer_norm_fwd",
    )(x2, g2, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x2, gamma, beta, eps, interpret, block_r):
    return _ln_fwd_impl(x2, gamma, beta, eps, interpret, block_r)


def _ln_fwd(x2, gamma, beta, eps, interpret, block_r):
    return _ln_fwd_impl(x2, gamma, beta, eps, interpret, block_r), (x2, gamma)


def _ln_bwd(eps, interpret, block_r, res, dy):
    x2, gamma = res
    x = x2.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dgamma = jnp.sum(g * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(g, axis=0).astype(gamma.dtype)
    gg = g * gamma.astype(jnp.float32)
    n = x.shape[-1]
    dx = (gg - jnp.mean(gg, axis=-1, keepdims=True)
          - xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True)) * rstd
    return dx.astype(x2.dtype), dgamma, dbeta


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, gamma, beta, eps=1e-5, block_rows=256, interpret=None):
    """Fused layernorm over the LAST axis of x; gamma/beta shape (D,)."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    # TPU tiling wants sublane blocks of 8; pad the row dim rather than
    # blowing VMEM with one full-array block (padded rows are sliced off).
    rp = (rows + 7) // 8 * 8
    if rp != rows:
        x2 = jnp.pad(x2, ((0, rp - rows), (0, 0)))
    # keep blocks well under VMEM (in+out, double-buffered): ~512k f32 = 2MB
    cap = max(8, (1 << 19) // d // 8 * 8)
    block_r = min(block_rows, cap, rp) // 8 * 8
    while block_r > 8 and rp % block_r:
        block_r -= 8
    out = _ln(x2, gamma, beta, float(eps), bool(interpret), int(block_r))
    return out[:rows].reshape(x.shape)
