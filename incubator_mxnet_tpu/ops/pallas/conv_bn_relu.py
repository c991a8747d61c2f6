"""Fused conv+BN+relu pallas kernels (reference analogue: the cuDNN
fused ConvBiasActivation / CUDNN_BATCHNORM_OPS paths the MXNet fork
leaned on for ResNet throughput).

Two kernels:

* :func:`scale_shift_act` — the BatchNorm tail ``act(x * scale + shift)``
  as ONE HBM pass (per-channel scale/shift broadcast along lanes). This
  is what training-mode BatchNormReLU fuses through after the batch-stat
  reduction, and what the general-geometry conv path uses as its
  epilogue.
* :func:`conv_bn_relu` — inference-style conv+BN+act. A 1x1/stride-1/
  no-pad NHWC conv IS a matmul over flattened pixels, so it runs as a
  single blocked pallas matmul whose final k-block applies the folded BN
  scale/shift and the activation before the one output write (the conv
  output never round-trips HBM unfused). Any other geometry keeps XLA's
  conv (MXU-tuned) and fuses only the epilogue.

Backward: scale_shift_act has a cheap closed-form VJP (the pre-activation
recompute is elementwise). conv_bn_relu's VJP re-derives through the XLA
reference formulation (one extra forward — remat-style; the fused path
targets inference/serving where no backward runs).

Off-TPU the kernels run with ``interpret=True`` (parity tests); shapes
are padded to tile boundaries and sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scale_shift_act", "conv_bn_relu", "fold_bn"]


def _vspec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _apply_act(y, act):
    if act is None:
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    raise ValueError(f"scale_shift_act: unsupported act {act!r} "
                     "(relu, relu6 or None)")


# ---------------------------------------------------------------------------
# fused scale+shift+activation epilogue
# ---------------------------------------------------------------------------

def _ssa_kernel(x_ref, s_ref, b_ref, o_ref, *, act):
    y = x_ref[:].astype(jnp.float32) * s_ref[:] + b_ref[:]
    o_ref[:] = _apply_act(y, act).astype(o_ref.dtype)


def _ssa_fwd_impl(x2, scale, shift, act, interpret, block_r):
    rows, d = x2.shape
    s2 = scale.reshape(1, d).astype(jnp.float32)
    b2 = shift.reshape(1, d).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_ssa_kernel, act=act),
        grid=(rows // block_r,),
        in_specs=[_vspec((block_r, d), lambda i: (i, 0)),
                  _vspec((1, d), lambda i: (0, 0)),
                  _vspec((1, d), lambda i: (0, 0))],
        out_specs=_vspec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name="scale_shift_act_fwd",
    )(x2, s2, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ssa(x2, scale, shift, act, interpret, block_r):
    return _ssa_fwd_impl(x2, scale, shift, act, interpret, block_r)


def _ssa_fwd(x2, scale, shift, act, interpret, block_r):
    return (_ssa_fwd_impl(x2, scale, shift, act, interpret, block_r),
            (x2, scale, shift))


def _ssa_bwd(act, interpret, block_r, res, dy):
    x2, scale, shift = res
    xf = x2.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    if act is not None:
        # recompute the pre-activation (elementwise — cheap) for the mask
        pre = xf * scale.astype(jnp.float32) + shift.astype(jnp.float32)
        if act == "relu":
            mask = pre > 0
        else:                       # relu6
            mask = (pre > 0) & (pre < 6.0)
        g = jnp.where(mask, g, 0.0)
    dx = (g * scale.astype(jnp.float32)).astype(x2.dtype)
    dscale = jnp.sum(g * xf, axis=0).astype(scale.dtype)
    dshift = jnp.sum(g, axis=0).astype(shift.dtype)
    return dx, dscale, dshift


_ssa.defvjp(_ssa_fwd, _ssa_bwd)


def scale_shift_act(x, scale, shift, act="relu", block_rows=256,
                    interpret=None):
    """``act(x * scale + shift)`` over the LAST axis of x in one HBM pass;
    scale/shift shape (C,). Differentiable (closed-form VJP)."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    rp = (rows + 7) // 8 * 8
    if rp != rows:
        x2 = jnp.pad(x2, ((0, rp - rows), (0, 0)))
    cap = max(8, (1 << 19) // d // 8 * 8)
    block_r = min(block_rows, cap, rp) // 8 * 8
    while block_r > 8 and rp % block_r:
        block_r -= 8
    out = _ssa(x2, scale, shift, act, bool(interpret), int(block_r))
    return out[:rows].reshape(x.shape)


# ---------------------------------------------------------------------------
# fused 1x1-conv (matmul) + BN epilogue
# ---------------------------------------------------------------------------

def _mm_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, nk, act):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:].astype(jnp.float32),
                          w_ref[:].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        y = acc_ref[:] * s_ref[:] + b_ref[:]
        o_ref[:] = _apply_act(y, act).astype(o_ref.dtype)


def _pick_block(n, pref=128, align=8):
    if n % pref == 0:
        return pref
    b = min(n, pref) // align * align
    while b > align and n % b:
        b -= align
    return b if b and n % b == 0 else n


def _mm_epilogue(x2, w2, scale, shift, act, interpret):
    """(M, K) @ (K, N) with fused per-column scale/shift/act on the final
    accumulation block. f32 accumulation in VMEM scratch. The row block
    is always sublane-aligned (multiple of 8; rows are padded to it) —
    M itself never constrains alignment. Channel dims are the caller's
    contract: on real TPU the selection layer admits only 128-lane-
    aligned Cin/Cout."""
    m, k = x2.shape
    n = w2.shape[1]
    bm = min(128, (max(m, 1) + 7) // 8 * 8)     # 8-aligned, rows padded
    mp = (m + bm - 1) // bm * bm
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    bn = _pick_block(n, 128)
    bk = _pick_block(k, 128)
    nk = k // bk
    s2 = scale.reshape(1, n).astype(jnp.float32)
    b2 = shift.reshape(1, n).astype(jnp.float32)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk, act=act),
        grid=(mp // bm, n // bn, nk),
        in_specs=[_vspec((bm, bk), lambda i, j, kk: (i, kk)),
                  _vspec((bk, bn), lambda i, j, kk: (kk, j)),
                  _vspec((1, bn), lambda i, j, kk: (0, j)),
                  _vspec((1, bn), lambda i, j, kk: (0, j))],
        out_specs=_vspec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), x2.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="matmul_scale_shift_act_fwd",
    )(x2, w2, s2, b2)
    return out[:m]


# ---------------------------------------------------------------------------
# conv + BN + act
# ---------------------------------------------------------------------------

def fold_bn(gamma, beta, mean, var, eps):
    """BN(moving stats) as an affine epilogue: scale = gamma*rsqrt(var+eps),
    shift = beta - mean*scale (f32 — matches the XLA path's f32 stats)."""
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale, shift


def _conv_ref(x, w, scale, shift, stride, pad, act):
    """XLA reference formulation — the VJP re-derivation target and the
    parity oracle for tests."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y.astype(jnp.float32) * scale + shift
    return _apply_act(y, act).astype(x.dtype)


def _cbr_fwd_impl(x, w, scale, shift, stride, pad, act, interpret):
    kh, kw = w.shape[0], w.shape[1]
    one_by_one = (kh == 1 and kw == 1 and tuple(stride) == (1, 1)
                  and tuple(pad) == (0, 0))
    if one_by_one:
        n, h, wd, cin = x.shape
        cout = w.shape[-1]
        x2 = x.reshape(n * h * wd, cin)
        w2 = w.reshape(cin, cout)
        out = _mm_epilogue(x2, w2, scale, shift, act, interpret)
        return out.reshape(n, h, wd, cout)
    # general geometry: XLA's conv (MXU-tuned), pallas fuses the epilogue
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return scale_shift_act(y, scale, shift, act=act, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _cbr(x, w, scale, shift, stride, pad, act, interpret):
    return _cbr_fwd_impl(x, w, scale, shift, stride, pad, act, interpret)


def _cbr_fwd(x, w, scale, shift, stride, pad, act, interpret):
    return (_cbr_fwd_impl(x, w, scale, shift, stride, pad, act, interpret),
            (x, w, scale, shift))


def _cbr_bwd(stride, pad, act, interpret, res, dy):
    x, w, scale, shift = res
    _, vjp = jax.vjp(
        lambda xx, ww, ss, bb: _conv_ref(xx, ww, ss, bb, stride, pad, act),
        x, w, scale, shift)
    return vjp(dy)


_cbr.defvjp(_cbr_fwd, _cbr_bwd)


def conv_bn_relu(x, weight, gamma, beta, mean, var, *, eps=1e-5,
                 stride=(1, 1), pad=(0, 0), act="relu", interpret=None):
    """Fused NHWC conv + BatchNorm(moving stats) + activation.

    x (N,H,W,Cin); weight HWIO. 1x1/stride-1/no-pad runs as ONE pallas
    matmul+epilogue kernel; other geometries run XLA's conv with the
    pallas scale/shift/act epilogue. Numerics match
    ``act(bn(conv(x)))`` computed the XLA way to f32 accumulation
    tolerance (the epilogue applies BN AFTER the conv sum, same order as
    the unfused path — weights are not pre-folded)."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    scale, shift = fold_bn(gamma, beta, mean, var, eps)
    return _cbr(x, weight, scale, shift, tuple(stride), tuple(pad), act,
                bool(interpret))
