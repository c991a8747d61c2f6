"""Pure-jax NN kernels (reference parity: src/operator/nn/*).

These are the XLA-native replacements for the reference's mshadow/cuDNN
kernels: conv/pool lower to lax convolution/reduce_window (MXU/VPU on TPU),
norms are fused elementwise chains XLA consolidates into single kernels.
All functions are pure (state in, state out) so they compose with jit/grad/
shard_map. Layouts: MXNet's default NCHW is supported everywhere, NHWC is
offered because it is the faster layout on TPU (channels-last feeds the MXU
without relayout); model zoo defaults to NHWC on TPU.

The three ops where ops/select.py chooses between a Pallas kernel and XLA
(`batch_norm`, `layer_norm`, `multihead_attention`) run under a
`jax.named_scope` named for the op, not the implementation: both branches
have the same owner in a device trace (docs/profiler.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# dense / linear
# ---------------------------------------------------------------------------

def dense(x, weight, bias=None, flatten=True):
    """FullyConnected (reference src/operator/nn/fully_connected.cc):
    weight layout (out_units, in_units); flatten=True collapses trailing dims."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

def _conv_dn(ndim, layout):
    if layout == "NCHW" or (layout is None and ndim == 4):
        return ("NCHW", "OIHW", "NCHW")
    if layout == "NHWC":
        return ("NHWC", "HWIO", "NHWC")
    if layout == "NCW" or (layout is None and ndim == 3):
        return ("NCH", "OIH", "NCH")  # 1D as H
    if layout == "NWC":
        return ("NHC", "HIO", "NHC")
    if layout == "NCDHW" or (layout is None and ndim == 5):
        return ("NCDHW", "OIDHW", "NCDHW")
    if layout == "NDHWC":
        return ("NDHWC", "DHWIO", "NDHWC")
    raise ValueError(f"unsupported conv layout {layout}")


def conv(x, weight, bias=None, kernel=None, stride=None, pad=None, dilate=None,
         num_group=1, layout="NCHW"):
    """Convolution (reference src/operator/nn/convolution.cc). `weight` is
    OIHW-ordered for NCHW (out, in/group, *k); HWIO for NHWC."""
    nsp = x.ndim - 2
    stride = stride or (1,) * nsp
    pad = pad or (0,) * nsp
    dilate = dilate or (1,) * nsp
    dn = _conv_dn(x.ndim, layout)
    y = lax.conv_general_dilated(
        x, weight,
        window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        rhs_dilation=tuple(dilate),
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if bias is not None:
        if layout.endswith("C") and layout[0] == "N" and "C" != layout[1]:
            y = y + bias  # channels-last broadcasts directly
        else:
            y = y + bias.reshape((1, -1) + (1,) * nsp)
    return y


def conv_transpose(x, weight, bias=None, stride=None, pad=None, dilate=None,
                   adj=None, num_group=1, layout="NCHW"):
    """Deconvolution (reference src/operator/nn/deconvolution.cc): gradient of
    conv w.r.t. input, implemented as lax.conv_transpose with IOHW weights."""
    nsp = x.ndim - 2
    stride = tuple(stride or (1,) * nsp)
    pad = tuple(pad or (0,) * nsp)
    dilate = tuple(dilate or (1,) * nsp)
    adj = tuple(adj or (0,) * nsp)
    if layout == "NCHW":
        dn = ("NCHW", "IOHW", "NCHW")
        kshape = weight.shape[2:]
    elif layout == "NHWC":
        dn = ("NHWC", "HWIO", "NHWC")
        kshape = weight.shape[:-2]
    else:
        raise ValueError(f"unsupported deconv layout {layout}")
    # MXNet output size: (in-1)*s - 2p + dilate*(k-1) + 1 + adj
    pads = []
    for i in range(nsp):
        k_eff = dilate[i] * (kshape[i] - 1) + 1
        lo = k_eff - 1 - pad[i]
        hi = k_eff - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    if num_group != 1:
        xs = jnp.split(x, num_group, axis=1 if layout == "NCHW" else -1)
        ws = jnp.split(weight, num_group, axis=0 if layout == "NCHW" else -2)
        ys = [lax.conv_transpose(xi, wi, stride, pads, rhs_dilation=dilate,
                                 dimension_numbers=dn)
              for xi, wi in zip(xs, ws)]
        y = jnp.concatenate(ys, axis=1 if layout == "NCHW" else -1)
    else:
        y = lax.conv_transpose(x, weight, stride, pads, rhs_dilation=dilate,
                               dimension_numbers=dn)
    if bias is not None:
        y = y + (bias if layout == "NHWC" else bias.reshape((1, -1) + (1,) * nsp))
    return y


def grid_generator(data, transform_type="affine", target_shape=None):
    """GridGenerator (reference src/operator/grid_generator.cc): sampling
    grid in [-1,1] normalized coords, (N, 2, H, W) with channel 0 = x.
    affine: data (N,6) row-major 2x3; warp: data = flow (N,2,H,W) added to
    the identity grid in pixel units."""
    if transform_type == "affine":
        h, w = target_shape
        n = data.shape[0]
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, HW)
        theta = data.reshape(n, 2, 3)
        out = theta @ base                                        # (N,2,HW)
        return out.reshape(n, 2, h, w)
    if transform_type == "warp":
        n, _, h, w = data.shape
        ys = jnp.arange(h, dtype=data.dtype)
        xs = jnp.arange(w, dtype=data.dtype)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        x = (data[:, 0] + gx) * (2.0 / jnp.maximum(w - 1, 1)) - 1.0
        y = (data[:, 1] + gy) * (2.0 / jnp.maximum(h - 1, 1)) - 1.0
        return jnp.stack([x, y], axis=1)
    raise ValueError(f"unknown transform_type {transform_type!r}")


def bilinear_sampler(data, grid):
    """BilinearSampler (reference src/operator/bilinear_sampler.cc): sample
    NCHW `data` at normalized grid (N,2,Ho,Wo); zero padding outside.
    One vectorized gather + 4-tap blend — XLA fuses it; no scalar loops."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0     # (N,Ho,Wo) in pixel coords
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def tap(yi, xi):
        inb = ((xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1))
        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        # gather per batch: data (N,C,H,W) at (N,Ho,Wo) points
        flat = data.reshape(n, c, h * w)
        idx = (yc * w + xc).reshape(n, 1, -1)
        vals = jnp.take_along_axis(flat, jnp.broadcast_to(idx, (n, c, idx.shape[-1])), axis=2)
        vals = vals.reshape(n, c, *xi.shape[1:])
        return vals * inb[:, None].astype(data.dtype)

    v00 = tap(y0, x0)
    v01 = tap(y0, x0 + 1)
    v10 = tap(y0 + 1, x0)
    v11 = tap(y0 + 1, x0 + 1)
    wx = wx[:, None].astype(data.dtype)
    wy = wy[:, None].astype(data.dtype)
    return ((1 - wy) * ((1 - wx) * v00 + wx * v01)
            + wy * ((1 - wx) * v10 + wx * v11))


def correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """Correlation (reference src/operator/correlation.cc, FlowNet):
    zero-centered displacement grid (radius max_displacement//stride2 in
    stride2 multiples), k x k patch sum normalized by k*k*C, centers
    cropped by border = max_displacement + (k-1)//2 from the pad_size-padded
    map, subsampled by stride1. The displacement loop is static, so it
    unrolls into one fused XLA computation (no dynamic shapes)."""
    import math
    n, c, h, w = data1.shape
    k = int(kernel_size)
    d = int(max_displacement)
    d2r = d // max(1, stride2)
    offsets = [stride2 * i for i in range(-d2r, d2r + 1)]
    border = d + (k - 1) // 2
    h2, w2 = h + 2 * pad_size, w + 2 * pad_size
    out_h = int(math.ceil((h2 - 2 * border) / float(stride1)))
    out_w = int(math.ceil((w2 - 2 * border) / float(stride1)))
    if out_h <= 0 or out_w <= 0:
        raise ValueError(
            f"correlation output would be empty: input {h}x{w}, pad "
            f"{pad_size}, border {border}")
    p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad_size, pad_size),
                         (pad_size, pad_size)))
    # extra d margin on data2 so every shifted slice stays in bounds
    p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad_size + d, pad_size + d),
                         (pad_size + d, pad_size + d)))
    norm = float(k * k * c)
    outs = []
    for dy in offsets:
        for dx in offsets:
            shifted = jax.lax.dynamic_slice(
                p2, (0, 0, d + dy, d + dx), (n, c, h2, w2))
            prod = ((p1 * shifted) if is_multiply
                    else jnp.abs(p1 - shifted)).sum(axis=1)  # (N,H2,W2)
            if k > 1:
                prod = jax.lax.reduce_window(
                    prod, 0.0, jax.lax.add, (1, k, k), (1, 1, 1), "SAME")
            outs.append(prod / norm)
    out = jnp.stack(outs, axis=1)        # (N, D2, H2, W2)
    out = out[:, :, border:border + (out_h - 1) * stride1 + 1:stride1,
              border:border + (out_w - 1) * stride1 + 1:stride1]
    return out


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    """SequenceMask (reference src/operator/sequence_mask.cc): positions at
    or beyond each sequence's length (along time `axis`) become `value`."""
    if not use_sequence_length or sequence_length is None:
        return data
    t = data.shape[axis]
    steps = jnp.arange(t)
    ln = sequence_length.astype(jnp.int32)      # (N,)
    if axis == 0:
        mask = steps[:, None] < ln[None, :]     # (T, N)
    else:
        mask = steps[None, :] < ln[:, None]     # (N, T)
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


def sequence_last(data, sequence_length=None, use_sequence_length=False,
                  axis=0):
    """SequenceLast: the last valid element along `axis` per sequence."""
    t = data.shape[axis]
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, t - 1, axis=axis)
    ln = jnp.clip(sequence_length.astype(jnp.int32) - 1, 0, t - 1)  # (N,)
    moved = jnp.moveaxis(data, axis, 0)          # (T, N, ...)
    idx = ln.reshape((1, -1) + (1,) * (moved.ndim - 2))
    idx = jnp.broadcast_to(idx, (1,) + moved.shape[1:])
    return jnp.take_along_axis(moved, idx, axis=0)[0]


def sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                     axis=0):
    """SequenceReverse: reverse the first len_n steps of each sequence,
    leaving padding in place."""
    t = data.shape[axis]
    moved = jnp.moveaxis(data, axis, 0)          # (T, N, ...)
    if not use_sequence_length or sequence_length is None:
        return jnp.moveaxis(moved[::-1], 0, axis)
    ln = sequence_length.astype(jnp.int32)       # (N,)
    steps = jnp.arange(t)[:, None]               # (T,1)
    src = jnp.where(steps < ln[None, :], ln[None, :] - 1 - steps, steps)
    src = src.reshape(src.shape + (1,) * (moved.ndim - 2))
    src = jnp.broadcast_to(src, moved.shape)
    out = jnp.take_along_axis(moved, src, axis=0)
    return jnp.moveaxis(out, 0, axis)


def bilinear_kernel_1d(k, dtype=jnp.float32):
    """The reference's bilinear deconv filter row (same formula as
    mx.init.Bilinear / src/operator/nn/upsampling-inl.h)."""
    import math
    f = math.ceil(k / 2.0)
    c = (2 * f - 1 - f % 2) / (2.0 * f)
    x = jnp.arange(k, dtype=dtype)
    return 1 - jnp.abs(x / f - c)


def upsampling(x, scale=2, sample_type="nearest", layout="NCHW"):
    """UpSampling (reference src/operator/nn/upsampling.cc). `nearest` is a
    repeat; `bilinear` is the reference's fixed-weight Deconvolution
    (kernel 2s-s%2, stride s, pad ceil((s-1)/2)) realised as ONE depthwise
    lhs-dilated conv — a single XLA conv the TPU tiles onto the MXU, no
    per-channel loop."""
    import math
    s = int(scale)
    if sample_type == "nearest":
        if layout == "NCHW":
            return jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        return jnp.repeat(jnp.repeat(x, s, axis=1), s, axis=2)
    if sample_type != "bilinear":
        raise ValueError(f"unknown UpSampling sample_type {sample_type!r}")
    k = 2 * s - s % 2
    pad_deconv = int(math.ceil((s - 1) / 2.0))
    p = k - 1 - pad_deconv  # deconv pad → lhs-dilated conv pad
    w1 = bilinear_kernel_1d(k, x.dtype)
    w2 = jnp.outer(w1, w1)
    if layout == "NCHW":
        ch = x.shape[1]
        kernel = jnp.broadcast_to(w2, (ch, 1, k, k))
        dn = ("NCHW", "OIHW", "NCHW")
    elif layout == "NHWC":
        ch = x.shape[3]
        kernel = jnp.broadcast_to(w2[:, :, None, None], (k, k, 1, ch))
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        raise ValueError(f"unsupported UpSampling layout {layout}")
    return lax.conv_general_dilated(
        x, kernel.astype(x.dtype), (1, 1), [(p, p), (p, p)],
        lhs_dilation=(s, s), feature_group_count=ch, dimension_numbers=dn)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

def pooling(x, pool_type="max", kernel=(2, 2), stride=None, pad=None,
            global_pool=False, count_include_pad=True, layout="NCHW",
            ceil_mode=False):
    """Pooling (reference src/operator/nn/pooling.cc) via lax.reduce_window."""
    nsp = x.ndim - 2
    channels_last = layout.endswith("C") and len(layout) == x.ndim and layout[1] != "C"
    sp_axes = tuple(range(1, 1 + nsp)) if channels_last else tuple(range(2, 2 + nsp))
    if global_pool:
        if pool_type == "max":
            return jnp.max(x, axis=sp_axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.sum(x, axis=sp_axes, keepdims=True)
            if pool_type == "avg":
                cnt = 1
                for a in sp_axes:
                    cnt *= x.shape[a]
                r = r / cnt
            return r
        raise ValueError(pool_type)
    stride = tuple(stride or kernel)
    pad = tuple(pad or (0,) * nsp)
    # ceil_mode: extend the high-side padding so the last partial window is
    # kept (MXNet ceil((in + 2p - k)/s) + 1 output size).
    hi_extra = [0] * nsp
    if ceil_mode:
        for i, a in enumerate(sp_axes):
            size = x.shape[a] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            if rem:
                hi_extra[i] = stride[i] - rem
    window = [1] * x.ndim
    strides = [1] * x.ndim
    pads = [(0, 0)] * x.ndim
    for i, a in enumerate(sp_axes):
        window[a] = kernel[i]
        strides[a] = stride[i]
        pads[a] = (pad[i], pad[i] + hi_extra[i])
    if pool_type == "max":
        # reduce_window, NOT patch extraction: patches are a convolution,
        # and on the TPU a float32 convolution takes bfloat16 operands —
        # the pooled values came back rounded, and the padding value
        # (finfo.min, -inf in bfloat16) times the patch kernel's zeros was
        # NaN (first chip run of a float32 ResNet-50, PR 22)
        lowest = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else np.iinfo(x.dtype).min
        return lax.reduce_window(x, np.array(lowest, x.dtype), lax.max,
                                 window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, jnp.asarray(0, x.dtype), lax.add,
                              window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            cnt = 1
            for i in range(nsp):
                cnt *= kernel[i]
            return s / cnt
        ones = jnp.ones(x.shape, x.dtype)
        cnt = lax.reduce_window(ones, jnp.asarray(0, x.dtype), lax.add,
                                window, strides, pads)
        return s / cnt
    raise ValueError(f"unsupported pool_type {pool_type}")


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@jax.named_scope("batch_norm")
def batch_norm(x, gamma, beta, moving_mean, moving_var, *, axis=1, eps=1e-5,
               momentum=0.9, training=True, use_global_stats=False,
               fix_gamma=False, act=None):
    """BatchNorm (reference src/operator/nn/batch_norm.cc). Returns
    (y, new_moving_mean, new_moving_var); caller threads state.

    ``act`` fuses a trailing activation (BatchNormReLU): on qualifying
    channels-last shapes the normalize+affine+act tail runs as ONE pallas
    HBM pass (ops/pallas/conv_bn_relu.scale_shift_act) — the stats
    reduction (training mode) stays XLA; otherwise the activation rides
    the XLA chain."""
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    red = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
    bshape = [1] * x.ndim
    bshape[axis % x.ndim] = x.shape[axis % x.ndim]
    if training and not use_global_stats:
        mean = jnp.mean(x, axis=red)
        var = jnp.var(x, axis=red)
        new_mm = momentum * moving_mean + (1 - momentum) * mean
        new_mv = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    from . import select as _sel
    if act is not None and _sel.scale_shift_act(x, axis, act=act):
        from . import pallas as _pallas
        scale, shift = _pallas.fold_bn(gamma, beta, mean, var, eps)
        return (_pallas.scale_shift_act(x, scale, shift, act=act),
                new_mm, new_mv)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
    y = (x - mean.reshape(bshape).astype(x.dtype)) * inv.reshape(bshape)
    y = y * gamma.reshape(bshape).astype(x.dtype) + beta.reshape(bshape).astype(x.dtype)
    if act is not None:
        y = activation(y, act)
    return y, new_mm, new_mv


def conv_bn_relu(x, weight, gamma, beta, moving_mean, moving_var, *,
                 eps=1e-5, stride=None, pad=None, dilate=None, num_group=1,
                 layout="NHWC", act="relu", training=False):
    """Fused conv+BN+activation (inference hot path). Qualifying calls
    (ops/select.py: inference BN, NHWC, ungrouped) run the pallas fused
    kernel — 1x1 convs as one matmul+epilogue program, other geometries
    as XLA conv + fused epilogue; everything else falls back to the
    unfused conv → batch_norm(act=...) chain with identical semantics.
    Returns y only (moving stats are unchanged by inference BN; training
    callers get the updated stats from the fallback chain via
    batch_norm)."""
    nsp = x.ndim - 2
    stride = tuple(stride or (1,) * nsp)
    pad = tuple(pad or (0,) * nsp)
    from . import select as _sel
    if (not training and nsp == 2
            and _sel.conv_bn_relu(x, weight, stride, pad, dilate, num_group,
                                  layout, training, act=act)):
        from . import pallas as _pallas
        return _pallas.conv_bn_relu(x, weight, gamma, beta, moving_mean,
                                    moving_var, eps=eps, stride=stride,
                                    pad=pad, act=act)
    y = conv(x, weight, None, stride=stride, pad=pad, dilate=dilate,
             num_group=num_group, layout=layout)
    caxis = -1 if layout.endswith("C") and layout[1] != "C" else 1
    y, _, _ = batch_norm(y, gamma, beta, moving_mean, moving_var,
                         axis=caxis, eps=eps, training=training, act=act)
    return y


@jax.named_scope("layer_norm")
def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """LayerNorm (reference src/operator/nn/layer_norm.cc). Stats in f32 for
    bf16 stability, one fused XLA chain. Qualifying shapes dispatch to the
    fused pallas kernel through the selection layer (ops/select.py)."""
    from . import select as _sel
    if _sel.layer_norm(x, gamma, axis):
        from . import pallas as _pallas
        return _pallas.layer_norm(x, gamma, beta, eps)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis % x.ndim] = x.shape[axis % x.ndim]
    y = y * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return y.astype(x.dtype)


def instance_norm(x, gamma, beta, eps=1e-5):
    """InstanceNorm: normalize over spatial dims per (N, C)."""
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return y * gamma.reshape(shape) + beta.reshape(shape)


def group_norm(x, gamma, beta, num_groups, eps=1e-5):
    """GroupNorm over channel groups (NCHW)."""
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.var(xg, axis=red, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return y * gamma.reshape(shape) + beta.reshape(shape)


def l2_normalization(x, eps=1e-10, mode="instance"):
    """L2Normalization (reference src/operator/l2_normalization.cc)."""
    if mode == "instance":
        red = tuple(range(1, x.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
    elif mode == "spatial":
        red = tuple(range(2, x.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=True) + eps)
    else:
        raise ValueError(mode)
    return x / n


# ---------------------------------------------------------------------------
# regularization / activations
# ---------------------------------------------------------------------------

def dropout(x, key, rate=0.5, training=True, axes=()):
    """Dropout; `axes` = broadcast axes (one shared mask along them, parity
    with mx.nd.Dropout axes= for spatial/channel dropout)."""
    if not training or rate == 0.0:
        return x
    keep = 1.0 - rate
    mshape = list(x.shape)
    for a in axes:
        mshape[a] = 1
    mask = jax.random.bernoulli(key, keep, tuple(mshape))
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": jax.nn.gelu,
    "gelu_tanh": lambda a: jax.nn.gelu(a, approximate=True),
    "erf_gelu": lambda a: jax.nn.gelu(a, approximate=False),
    "swish": jax.nn.silu,
    "silu": jax.nn.silu,
    "mish": lambda a: a * jnp.tanh(jax.nn.softplus(a)),
    "relu6": lambda a: jnp.clip(a, 0, 6),
    "hard_sigmoid": jax.nn.hard_sigmoid,
    "hard_swish": jax.nn.hard_swish,
    "leaky": lambda a: jax.nn.leaky_relu(a, 0.25),
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "log_softmax": jax.nn.log_softmax,
    "softmax": jax.nn.softmax,
}


def activation(x, act_type):
    try:
        return _ACTIVATIONS[act_type](x)
    except KeyError:
        raise ValueError(f"unknown activation {act_type!r}; "
                         f"known: {sorted(_ACTIVATIONS)}") from None


# ---------------------------------------------------------------------------
# losses / classification heads
# ---------------------------------------------------------------------------

@jax.named_scope("cross_entropy")
def softmax_cross_entropy(logits, labels, axis=-1, sparse_label=True):
    """-log softmax(logits)[label] over `axis`, or, with `sparse_label`
    off, -sum(labels * log softmax(logits)) against a distribution of the
    logits' shape.

    Class ids (`sparse_label`) take a rule of their own, `_sparse_ce`: it
    writes nothing of the logits' size in the forward and keeps only the
    logits it was given and a float32 log-sum-exp a row (as its max and the
    log of the sum beside it, so that logits far from 0 lose nothing).
    Inside a program that GSPMD partitions over several devices
    (ops/select.py `partitioned`) class ids keep jax's log-softmax and
    gather, which GSPMD splits as it always has: how it splits the rule
    is not measured."""
    from . import select as _sel
    if not sparse_label or _sel.meshed():
        logp = jax.nn.log_softmax(logits, axis=axis)
        if sparse_label:
            lab = jnp.expand_dims(labels.astype(jnp.int32), axis)
            return -jnp.take_along_axis(logp, lab, axis=axis).squeeze(axis)
        return -jnp.sum(labels * logp, axis=axis)
    axis = axis % logits.ndim
    n = logits.shape[axis]
    label = labels.astype(jnp.int32)
    # a negative id counts from the end and one outside [-n, n) reads NaN,
    # as take_along_axis has them
    label = jnp.where(label < 0, label + n, label)
    loss = _sparse_ce(axis, logits, label)
    return jnp.where((label >= 0) & (label < n), loss, jnp.nan)


def _sparse_ce_fwd(axis, x, label):
    """(m - x[label]) + log sum exp(x - m) a row, m the row's max: reductions
    over x alone, in float32, the label's logit a masked sum beside the sum
    of exponentials. Not a gather: XLA fuses no producer into a gather's
    operand, so where x is the head's output widened to float32 it would
    write that copy out. The loss is rounded to x's dtype at the end."""
    xf = x.astype(jnp.float32)
    m = jnp.max(x, axis=axis, keepdims=True).astype(jnp.float32)
    log_sum = jnp.log(jnp.sum(jnp.exp(xf - m), axis=axis, keepdims=True))
    picked = jnp.sum(jnp.where(_onehot(x.shape, axis, label), xf, 0.0),
                     axis=axis, keepdims=True)
    loss = (m - picked) + log_sum
    return loss.squeeze(axis).astype(x.dtype), (x, label, m, log_sum)


def _onehot(shape, axis, label):
    """An iota compared with the label, never a scatter."""
    return (lax.broadcasted_iota(jnp.int32, shape, axis)
            == jnp.expand_dims(label, axis))


def _sparse_ce_bwd(axis, res, g):
    """g * (softmax(x) - onehot(label)) in one elementwise pass over x, in
    float32 and rounded to x's dtype."""
    x, label, m, log_sum = res
    onehot = _onehot(x.shape, axis, label)
    grad = (jnp.exp(x.astype(jnp.float32) - m - log_sum)
            - onehot.astype(jnp.float32))
    g = jnp.expand_dims(g, axis).astype(jnp.float32)
    return (g * grad).astype(x.dtype), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sparse_ce(axis, x, label):
    return _sparse_ce_fwd(axis, x, label)[0]


_sparse_ce.defvjp(_sparse_ce_fwd, _sparse_ce_bwd)


def smooth_l1(x, scalar=1.0):
    """smooth_l1 (reference: used by SSD loc loss)."""
    s2 = scalar * scalar
    absx = jnp.abs(x)
    return jnp.where(absx < 1.0 / s2, 0.5 * s2 * jnp.square(x), absx - 0.5 / s2)


# ---------------------------------------------------------------------------
# attention (XLA path; pallas kernel in ops/pallas/ for the TPU fast path)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def multihead_attention(q, k, v, num_heads, mask=None, dropout_rate=0.0,
                        key=None, training=False, scale=None, causal=False,
                        num_kv_heads=None, window=None):
    """Batched MHA on (B, L, D) inputs already projected; splits heads,
    scaled-dot-product, merges heads. Reference: src/operator/contrib/
    transformer.cc (interleaved_matmul_*).

    `num_kv_heads` (grouped heads): k and v hold that many heads, k of q's
    head size, and query head h reads key/value head h // (num_heads /
    num_kv_heads). v's heads may have a size of their own (latent attention:
    keys of 192 beside values of 128); the result is (B, L, num_heads x
    that). `window` (with `causal`): a row sees the `window` keys up to its
    own.

    Fast path: the pallas flash-attention kernel (ops/pallas/) — O(L)
    memory, scores stay in VMEM; causal, window and grouped heads all stay
    in it. What leaves it for the XLA formulation below (ops/select.py): an
    explicit mask, attention-weight dropout in training, a program
    partitioned over a mesh."""
    from . import select as _sel

    b, lq, d = q.shape
    lk = k.shape[1]
    hd = d // num_heads
    kv_heads = num_heads if num_kv_heads is None else num_kv_heads
    if (num_heads % kv_heads or k.shape[2] != kv_heads * hd
            or v.shape[2] % kv_heads):
        raise ValueError(f"multihead_attention: {num_heads} heads of {hd} over "
                         f"{kv_heads} key/value heads need k of width "
                         f"{kv_heads * hd}, got {k.shape[2]} (and v of "
                         f"{v.shape[2]})")
    if window is not None and not causal:
        raise ValueError("multihead_attention: window= needs causal=True")
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    def split(x, l, heads):
        return x.reshape(b, l, heads, -1).transpose(0, 2, 1, 3)

    qh = split(q, lq, num_heads)
    kh, vh = split(k, lk, kv_heads), split(v, lk, kv_heads)
    if _sel.flash_attention(mask, dropout_rate > 0.0 and training):
        from . import pallas as _pallas
        out = _pallas.flash_attention(qh, kh, vh, causal=causal,
                                      window=window, scale=scale)
        return out.transpose(0, 2, 1, 3).reshape(b, lq, -1)

    if kv_heads != num_heads:
        kh, vh = (jnp.repeat(x, num_heads // kv_heads, axis=1)
                  for x in (kh, vh))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        if lq > lk:
            raise ValueError("causal attention with more queries than keys is "
                             "undefined (use an explicit mask)")
        tri = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        if window is not None:      # the band: row - col < window
            tri = jnp.logical_and(tri, ~jnp.tril(
                jnp.ones((lq, lk), dtype=bool), k=lk - lq - window))
        mask = tri if mask is None else jnp.logical_and(mask, tri)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.asarray(-1e9, scores.dtype))
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and training and key is not None:
        w = dropout(w, key, dropout_rate, training)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, lq, -1)


def latent_attention(q_n, q_r, kv, k_r, num_heads):
    """Causal attention of a latent layer (MLA) on its parts as the
    products wrote them: q_n (B, L, H dn) and q_r (B, L, H dr), a head's
    queries without and with positions; kv (B, L, H (dn + dv)), head h's
    key part k_n,h and values v_h side by side; k_r (B, L, dr), the part
    of the keys every head shares. Head h's score is (q_n,h k_n,h^T +
    q_r,h k_r^T) / sqrt(dn + dr); returns (B, L, H dv).

    ops/select.py's row `latent_attention` (decided here, once a layer)
    runs the flash kernels on the parts under the op scope `attention`
    (ops/pallas/flash_attention.py `latent_flash_attention`). Elsewhere
    the keys are assembled a head at a time, [k_n,h ; k_r], and so are the
    queries, and `multihead_attention` runs on them."""
    from . import select as _sel
    b, length = q_n.shape[:2]
    dn, dr = q_n.shape[2] // num_heads, k_r.shape[2]
    dv = kv.shape[2] // num_heads - dn
    if _sel.latent_attention(dn, dr, dv):
        from . import pallas as _pallas
        with jax.named_scope("attention"):
            return _pallas.latent_flash_attention(q_n, q_r, kv, k_r,
                                                  num_heads)
    q = jnp.concatenate([q_n.reshape(b, length, num_heads, dn),
                         q_r.reshape(b, length, num_heads, dr)], -1)
    kv = kv.reshape(b, -1, num_heads, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, :, None], kv.shape[:3] + (dr,))], -1)
    return multihead_attention(
        q.reshape(b, length, -1), k.reshape(b, k.shape[1], -1),
        kv[..., dn:].reshape(b, kv.shape[1], -1), num_heads, causal=True)


# ---------------------------------------------------------------------------
# RMS norm, rotary positions
# ---------------------------------------------------------------------------

@jax.named_scope("rms_norm")
def rms_norm(x, gamma, eps=1e-6):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis; the statistic
    and the scaling in float32, the result in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(head_dim, rope_type="default", rope_theta=10000.0,
                     factor=1.0, original_max_position_embeddings=None,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None):
    """(inv_freq, attention_factor) of a rotary embedding from the keys of a
    published `rope_parameters` section: head_dim / 2 float64 frequencies
    and the factor cos and sin are multiplied by.

    "default": inv_freq_j = theta^(-2j / head_dim), factor 1.
    "yarn" (Peng et al. 2023, as HF's `_compute_yarn_parameters`): the
    frequencies that turn fewer than `beta_slow` times over the original
    context are divided by `factor`, those that turn more than `beta_fast`
    times are kept, with a linear ramp over the dimensions between; the
    attention factor defaults to 0.1 ln(factor) + 1."""
    half = head_dim // 2
    inv_freq = rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope_type == "default":
        return inv_freq, 1.0
    if rope_type != "yarn":
        raise ValueError(f"rope_frequencies: rope_type {rope_type!r} "
                         f"(known: 'default', 'yarn')")

    def dimension(turns):       # the dimension that turns `turns` times
        return (head_dim * np.log(original_max_position_embeddings
                                  / (turns * 2 * np.pi))
                / (2 * np.log(rope_theta)))
    low = max(np.floor(dimension(beta_fast)), 0)
    high = min(np.ceil(dimension(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0
    return inv_freq, float(attention_factor)


@jax.named_scope("rope")
def rope(x, inv_freq, num_heads, factor=1.0, rotary_dim=None,
         interleaved=False):
    """Rotary positions on (B, L, H * head_dim), HF's rotate-half form on
    each head: x cos + rotate_half(x) sin, with cos and sin of
    position * inv_freq repeated over both halves and times `factor`.
    Angles and the rotation in float32, the result in x's dtype.

    `rotary_dim` (a published `partial_rotary_factor` x head_dim): only the
    first `rotary_dim` channels of every head are rotated, by `inv_freq`'s
    rotary_dim / 2 frequencies; the others pass as they are, bit for bit.

    `interleaved` (a published `rope_interleave`, whole heads): channels
    (2j, 2j + 1) turn together by frequency j. They are taken apart into
    the two halves first, and the result stays in that order (HF's
    DeepSeek-V3 writes it so): the same order for q and k keeps q . k."""
    b, l, d = x.shape
    hd = d // num_heads
    angle = (jnp.arange(l, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos = (jnp.cos(angle) * factor)[None, :, None, :]
    sin = (jnp.sin(angle) * factor)[None, :, None, :]
    if rotary_dim is not None and rotary_dim != hd:
        heads = x.reshape(b, l, num_heads, hd)
        xf = heads[..., :rotary_dim].astype(jnp.float32)
        x1, x2 = xf[..., :rotary_dim // 2], xf[..., rotary_dim // 2:]
        out = jnp.concatenate(
            [(x1 * cos - x2 * sin).astype(x.dtype),
             (x2 * cos + x1 * sin).astype(x.dtype),
             heads[..., rotary_dim:]], -1)
        return out.reshape(b, l, d)
    xf = x.astype(jnp.float32).reshape(b, l, num_heads, hd)
    if interleaved:
        xf = xf.reshape(b, l, num_heads, hd // 2, 2).swapaxes(3, 4).reshape(
            b, l, num_heads, hd)
    x1, x2 = xf[..., :hd // 2], xf[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(b, l, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# linear attention: the gated delta rule, chunked (Kimi Delta Attention)
# ---------------------------------------------------------------------------

_DELTA_CHUNK = 64   # tokens whose products inside are matrix products
_DELTA_SUB = 16     # tokens of a chunk's diagonal blocks, decay by decay
_DELTA_GROUP = 8    # chunks a step of the outer scan; a state kept a step

_exact = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)


def short_conv(x, weight):
    """Causal depthwise convolution along the sequence: x (B, L, C), weight
    (K, C); y_t = sum_j weight[j] x_(t - K + 1 + j), zeros before the
    sequence. Summed in float32, returned in x's dtype."""
    taps, length = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(weight[j].astype(jnp.float32) * padded[:, j:j + length]
            for j in range(taps))
    return y.astype(x.dtype)


def _diagonal_blocks(a, size):
    """(..., n, n) -> (..., n / size, size, size): the blocks on the
    diagonal."""
    return jnp.stack([a[..., i:i + size, i:i + size]
                      for i in range(0, a.shape[-1], size)], -3)


def _unit_lower_inverse(a):
    """(I + a)^-1 of strictly lower triangular a (..., n, n), n a power of
    two times `_DELTA_SUB`: the diagonal blocks of that size, all at once,
    by the finite series (I - a)(I + a^2)(I + a^4)(I + a^8) (a^16 = 0;
    squared three times, its terms stay within 16-choose-8 of the
    result's), then neighbours joined level by level, [[P, 0], [-R C P, R]]
    for [[P^-1, 0], [C, R^-1]]. float32 at `highest`: what follows a chunk
    multiplies with it."""
    n, size = a.shape[-1], min(a.shape[-1], _DELTA_SUB)
    x = -_diagonal_blocks(a, size)
    inverse = jnp.eye(size, dtype=a.dtype) + x
    for _ in range((size - 1).bit_length() - 1):
        x = _exact(x, x)
        inverse = inverse + _exact(inverse, x)
    while size < n:
        top, bottom = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        below = _diagonal_blocks(a, 2 * size)[..., size:, :size]
        corner = -_exact(_exact(bottom, below), top)
        inverse = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], -1),
            jnp.concatenate([corner, bottom], -1)], -2)
        size *= 2
    return inverse[..., 0, :, :]


def _delta_group(state, q, k, v, g, beta):
    """`_DELTA_GROUP` chunks of the gated delta rule from `state` (B, H, dk,
    dv) float32: q, k, g (B, H, T, dk), v (B, H, T, dv), beta (B, H, T) ->
    (the state after them, o (B, H, T, dv)).

    With G the log-decays cumulated over a chunk, the chunk's T rows obey
    (I + A) U = beta (V - (K e^G) S), A_ti = beta_t sum_c k_tc k_ic
    e^(G_tc - G_ic) for i < t; then O = (Q e^G) S + P U with P_ti the same
    sum over q_t for i <= t, and S' = e^(G_last) S + (K e^(G_last - G))^T U.
    No decay is divided by: between sub-chunks of `_DELTA_SUB` rows, row t
    carries e^(G_t - G_before), G_before at its sub-chunk's start, and
    column i e^(G_before - G_i), both exponents <= 0; inside a sub-chunk
    the (rows, rows, dk) differences are exponentiated themselves. What is
    parallel over the chunks runs for all of them at once; the inner scan
    over them holds three products a chunk. Products take operands in q's
    dtype and sum in float32; the inverse and what it multiplies are
    float32 at `highest`."""
    b, h, t, dk = k.shape
    c, s = _DELTA_CHUNK, _DELTA_SUB
    n, m = t // c, c // s
    dtype, f32 = q.dtype, jnp.float32

    def chunked(x):
        return x.reshape(b, h, n, c, *x.shape[3:])

    q, k, v, g, beta = (chunked(x).astype(f32) for x in (q, k, v, g, beta))
    total = jnp.cumsum(g, axis=3)                       # (b, h, n, c, dk)
    sub = total.reshape(b, h, n, m, s, dk)
    before = jnp.concatenate([jnp.zeros_like(sub[:, :, :, :1, 0]),
                              sub[:, :, :, :-1, -1]], 3)   # (b, h, n, m, dk)
    inside = sub - before[..., None, :]                 # <= 0
    by_sub = (b, h, n, m, s, dk)
    k_sub, q_sub = k.reshape(by_sub), q.reshape(by_sub)
    # between sub-chunks: rows of sub-chunk a against the columns before it
    reach = before[:, :, :, :, None] - total[:, :, :, None]  # (.., m, c, dk)
    earlier = jnp.arange(c)[None, :] < s * jnp.arange(m)[:, None]
    k_col = (k[:, :, :, None] * jnp.exp(jnp.where(
        earlier[:, :, None], reach, -jnp.inf))).astype(dtype)
    rows = jnp.exp(inside)
    pairs = "bhnmsd,bhnmcd->bhnmsc"
    kk = jnp.einsum(pairs, (k_sub * rows).astype(dtype), k_col,
                    preferred_element_type=f32)
    qk = jnp.einsum(pairs, (q_sub * rows).astype(dtype), k_col,
                    preferred_element_type=f32)
    # inside a sub-chunk: e^(G_t - G_i) entry by entry, i <= t
    lower = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    decay = jnp.exp(jnp.where(
        lower[:, :, None],
        inside[:, :, :, :, :, None] - inside[:, :, :, :, None], -jnp.inf))
    k_cols = k_sub[:, :, :, :, None] * decay             # (.., m, s, s, dk)
    on_diagonal = "bhnmstd,bhnmsd->bhnmst"
    place = jnp.eye(m, dtype=f32)

    def square(between, within):
        whole = between + jnp.einsum("bhnmst,mr->bhnmsrt", within,
                                     place).reshape(between.shape)
        return whole.reshape(b, h, n, c, c)

    kk = square(kk, jnp.einsum(on_diagonal, k_cols, k_sub))
    qk = square(qk, jnp.einsum(on_diagonal, k_cols, q_sub))
    strictly = jnp.tril(jnp.ones((c, c), f32), -1)
    inverse = _unit_lower_inverse(beta[..., None] * kk * strictly)
    carried = jnp.exp(total)
    w = _exact(inverse, beta[..., None] * k * carried)
    u_alone = _exact(inverse, beta[..., None] * v)
    last = total[:, :, :, -1:]
    per_chunk = (w.astype(dtype), u_alone, (q * carried).astype(dtype),
                 qk.astype(dtype), (k * jnp.exp(last - total)).astype(dtype),
                 jnp.exp(last[:, :, :, 0]))

    def chunk(state, xs):
        w, u_alone, q_in, qk, k_out, kept = xs
        held = state.astype(dtype)
        u = u_alone - jnp.einsum("bhck,bhkv->bhcv", w, held,
                                 preferred_element_type=f32)
        u_low = u.astype(dtype)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_in, held,
                        preferred_element_type=f32)
             + jnp.einsum("bhct,bhtv->bhcv", qk, u_low,
                          preferred_element_type=f32))
        state = state * kept[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, u_low, preferred_element_type=f32)
        return state, o

    state, o = lax.scan(chunk, state,
                        tuple(jnp.moveaxis(x, 2, 0) for x in per_chunk))
    return state, jnp.moveaxis(o, 0, 2).reshape(b, h, t, -1).astype(dtype)


def _by_group(x, t):
    """(B, H, L, ...) -> (L / t, B, H, t, ...): the outer scan's steps."""
    b, h, length = x.shape[:3]
    return jnp.moveaxis(x.reshape(b, h, length // t, t, *x.shape[3:]), 2, 0)


def _joined(x):
    """`_by_group`'s inverse."""
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(*x.shape[:2], -1, *x.shape[4:])


def _delta_steps(q, k, v, g, beta):
    t = min(_DELTA_GROUP * _DELTA_CHUNK, q.shape[2])
    return tuple(_by_group(x, t) for x in (q, k, v, g, beta))


def _delta_fwd(q, k, v, g, beta):
    def step(state, xs):
        after, o = _delta_group(state, *xs)
        return after, (o, state)
    zero = jnp.zeros((*q.shape[:2], q.shape[3], v.shape[3]), jnp.float32)
    _, (o, starts) = lax.scan(step, zero, _delta_steps(q, k, v, g, beta))
    return _joined(o), (q, k, v, g, beta, starts)


def _delta_bwd(res, d_o):
    """The inputs and the state each step of the outer scan started from
    are all that was kept: a step's chunks are made again, and taken back,
    one step at a time from the last."""
    *inputs, starts = res

    def step(d_state, xs):
        start, d_o, *given = xs
        _, pull = jax.vjp(_delta_group, start, *given)
        d_start, *d_given = pull((d_state, d_o))
        return d_start, d_given
    steps = _delta_steps(*inputs)
    _, grads = lax.scan(step, jnp.zeros_like(starts[0]),
                        (starts, _by_group(d_o, steps[0].shape[3]), *steps),
                        reverse=True)
    return tuple(_joined(x) for x in grads)


@jax.custom_vjp
def _delta_rule(q, k, v, g, beta):
    return _delta_fwd(q, k, v, g, beta)[0]


_delta_rule.defvjp(_delta_fwd, _delta_bwd)


def _delta_inputs(*arrays):
    """((B, L, H, ...) arrays as the scan takes them: heads first, L padded
    to whole steps with tokens that change nothing (beta = 0, g = 0); the
    way back for a result)."""
    length = arrays[0].shape[1]
    chunks = -(-length // _DELTA_CHUNK)
    pad = -length % (min(_DELTA_GROUP, chunks) * _DELTA_CHUNK)

    def heads_first(x):
        x = jnp.moveaxis(x, 2, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))

    def back(x):
        return jnp.moveaxis(x[:, :, :length], 1, 2)
    return tuple(heads_first(x) for x in arrays), back


def _delta_padded(*arrays):
    """((B, L, H, ...) arrays as the Pallas kernels read them: as they are,
    L padded to whole grid steps with tokens that change nothing; the way
    back for a result)."""
    from .pallas import gated_delta_rule as _kernels
    length = arrays[0].shape[1]
    pad = _kernels.padded_length(length) - length

    def padded(x):
        return x if not pad else jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    def back(x):
        return x[:, :length]
    return tuple(padded(x) for x in arrays), back


def _delta_kernel_fwd(q, k, v, g, beta):
    from .pallas import gated_delta_rule as _kernels
    inputs, back = _delta_padded(q, k, v, g, beta)
    o, starts = _kernels.delta_rule_fwd(*inputs)
    return back(o), (q, k, v, g, beta, starts)


def _delta_kernel_bwd(res, d_o):
    from .pallas import gated_delta_rule as _kernels
    *given, starts = res
    inputs, back = _delta_padded(*given)
    grads = _kernels.delta_rule_bwd(*inputs, starts, _delta_padded(d_o)[0][0])
    return tuple(back(d) for d in grads)


@jax.custom_vjp
def _delta_rule_kernel(q, k, v, g, beta):
    return _delta_kernel_fwd(q, k, v, g, beta)[0]


_delta_rule_kernel.defvjp(_delta_kernel_fwd, _delta_kernel_bwd)


def _lowest_decay(g, axis=2):
    """The most negative log-decay cumulated over a chunk of g, whose
    tokens lie along `axis` (whole chunks of them): a chunk's own sum,
    since no entry is positive."""
    by_chunk = g.reshape(*g.shape[:axis], -1, _DELTA_CHUNK,
                         *g.shape[axis + 1:])
    return lax.stop_gradient(jnp.min(jnp.sum(by_chunk, axis=axis + 1)))


@jax.named_scope("scan")
def gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule with a decay a channel, from a zero state:

        S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
        o_t = S_t^T q_t

    q, k, g (B, L, H, dk), v (B, L, H, dv), beta (B, L, H); g <= 0 is the
    LOG of the decay. Returns (o (B, L, H, dv) in q's dtype, the most
    negative log-decay cumulated over any chunk: float32's exp underflows
    below -87, and a chunk that reaches it has forgotten its state anyway).

    Chunked: chunks of 64 tokens, matrix products inside, the state S
    (float32) carried across them, 8 chunks a step. Where ops/select.py
    says so (`gated_delta_rule`: the chip, one device, heads of a multiple
    of 128) the steps are the Pallas kernels of ops/pallas/
    gated_delta_rule.py, which read q, k, v, g as they are, with no
    heads-first copy, and keep a chunk's tiles and the state in VMEM;
    elsewhere `_delta_group` under a `lax.scan`, heads first. Either way
    the backward is the op's own: it keeps the inputs and a state every 512
    tokens and makes the rest again (`_delta_bwd`: `jax.vjp` of a step; the
    kernels: a rule written by hand). L is padded with tokens that change
    nothing (beta = 0, g = 0)."""
    from . import select
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if select.gated_delta_rule(q.shape[3], v.shape[3], q.dtype):
        return (_delta_rule_kernel(q, k, v, g, beta),
                _lowest_decay(_delta_padded(g)[0][0], 1))
    inputs, unpad = _delta_inputs(q, k, v, g, beta)
    return unpad(_delta_rule(*inputs)), _lowest_decay(inputs[3])


def _scoped(scope, fn):
    """fn, traced under the op scope `scope` (or none)."""
    if scope is None:
        return fn

    def run(*args):
        with jax.named_scope(scope):
            return fn(*args)
    return run


def _taken_back(scope, fn, *args):
    """(fn(*args), its pullback), both traced under the op scope `scope`. A
    name pushed INSIDE a function that `jax.vjp` transforms comes out
    wrapped whole, `transpose(jvp(a/b))`, which a reader of owners cannot
    split at the `/` (docs/profiler.md): so the stages of the mixer push
    their own last name only, and `linear_attention` is opened out here."""
    out, pull = _scoped(scope, functools.partial(jax.vjp, fn))(*args)
    return out, _scoped(scope, pull)


def _project(x, *weights):
    """x through (out, in) matrices one after the other, no bias."""
    for w in weights:
        x = dense(x, w, None, flatten=False)
    return x


@jax.named_scope("projections")
def _kda_projected(h, wq, wk, wv, wfa, wfb, wb):
    """h Wq, h Wk, h Wv, the log-decay's rank (h Wfa) Wfb, and h Wb."""
    return (_project(h, wq), _project(h, wk), _project(h, wv),
            _project(h, wfa, wfb), _project(h, wb))


@jax.named_scope("conv")
def _kda_mixed(num_heads, q, k, v, conv_q, conv_k, conv_v):
    """silu(conv(.)) in heads; q and k of unit length, q times dk^-1/2."""
    b, length = q.shape[:2]

    def mixed(x, taps, unit=None):
        x = jax.nn.silu(short_conv(x, taps)).reshape(b, length, num_heads, -1)
        if unit is None:
            return x
        xf = x.astype(jnp.float32)
        norm = lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-6)
        return (xf * (norm * unit)).astype(x.dtype)

    return (mixed(q, conv_q, (q.shape[2] // num_heads) ** -0.5),
            mixed(k, conv_k, 1.0), mixed(v, conv_v))


@jax.named_scope("gate")
def _kda_gates(num_heads, decay, beta, a_log, dt_bias):
    """(the log-decay a channel, beta), float32."""
    f32 = jnp.float32
    g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        (decay.astype(f32) + dt_bias.astype(f32)).reshape(
            *decay.shape[:2], num_heads, -1))
    return g, jax.nn.sigmoid(beta.astype(f32))


@jax.named_scope("out_norm")
def _kda_normed(eps, o, gate, gamma):
    """rms_norm(o; gamma) by head times sigmoid(gate), heads merged."""
    of = o.astype(jnp.float32)
    of = of * lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    of = of * gamma.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32).reshape(of.shape))
    return of.astype(o.dtype).reshape(*o.shape[:2], -1)


_kda_rank = jax.named_scope("projections")(_project)   # the gate's, and Wo


def _kda_stages(num_heads, eps, h, back, front):
    """The mixer's stages around the scan, each with its pullback: (what the
    scan reads, then a function of the scan's o that gives (out, pull))."""
    wga, wgb, gamma, wo = back
    wq, wk, wv, wfa, wfb, wb, conv_q, conv_k, conv_v, a_log, dt_bias = front
    la = "linear_attention"
    projected, pull_projected = _taken_back(None, _kda_projected, h, wq, wk,
                                            wv, wfa, wfb, wb)
    qkv, pull_mixed = _taken_back(
        la, functools.partial(_kda_mixed, num_heads), *projected[:3], conv_q,
        conv_k, conv_v)
    gates, pull_gates = _taken_back(
        la, functools.partial(_kda_gates, num_heads), *projected[3:], a_log,
        dt_bias)

    def pull_front(d_qkv, d_gates):
        *d_pre, d_cq, d_ck, d_cv = pull_mixed(tuple(d_qkv))
        d_decay, d_beta, d_a_log, d_dt_bias = pull_gates(tuple(d_gates))
        d_h, *d_weights = pull_projected((*d_pre, d_decay, d_beta))
        return d_h, (*d_weights, d_cq, d_ck, d_cv, d_a_log, d_dt_bias)

    def after(o):
        gate, pull_gate = _taken_back(None, _kda_rank, h, wga, wgb)
        merged, pull_normed = _taken_back(
            la, functools.partial(_kda_normed, eps), o, gate, gamma)
        out, pull_out = _taken_back(None, _kda_rank, merged, wo)

        def pull_back(d_out):
            d_merged, d_wo = pull_out(d_out)
            d_o, d_gate, d_gamma = pull_normed(d_merged)
            d_h, d_wga, d_wgb = pull_gate(d_gate)
            return d_o, d_h, (d_wga, d_wgb, d_gamma, d_wo)
        return out, pull_back

    return (*qkv, *gates), pull_front, after


def _kda_fwd(num_heads, eps, kernel, h, back, front):
    scanned, _, after = _kda_stages(num_heads, eps, h, back, front)
    with jax.named_scope("linear_attention/scan"):
        if kernel:
            o, (*_, starts) = _delta_kernel_fwd(*scanned)
            lowest = _lowest_decay(_delta_padded(scanned[3])[0][0], 1)
        else:
            inputs, unpad = _delta_inputs(*scanned)
            o, (*_, starts) = _delta_fwd(*inputs)
            o, lowest = unpad(o), _lowest_decay(inputs[3])
    return (after(o)[0], lowest), (h, back, front, starts, o)


def _kda_bwd(num_heads, eps, kernel, res, cotangents):
    """The block's input, its weights, the scan's result and a state every
    512 tokens are all the rule keeps (`_delta_bwd`, or the kernels'
    `delta_rule_bwd`): projections, convolutions, norms and gates are
    written as made again, then taken back with the scan. XLA decides what
    that costs: where memory allows it merges what is made again with the
    forward's own and keeps it (the Kimi-Linear cell: 7.35 GB of
    temporaries; behind an `optimization_barrier`, which forbids the merge,
    6.43 GB, four times the program, and the benchmark's memory check at
    8.5%: PERF.md, PR 32)."""
    h, back, front, starts, o = res
    scanned, pull_front, after = _kda_stages(num_heads, eps, h, back, front)
    d_o, d_h_back, d_back = after(o)[1](cotangents[0])
    with jax.named_scope("linear_attention/scan"):
        if kernel:
            d_scanned = _delta_kernel_bwd((*scanned, starts), d_o)
        else:
            inputs, unpad = _delta_inputs(*scanned)
            d_scanned = [unpad(d) for d in _delta_bwd(
                (*inputs, starts), _delta_inputs(d_o)[0][0])]
    d_scanned = [d.astype(x.dtype) for d, x in zip(d_scanned, scanned)]
    d_h, d_front = pull_front(d_scanned[:3], d_scanned[3:])
    return d_h + d_h_back, d_back, d_front


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _kda(num_heads, eps, kernel, h, back, front):
    return _kda_fwd(num_heads, eps, kernel, h, back, front)[0]


_kda.defvjp(_kda_fwd, _kda_bwd)


def linear_attention(h, wq, wk, wv, wfa, wfb, wb, wga, wgb, wo, conv_q,
                     conv_k, conv_v, a_log, dt_bias, gamma, num_heads,
                     eps=1e-5):
    """Kimi Delta Attention, one sequence mixer, on the block's input h (B,
    L, D) with its weights ((out, in) each, no bias): Wq, Wk, Wv to H heads
    of dk (dv); Wfa then Wfb the log-decay's rank; Wb the writing strength
    a head; Wga then Wgb the output gate's rank; Wo back to D; conv_* (K,
    channels) the depthwise taps; a_log (H,), dt_bias (H dk), gamma (dv,).

        q, k, v = silu(conv(h W.)); by head q^ = q / |q| dk^-1/2, k^ = k / |k|
        g = -exp(a_log) softplus((h Wfa) Wfb + dt_bias);  beta = sigmoid(h Wb)
        o = gated_delta_rule(q^, k^, v, g, beta)
        out = (rms_norm(o; gamma, by head) sigmoid((h Wga) Wgb)) Wo

    under the op scopes `linear_attention/conv` (with silu and the unit
    norm), `/gate`, `/scan` and `/out_norm`, the matrix products around
    them under `projections`. ONE differentiable unit (`_kda_bwd`): its
    backward keeps h, the weights, o and the scan's state every 512 tokens
    (0.14 GB a layer at 8192 x 2304 where the projections' results would be
    0.6), and makes the rest again. The scan is the Pallas kernels
    `gated_delta_rule_fwd` / `_bwd` where ops/select.py's row
    `gated_delta_rule` qualifies (decided here, once a layer, and handed
    to the backward), `_delta_group`'s XLA form elsewhere. Returns (out (B,
    L, D), `gated_delta_rule`'s most negative cumulated log-decay)."""
    from . import select
    kernel = select.gated_delta_rule(wq.shape[0] // num_heads,
                                     wv.shape[0] // num_heads, h.dtype)
    return _kda(num_heads, eps, kernel, h, (wga, wgb, gamma, wo),
                (wq, wk, wv, wfa, wfb, wb, conv_q, conv_k, conv_v, a_log,
                 dt_bias))


# ---------------------------------------------------------------------------
# compressed convolutional attention (CCA, arXiv:2510.04476)
# ---------------------------------------------------------------------------

def head_conv(x, weight):
    """Causal convolution along the sequence that MIXES the channels of a
    head: x (B, L, H * d), weight (K, H, d, d); head h of y_t is sum_j
    x_(t - K + 1 + j)[h] weight[j, h], zeros before the sequence. ONE
    product a head, over the K taps' channels side by side."""
    taps, heads, hd = weight.shape[:3]
    b, length = x.shape[:2]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).reshape(
        b, length + taps - 1, heads, hd)
    before = jnp.concatenate([padded[:, j:j + length] for j in range(taps)],
                             -1)
    y = jnp.einsum("blhc,hcd->blhd", before,
                   weight.transpose(1, 0, 2, 3).reshape(heads, taps * hd, hd))
    return y.reshape(b, length, heads * hd)


def _unit_heads(x, num_heads, gain):
    """sqrt(d) gain x / sqrt(sum x^2 + 1e-6) by head of d channels, in
    float32; `gain` (num_heads,) or None."""
    b, length, d = x.shape
    hd = d // num_heads
    xf = x.astype(jnp.float32).reshape(b, length, num_heads, hd)
    y = xf * (lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + 1e-6)
              * hd ** 0.5)
    if gain is not None:
        y = y * gain.astype(jnp.float32)[:, None]
    return y.reshape(b, length, d).astype(x.dtype)


@jax.named_scope("compressed_attention")
def compressed_attention(q, k, v, conv0, conv1, temp, inv_freq, num_heads,
                         num_kv_heads, rotary_dim=None, factor=1.0):
    """Attention inside a compressed latent, between a mixer's down
    projections and its output projection: q (B, L, H d), k and v (B, L,
    G d) as projected from the block's input, H query heads over G
    key/value heads of d.

    `shift`: the upper half of v's channels comes from the token BEFORE
    (zeros at the first): v = [x W_V1 ; shift(x) W_V2] with the shift made
    behind the product, which it commutes with. `conv`: [q ; k] through a
    causal depthwise convolution (`conv0` (K0, (H + G) d)) and then one that
    mixes the d channels of each of the H + G heads (`conv1` (K1, H + G, d,
    d)). `mean`: the convolved q gains (q + k) / 2 of the projections, k of
    its group broadcast over the group's query heads; the convolved k gains
    (the group's mean q + k) / 2. `norm`: q and k to length sqrt(d) by head,
    k times its head's `temp` (G,). `rope` on the first `rotary_dim`
    channels of every head (`rope`), then causal attention with query head
    h reading key/value head h // (H / G) (`multihead_attention`, so the
    flash kernels where ops/select.py says so). Returns (B, L, H d)."""
    b, length, wide = q.shape
    hd = wide // num_heads
    group = num_heads // num_kv_heads
    with jax.named_scope("shift"):
        half = v.shape[-1] // 2
        v = jnp.concatenate(
            [v[..., :half],
             jnp.pad(v[:, :-1, half:], ((0, 0), (1, 0), (0, 0)))], -1)
    with jax.named_scope("conv"):
        mixed = head_conv(short_conv(jnp.concatenate([q, k], -1), conv0),
                          conv1)
        q_c, k_c = mixed[..., :wide], mixed[..., wide:]
    with jax.named_scope("mean"):
        qg = q.astype(jnp.float32).reshape(b, length, num_kv_heads, group, hd)
        kg = k.astype(jnp.float32).reshape(b, length, num_kv_heads, 1, hd)
        q = (q_c.astype(jnp.float32)
             + ((qg + kg) * 0.5).reshape(b, length, wide)).astype(q.dtype)
        k = (k_c.astype(jnp.float32)
             + ((jnp.mean(qg, 3, keepdims=True) + kg) * 0.5).reshape(
                 b, length, -1)).astype(k.dtype)
    with jax.named_scope("norm"):
        q = _unit_heads(q, num_heads, None)
        k = _unit_heads(k, num_kv_heads, temp)
    q = rope(q, inv_freq, num_heads, factor, rotary_dim)
    k = rope(k, inv_freq, num_kv_heads, factor, rotary_dim)
    return multihead_attention(q, k, v, num_heads, causal=True,
                               num_kv_heads=num_kv_heads)


# ---------------------------------------------------------------------------
# sparse experts (dropless top-k routing over the experts held here)
# ---------------------------------------------------------------------------

def grouped_matmul(lhs, rhs, group_sizes, kernel):
    """lhs[rows of group g] @ rhs[g] for consecutive row groups: lhs (M, K),
    rhs (G, K, N), group_sizes (G,) int32 -> (M, N). Rows beyond the groups
    are UNSPECIFIED, in the result and in lhs's gradient: zeros from
    `jax.lax.ragged_dot`; whatever the buffer held from the Mosaic grouped
    matmul of ops/pallas/, which runs where `kernel` says so (the caller
    asks ops/select.py, under the scopes of whoever traces it)."""
    if kernel:
        from . import pallas as _pallas
        return _pallas.grouped_matmul(lhs, rhs, group_sizes)
    return lax.ragged_dot(lhs, rhs, group_sizes)


def _held_rows(rows, held, top_k):
    """(tokens, top_k, D) of the rows gathered for every assignment, zero
    where the assignment's expert is not held: those rows lie beyond the
    groups, where `grouped_matmul` writes nothing."""
    rows = rows.reshape(-1, top_k, rows.shape[-1])
    return jnp.where(held.reshape(-1, top_k, 1), rows,
                     jnp.zeros((), rows.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch_rows(x, order, slot, held, top_k):
    """Row r of the result is token order[r] // top_k: the tokens, one copy
    an assignment, in the order of their experts."""
    return x[order // top_k]


def _dispatch_bwd(top_k, res, g):
    # `slot` is the inverse of `order`: a token's gradient is a gather of
    # its top_k rows, where autodiff would scatter-add
    slot, held = res
    picked = _held_rows(g[slot], held, top_k)
    return (jnp.sum(picked, axis=1, dtype=jnp.float32).astype(g.dtype),
            None, None, None)


_dispatch_rows.defvjp(
    lambda x, order, slot, held, top_k: (x[order // top_k], (slot, held)),
    _dispatch_bwd)


@jax.custom_vjp
def _unsort_rows(y, order, slot):
    """Row a of the result is row slot[a] of y: the experts' rows back in
    the order of the assignments."""
    return y[slot]


_unsort_rows.defvjp(lambda y, order, slot: (y[slot], order),
                    lambda order, g: (g[order], None, None))


def _gated(gate_rows, up_rows):
    return (jax.nn.silu(gate_rows) * up_rows).astype(gate_rows.dtype)


@jax.named_scope("moe")
def _every_row(top_k, kernel, x2, prob, order, held, sizes, gate, up, down):
    """Dispatch, experts and combine on buffers of tokens x top_k rows, for
    a holder whose live rows may fill them (every expert held: PR 28's
    program). Autodiff keeps what the backward needs, nothing is made
    again; on the chip the rungs' backward, which keeps nothing, costs 29%
    more at such a load (PERF.md, PR 29)."""
    product = functools.partial(grouped_matmul, group_sizes=sizes,
                                kernel=kernel)
    with jax.named_scope("dispatch"):
        slot = jnp.argsort(order)       # the inverse permutation
        rows = _dispatch_rows(x2, order, slot, held, top_k)
    with jax.named_scope("experts"):
        out = product(_gated(product(rows, gate), product(rows, up)), down)
    with jax.named_scope("combine"):
        mine = _held_rows(_unsort_rows(out, order, slot), held, top_k)
        y = jnp.sum(mine.astype(jnp.float32) * prob[..., None], axis=1)
    return y.astype(x2.dtype)


def row_capacities(rows, count, experts):
    """The ladder of row-buffer capacities for `count` held of `experts`
    over `rows` = tokens x top_k assignments, from the shapes alone: the
    held experts' even share and a quarter more, up to a multiple of 128
    (ops/select.py `grouped_matmul`), and `rows` itself on top, which holds
    any routing. A holder of every expert has the one rung, `_every_row`.
    (A rung is a compiled copy of the layer: 66 MB of program and over a
    second of every start in the Mellum2 cell: PERF.md, PR 29.)"""
    share = -(-rows * count // experts)
    first = -(-5 * share // 512) * 128
    return (first, rows) if first < rows else (rows,)


def row_capacity(live, ladder):
    """Index of the smallest rung of `ladder` that holds `live` rows; the
    program picks it from a traced count, the host from a read one."""
    return sum(live > rung for rung in ladder[:-1])


def _rung_rows(top_k, capacity, x2, prob, order, sizes):
    """(assignment, token, weight (capacity, 1), live (capacity, 1), rows)
    of the first `capacity` assignments in expert order; `live` marks the
    rows the groups cover."""
    picked = order[:capacity]
    live = jnp.arange(capacity) < jnp.sum(sizes)
    with jax.named_scope("dispatch"):
        rows = x2[picked // top_k]
    return (picked, picked // top_k, prob.reshape(-1)[picked][:, None],
            live[:, None], rows)


def _sum_by_token(rows, picked, top_k, tokens, count, weight=None,
                  kernel=False, dtype=jnp.float32):
    """(tokens, D): row t sums the first `count` of `rows` that are token
    t's assignments (`picked`), each times its float32 `weight` where one is
    given, in float32; the rows past `count` are never added. The Pallas
    kernel (ops/pallas/token_sum.py, where `kernel` says so: ops/select.py
    `sum_by_token`) reads the live rows alone and writes each sum once, in
    `dtype`: on the chip 0.27 ms for the Mellum2 cell's 10240 rows, 0.81
    for 65536 rows with 28406 live. XLA masks every row and adds it where
    it belongs (1.73 ms), or, with a row an assignment, gathers them token
    by token (5.24 ms: PERF.md, PR 35), in float32."""
    if kernel:
        from .pallas import token_sum
        return token_sum.sum_by_token(rows, picked // top_k, count, tokens,
                                      weight, out_dtype=dtype)
    live = (jnp.arange(rows.shape[0]) < count)[:, None]
    if weight is None:
        rows = jnp.where(live, rows, jnp.zeros((), rows.dtype))
    else:
        rows = jnp.where(live, rows.astype(jnp.float32) * weight[:, None],
                         0.0)
    if picked.shape[0] == tokens * top_k:
        mine = rows[jnp.argsort(picked)].reshape(tokens, top_k, -1)
        return jnp.sum(mine, axis=1, dtype=jnp.float32)
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[
        picked // top_k].add(rows.astype(jnp.float32))


@jax.named_scope("moe")
def _rung_fwd(top_k, capacity, kernel, summed, x2, prob, order, sizes, gate,
              up, down):
    """The held experts' weighted sum a token, on buffers of `capacity`
    rows. Rows beyond the groups hold whatever the kernels left: they are
    never added (`_sum_by_token`), never multiplied away."""
    product = functools.partial(grouped_matmul, group_sizes=sizes,
                                kernel=kernel)
    picked, _, weight, _, rows = _rung_rows(top_k, capacity, x2, prob,
                                            order, sizes)
    with jax.named_scope("experts"):
        out = product(_gated(product(rows, gate), product(rows, up)), down)
    with jax.named_scope("combine"):
        y = _sum_by_token(out, picked, top_k, x2.shape[0], jnp.sum(sizes),
                          weight[:, 0], summed, x2.dtype)
    return y.astype(x2.dtype)


@jax.named_scope("moe")
def _rung_bwd(top_k, capacity, kernel, summed, x2, prob, order, sizes, gate,
              up, down, g):
    """Gradients of `_rung_fwd` for (x2, prob, gate, up, down): each gather
    transposed as a sum by token and each sum as a gather. The rows are
    gathered and the first two products made again: no residual has a
    rung's shape. Written out, it runs a product less than `jax.vjp` of
    `_rung_fwd` would (`through` serves the weight's gradient and
    `inner`'s; 159.3 ms a step of the Mellum2 cell for 162.2: PERF.md,
    PR 29) and sums the rows' gradients in float32."""
    product = functools.partial(grouped_matmul, group_sizes=sizes,
                                kernel=kernel)
    picked, token, weight, live, rows = _rung_rows(top_k, capacity, x2,
                                                   prob, order, sizes)
    with jax.named_scope("combine"):
        g_rows = g[token]
        d_out = jnp.where(live, g_rows.astype(jnp.float32) * weight,
                          0.0).astype(g.dtype)
    with jax.named_scope("experts"):
        hidden, pull_hidden = jax.vjp(
            lambda r, a, b: (product(r, a), product(r, b)), rows, gate, up)
        inner, pull_gated = jax.vjp(_gated, *hidden)
        # taken for its pull alone: the product has no reader and is not run
        _, pull_down = jax.vjp(product, inner, down)
        through = pull_down(g_rows)[0].astype(jnp.float32)
        d_down = pull_down(d_out)[1]
        d_rows, d_gate, d_up = pull_hidden(pull_gated(
            jnp.where(live, through * weight, 0.0).astype(g.dtype)))
    with jax.named_scope("combine"):
        d_weight = jnp.where(live[:, 0], jnp.sum(
            through * inner.astype(jnp.float32), axis=-1), 0.0)
        d_prob = jnp.zeros((prob.size,), prob.dtype).at[picked].set(
            d_weight, unique_indices=True)
    with jax.named_scope("dispatch"):
        d_x2 = _sum_by_token(d_rows, picked, top_k, x2.shape[0],
                             jnp.sum(sizes), None, summed, x2.dtype)
    return (d_x2.astype(x2.dtype), d_prob.reshape(prob.shape), d_gate, d_up,
            d_down)


# the inner `jit`: traced and lowered once for every layer of these shapes
# and this choice of kernels, not once a layer (the Mellum2 cell has four)
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _on_rung(body, top_k, ladder, kernel, summed, *operands):
    """`body` at the smallest capacity of `ladder` that holds the live
    rows, chosen on the device."""
    # the barrier keeps XLA from cloning what reads the result into every
    # branch: 159.2 ms a step of the Mellum2 cell for 161.0 without it
    # (PERF.md, PR 29). It is also what the benchmark's memory check passes
    # by (reserved HBM against the compiler's count of temporaries: 2.78%
    # apart, 5.7% without it, limit 5: ROADMAP.md, Queue 3, item 3)
    return lax.optimization_barrier(lax.switch(
        row_capacity(jnp.sum(operands[3]), ladder),
        [functools.partial(body, top_k, rung, kernel, summed)
         for rung in ladder],
        *operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _routed(top_k, ladder, kernel, summed, *operands):
    """Dispatch, experts and combine of (x2, prob, order, sizes, gate, up,
    down) as ONE differentiable unit: forward and backward each switch on
    the same rung and run the same product and the same sum by token
    (`kernel`, `summed`: jax runs a backward rule after the scopes that
    ops/select.py reads have closed), and what
    passes between them has no rung's shape (autodiff of a `switch` would
    make every branch return every branch's residuals, zero-filled)."""
    # jax traces a backward rule under its operands' abstract mesh and a
    # forward under none: under the same one, megablox's `jit`ted kernels
    # are traced once for both (the backward makes products again)
    with jax.sharding.use_abstract_mesh(jax.typeof(operands[0]).sharding.mesh):
        return _on_rung(_rung_fwd, top_k, ladder, kernel, summed, *operands)


def _routed_bwd(top_k, ladder, kernel, summed, operands, g):
    d_x2, d_prob, *d_weights = _on_rung(_rung_bwd, top_k, ladder, kernel,
                                        summed, *operands, g)
    return (d_x2, d_prob, None, None, *d_weights)


_routed.defvjp(lambda *args: (_routed(*args), args[4:]), _routed_bwd)


def gated_ffn(x, gate, up, down):
    """(silu(x gate) * (x up)) down: x (..., D), gate and up (D, F), down
    (F, D); the gated feed-forward of a dense layer or a shared expert."""
    return jnp.dot(_gated(jnp.dot(x, gate), jnp.dot(x, up)), down)


def router_mlp(x, previous, down, gamma, w1, w2, w3):
    """A router that is more than one product (ZAYA1, arXiv:2511.17127), on
    x (..., D): r = x down^T (R wide); with the `previous` layer's state,
    r += gamma * previous (`gamma` (R,): an average over depth, learned);
    logits = w3 gelu(w2 gelu(w1 r)) over the E experts in float32 (w1, w2
    (R, R), w3 (E, R); erf GELU, no bias). Returns (logits (..., E), the
    state r (..., R) that the next layer's router takes). Under the op
    scopes `moe/router/down`, `/average`, `/mlp`; `sparse_experts(logits=)`
    routes by them."""
    with jax.named_scope("moe"), jax.named_scope("router"):
        with jax.named_scope("down"):
            state = jnp.dot(x, down.T)
        if previous is not None:
            with jax.named_scope("average"):
                state = state + gamma * previous
        with jax.named_scope("mlp"):
            hidden = jax.nn.gelu(jnp.dot(state, w1.T), approximate=False)
            hidden = jax.nn.gelu(jnp.dot(hidden, w2.T), approximate=False)
            logits = jnp.dot(hidden, w3.T,
                             preferred_element_type=jnp.float32)
    return logits, state


def sparse_experts(x, router, gate, up, down, top_k, first=0,
                   norm_topk_prob=True, scoring="softmax", bias=None,
                   scale=1.0, logits=None):
    """The part that the experts held here add to a sparse-expert layer.

    x (..., D); router (E, D) over ALL E experts, or None with `logits`
    (..., E), float32, of a router computed outside this op's one product
    (`router_mlp`); gate and up (C, D, F), down (C, F, D): the C experts
    [first, first + C) held here. Every token takes its `top_k` largest of
    softmax(x router^T) (float32), with weights normalised over the top_k
    when `norm_topk_prob` (with ONE expert a token that makes every weight
    1 and leaves the router no gradient: such a family's weight is the
    chosen expert's own probability); expert e gives (silu(x gate_e) * (x
    up_e)) down_e. `scoring="sigmoid"` scores each expert by itself; `bias`
    (E,) is added to the scores for the CHOICE alone (the weights are the
    scores', and no gradient reaches it: a balancing bias that is no
    weight); `scale` multiplies the weights. The result sums, for each
    token, the weighted outputs of its experts that are held here (in
    float32); what the others would add is left out. No assignment to a held expert is
    dropped, whatever the routing: the assignments to held experts are
    sorted by expert, and the row buffers hold the first `capacity` of
    them, the smallest rung of `row_capacities` that holds the live rows of
    THIS call (chosen on the device from `load`: a quarter over the even
    share while the routing is near its balance, tokens x top_k when it is
    not). Everything that walks a row buffer walks `capacity` rows, and the
    grouped products compute the live rows and no other. A holder of every
    expert has the one capacity, tokens x top_k, and chooses nothing.

    Returns (y like x, load (E,) int32: assignments to each expert)."""
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    tokens, count = x2.shape[0], gate.shape[0]
    experts = router.shape[0] if logits is None else logits.shape[-1]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            if logits is None:
                logits = jnp.dot(x2, router.T,
                                 preferred_element_type=jnp.float32)
            else:
                logits = logits.reshape(tokens, experts)
            if scoring == "softmax" and bias is None:
                prob, chosen = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
            else:
                score = (jax.nn.softmax(logits, axis=-1)
                         if scoring == "softmax" else jax.nn.sigmoid(logits))
                _, chosen = lax.top_k(
                    score if bias is None else lax.stop_gradient(
                        score + bias.astype(jnp.float32)), top_k)
                prob = jnp.take_along_axis(score, chosen, axis=-1)
            if norm_topk_prob:
                prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
            if scale != 1.0:
                prob = prob * scale
            chosen = chosen.reshape(-1)
            # counted by comparison: a scatter-add of every assignment is slow
            load = jnp.sum(chosen[:, None] == jnp.arange(experts)[None, :],
                           axis=0, dtype=jnp.int32)
        with jax.named_scope("dispatch"):
            local = chosen - first
            held = jnp.logical_and(local >= 0, local < count)
            # assignments to held experts first, by expert; the rest behind
            order = jnp.argsort(jnp.where(held, local, count), stable=True)
            sizes = lax.dynamic_slice(load, (first,), (count,))
    # Pallas or XLA is chosen HERE, under the caller's scopes, for every rung
    # and for the backward (the first rung is a multiple of 128 wherever the
    # last one is); the rungs open `moe` again under their `cond`
    ladder = row_capacities(tokens * top_k, count, experts)
    from . import select as _sel
    kernel = _sel.grouped_matmul(
        jax.ShapeDtypeStruct((ladder[-1], d), x.dtype), gate)
    operands = (x2, prob, order, sizes, gate, up, down)
    if len(ladder) == 1:
        y = _every_row(top_k, kernel, *operands[:3], held, *operands[3:])
    else:
        summed = _sel.sum_by_token(
            jax.ShapeDtypeStruct((ladder[-1], d), x.dtype), tokens)
        y = _routed(top_k, ladder, kernel, summed, *operands)
    return y.reshape(*lead, d), load


# ---------------------------------------------------------------------------
# vision extras (reference: src/operator/roi_pooling.cc, im2col.h)
# ---------------------------------------------------------------------------

def roi_pooling(x, rois, pooled_size, spatial_scale):
    """ROI max pooling, NCHW. x: (N,C,H,W); rois: (R,5) [batch_idx, x0, y0,
    x1, y1] in image coords. Static-shape TPU formulation: one mask-matmul
    per pooled cell over the full H,W grid is replaced by a gather-free
    max over a masked grid — vectorized over rois via vmap."""
    n, c, h, w = x.shape
    ph, pw = pooled_size
    ys = jnp.arange(h, dtype=jnp.float32)
    xs = jnp.arange(w, dtype=jnp.float32)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x0, y0, x1, y1 = roi[1] * spatial_scale, roi[2] * spatial_scale, \
            roi[3] * spatial_scale, roi[4] * spatial_scale
        x0, y0 = jnp.round(x0), jnp.round(y0)
        x1, y1 = jnp.round(x1), jnp.round(y1)
        rw = jnp.maximum(x1 - x0 + 1.0, 1.0)
        rh = jnp.maximum(y1 - y0 + 1.0, 1.0)
        bin_h, bin_w = rh / ph, rw / pw
        img = x[b]                                          # (C,H,W)

        def cell(i, j):
            hs = jnp.floor(y0 + i * bin_h)
            he = jnp.ceil(y0 + (i + 1) * bin_h)
            ws_ = jnp.floor(x0 + j * bin_w)
            we = jnp.ceil(x0 + (j + 1) * bin_w)
            mask = ((ys >= hs) & (ys < he))[:, None] & \
                   ((xs >= ws_) & (xs < we))[None, :]
            empty = ~mask.any()
            val = jnp.max(jnp.where(mask[None], img, -jnp.inf), axis=(1, 2))
            return jnp.where(empty, 0.0, val)

        ii, jj = jnp.meshgrid(jnp.arange(ph), jnp.arange(pw), indexing="ij")
        cells = jax.vmap(jax.vmap(cell))(ii, jj)            # (ph,pw,C)
        return cells.transpose(2, 0, 1)                     # (C,ph,pw)

    return jax.vmap(one_roi)(rois.astype(jnp.float32))      # (R,C,ph,pw)


def im2col(x, kernel, stride=None, dilate=None, pad=None):
    """Unfold NCHW patches to columns (reference im2col.h):
    (N, C, H, W) -> (N, C*kh*kw, L) with L = out_h*out_w."""
    kh, kw = kernel
    stride = stride or (1, 1)
    dilate = dilate or (1, 1)
    pad = pad or (0, 0)
    n, c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    out_h = (h + 2 * pad[0] - dilate[0] * (kh - 1) - 1) // stride[0] + 1
    out_w = (w + 2 * pad[1] - dilate[1] * (kw - 1) - 1) // stride[1] + 1
    # extract_patches via gather of strided indices (static shapes)
    i0 = jnp.arange(out_h) * stride[0]
    j0 = jnp.arange(out_w) * stride[1]
    ki = jnp.arange(kh) * dilate[0]
    kj = jnp.arange(kw) * dilate[1]
    rows = i0[:, None] + ki[None, :]                         # (out_h, kh)
    cols = j0[:, None] + kj[None, :]                         # (out_w, kw)
    # (N, C, out_h, kh, W') -> (N, C, out_h, kh, out_w, kw)
    patches = xp[:, :, rows][:, :, :, :, cols]
    patches = patches.transpose(0, 1, 3, 5, 2, 4)            # N,C,kh,kw,oh,ow
    return patches.reshape(n, c * kh * kw, out_h * out_w)


# ---------------------------------------------------------------------------
# contrib vision ops (reference src/operator/contrib/: roi_align.cc,
# bilinear_resize.cc, adaptive_avg_pooling.cc)
# ---------------------------------------------------------------------------

def _interp_matrix(out_len, in_len):
    """(out_len, in_len) bilinear row-sampling matrix, align-corners
    semantics (the reference BilinearResize2D kernel). Interpolation as a
    dense matmul keeps the op on the MXU instead of gather units."""
    if in_len == 1:
        return jnp.ones((out_len, 1), jnp.float32)
    pos = jnp.linspace(0.0, in_len - 1.0, out_len)
    i0 = jnp.floor(pos).astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, in_len - 1)
    f = (pos - i0).astype(jnp.float32)
    rows = jnp.arange(out_len)
    a = jnp.zeros((out_len, in_len), jnp.float32)
    return a.at[rows, i0].add(1.0 - f).at[rows, i1].add(f)


def dot_mx(x, y, transpose_a=False, transpose_b=False):
    """MXNet dot semantics on raw arrays: contract last axis of x with
    first axis of y; transpose_a swaps x's last two axes, transpose_b
    swaps y's first two. The ONE implementation behind nd.dot and the
    symbol 'dot' op."""
    if transpose_a:
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if transpose_b:
        y = jnp.swapaxes(y, 0, 1) if y.ndim > 1 else y
    if x.ndim == 1 and y.ndim == 1:
        return jnp.dot(x, y)
    return jnp.tensordot(x, y, axes=1)


def validate_resize_sizes(height, width, op="BilinearResize2D"):
    """Shared nd/symbol-path validation: explicit positive integer sizes
    (python ints or numpy integer scalars; bool rejected). Returns them as
    python ints."""
    import operator as _op
    try:
        if isinstance(height, bool) or isinstance(width, bool):
            raise TypeError
        height, width = _op.index(height), _op.index(width)
        if height <= 0 or width <= 0:
            raise TypeError
    except TypeError:
        raise ValueError(f"{op} requires explicit positive integer height= "
                         f"and width= (got height={height!r}, "
                         f"width={width!r})")
    return height, width


def _fractional_compute_dtype(x):
    """Fractional-weight ops (resize/avg-pool/roi sampling) must not cast
    weights in [0,1] to an integer input dtype — that truncates them to 0
    and silently zeroes the output. Integer inputs compute in f32 and the
    caller rounds back."""
    return x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32


def _cast_back(y, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return y
    return jnp.round(y).astype(dtype)


def bilinear_resize(x, height, width):
    """BilinearResize2D, NCHW (reference contrib op). out = A_h @ x @ A_w.T
    per channel — two MXU contractions, no dynamic gathers. Integer images
    (e.g. uint8) compute in f32 and round back."""
    cd = _fractional_compute_dtype(x)
    a_h = _interp_matrix(height, x.shape[2]).astype(cd)
    a_w = _interp_matrix(width, x.shape[3]).astype(cd)
    y = jnp.einsum("ij,ncjk,lk->ncil", a_h, x.astype(cd), a_w)
    return _cast_back(y, x.dtype)


def adaptive_avg_pool(x, output_size):
    """AdaptiveAvgPooling2D, NCHW (reference contrib op). Torch-style
    bins: cell i averages rows [floor(i*H/oh), ceil((i+1)*H/oh)). The
    (static) bin structure becomes averaging matrices -> MXU einsum."""
    import numpy as _np
    oh, ow = ((output_size, output_size) if isinstance(output_size, int)
              else tuple(output_size))

    def avg_matrix(out_len, in_len):
        m = _np.zeros((out_len, in_len), _np.float32)
        for i in range(out_len):
            s = (i * in_len) // out_len
            e = -(-((i + 1) * in_len) // out_len)  # ceil div
            m[i, s:e] = 1.0 / (e - s)
        return jnp.asarray(m)

    cd = _fractional_compute_dtype(x)
    a_h = avg_matrix(oh, x.shape[2]).astype(cd)
    a_w = avg_matrix(ow, x.shape[3]).astype(cd)
    y = jnp.einsum("ij,ncjk,lk->ncil", a_h, x.astype(cd), a_w)
    return _cast_back(y, x.dtype)


def roi_align(x, rois, pooled_size, spatial_scale, sample_ratio=-1):
    """ROIAlign, NCHW (reference src/operator/contrib/roi_align.cc —
    the Mask R-CNN op: no coordinate rounding, bilinear sample points
    averaged per cell). x (N,C,H,W); rois (R,5) [batch_idx, x0, y0,
    x1, y1] image coords. sample_ratio<=0 uses 2 samples per bin axis
    (static shapes; the reference's adaptive ceil(bin) is data-dependent
    and would defeat jit)."""
    n, c, h, w = x.shape
    ph, pw = pooled_size
    s = sample_ratio if sample_ratio and sample_ratio > 0 else 2
    out_dtype = x.dtype
    x = x.astype(_fractional_compute_dtype(x))

    ky = (jnp.arange(ph)[:, None] + (jnp.arange(s)[None, :] + 0.5) / s)  # (ph,s)
    kx = (jnp.arange(pw)[:, None] + (jnp.arange(s)[None, :] + 0.5) / s)  # (pw,s)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x0, y0, x1, y1 = (roi[1] * spatial_scale, roi[2] * spatial_scale,
                          roi[3] * spatial_scale, roi[4] * spatial_scale)
        rh = jnp.maximum(y1 - y0, 1.0)
        rw = jnp.maximum(x1 - x0, 1.0)
        ys = (y0 + ky * (rh / ph)).reshape(-1)                # (ph*s,)
        xs = (x0 + kx * (rw / pw)).reshape(-1)                # (pw*s,)
        # reference border rule (roi_align.cc): samples beyond one pixel
        # outside the image contribute ZERO; the [-1, H] band clamps to
        # the edge for the bilinear corners
        vy = ((ys >= -1.0) & (ys <= h)).astype(x.dtype)
        vx = ((xs >= -1.0) & (xs <= w)).astype(x.dtype)
        ys = jnp.clip(ys, 0.0, h - 1.0)
        xs = jnp.clip(xs, 0.0, w - 1.0)
        yi0 = jnp.floor(ys).astype(jnp.int32)
        xi0 = jnp.floor(xs).astype(jnp.int32)
        yi1 = jnp.minimum(yi0 + 1, h - 1)
        xi1 = jnp.minimum(xi0 + 1, w - 1)
        fy = (ys - yi0).astype(x.dtype)
        fx = (xs - xi0).astype(x.dtype)
        img = x[b]                                            # (C,H,W)
        # separable bilinear: gather rows then columns
        gy0 = jnp.take(img, yi0, axis=1)                      # (C,PY,W)
        gy1 = jnp.take(img, yi1, axis=1)
        gy = gy0 * (1 - fy)[None, :, None] + gy1 * fy[None, :, None]
        g00 = jnp.take(gy, xi0, axis=2)                       # (C,PY,PX)
        g01 = jnp.take(gy, xi1, axis=2)
        vals = g00 * (1 - fx)[None, None, :] + g01 * fx[None, None, :]
        vals = vals * (vy[None, :, None] * vx[None, None, :])
        vals = vals.reshape(c, ph, s, pw, s)
        return vals.mean(axis=(2, 4))                         # (C,ph,pw)

    return _cast_back(jax.vmap(one_roi)(rois.astype(jnp.float32)),
                      out_dtype)
