"""NDArray-level operator namespace (parity: mx.nd.Convolution etc.).

Thin recordable wrappers over ops/_raw.py. Gluon layers call these in eager
mode; under hybridize the same code runs with tracers and compiles into one
XLA computation. `from incubator_mxnet_tpu import ops` or use the mirrored
names on `mx.nd`.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from .. import autograd
from ..ndarray import NDArray, _apply, _as_nd, _is_tracer
from ..ndarray import random as ndrandom
from . import _raw

from .box import (box_iou, box_nms, MultiBoxPrior, MultiBoxTarget,
                  MultiBoxDetection)

__all__ = ["FullyConnected", "Convolution", "Deconvolution", "Pooling",
           "ConvBNReLU",
           "BatchNorm", "LayerNorm", "InstanceNorm", "GroupNorm", "Activation",
           "Dropout", "L2Normalization", "softmax_cross_entropy", "smooth_l1",
           "UpSampling", "multihead_attention", "latent_attention",
           "RMSNorm", "rope",
           "sparse_experts", "router_mlp", "compressed_attention",
           "gated_ffn", "linear_attention", "box_iou",
           "box_nms",
           "MultiBoxPrior", "MultiBoxTarget", "MultiBoxDetection",
           "ROIPooling", "ROIAlign", "BilinearResize2D",
           "AdaptiveAvgPooling2D", "im2col", "SliceChannel",
           "SequenceMask", "SequenceLast", "SequenceReverse",
           "GridGenerator", "BilinearSampler", "SpatialTransformer",
           "Correlation", "foreach", "while_loop", "cond"]


def _symbolic(x):
    """True when a Gluon forward is being traced to a Symbol graph (the
    block was called with a Symbol input — see gluon/symbolize.py)."""
    return not isinstance(x, NDArray) and type(x).__name__ == "Symbol"


def _sym_call(name, out_index=None, **kw):
    from ..gluon.symbolize import sym_call
    return sym_call(name, out_index=out_index, **kw)


def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    if _symbolic(data):
        return _sym_call("FullyConnected", data=data, weight=weight,
                         bias=None if no_bias else bias,
                         no_bias=no_bias or bias is None,
                         num_hidden=num_hidden, flatten=flatten)
    if no_bias or bias is None:
        return _apply(lambda x, w: _raw.dense(x, w, None, flatten),
                      [data, weight], name="FullyConnected")
    return _apply(lambda x, w, b: _raw.dense(x, w, b, flatten),
                  [data, weight, bias], name="FullyConnected")


def Convolution(data, weight, bias=None, kernel=None, stride=None, pad=None,
                dilate=None, num_filter=None, num_group=1, no_bias=False,
                layout="NCHW"):
    if _symbolic(data):
        if num_filter is None and hasattr(weight, "shape"):
            num_filter = (weight.shape[-1] if layout == "NHWC"
                          else weight.shape[0])
        return _sym_call("Convolution", data=data, weight=weight,
                         bias=None if no_bias else bias,
                         no_bias=no_bias or bias is None, kernel=kernel,
                         stride=stride, pad=pad, dilate=dilate,
                         num_filter=num_filter, num_group=num_group,
                         layout=layout)
    kw = dict(kernel=kernel, stride=stride, pad=pad, dilate=dilate,
              num_group=num_group, layout=layout)
    if no_bias or bias is None:
        return _apply(lambda x, w: _raw.conv(x, w, None, **kw),
                      [data, weight], name="Convolution")
    return _apply(lambda x, w, b: _raw.conv(x, w, b, **kw),
                  [data, weight, bias], name="Convolution")


def Deconvolution(data, weight, bias=None, kernel=None, stride=None, pad=None,
                  dilate=None, adj=None, num_filter=None, num_group=1,
                  no_bias=False, layout="NCHW"):
    if _symbolic(data):
        if hasattr(weight, "shape"):
            if kernel is None:
                kernel = (weight.shape[:-2] if layout == "NHWC"
                          else weight.shape[2:])
            if num_filter is None:
                num_filter = num_group * (weight.shape[-2] if layout == "NHWC"
                                          else weight.shape[1])
        return _sym_call("Deconvolution", data=data, weight=weight,
                         bias=None if no_bias else bias,
                         no_bias=no_bias or bias is None, kernel=kernel,
                         stride=stride, pad=pad, dilate=dilate, adj=adj,
                         num_filter=num_filter, num_group=num_group,
                         layout=layout)
    kw = dict(stride=stride, pad=pad, dilate=dilate, adj=adj,
              num_group=num_group, layout=layout)
    if no_bias or bias is None:
        return _apply(lambda x, w: _raw.conv_transpose(x, w, None, **kw),
                      [data, weight], name="Deconvolution")
    return _apply(lambda x, w, b: _raw.conv_transpose(x, w, b, **kw),
                  [data, weight, bias], name="Deconvolution")


def ConvBNReLU(data, weight, gamma, beta, moving_mean, moving_var, *,
               eps=1e-5, stride=None, pad=None, dilate=None, num_group=1,
               layout="NHWC", act_type="relu"):
    """Fused conv + BatchNorm + activation — the inference/serving hot
    path (reference analogue: cuDNN's fused ConvBiasActivation). In
    predict mode, qualifying shapes (ops/select.py) run the pallas fused
    kernel (1x1 convs as one matmul+epilogue program); otherwise the op
    is the exact conv→BN→act chain. Moving stats are read, never
    written — training graphs should keep separate Conv/BatchNorm blocks
    so the stats update (this op discards batch-stat updates)."""
    training = autograd.is_training()

    def f(x, w, g, b, mm, mv):
        return _raw.conv_bn_relu(x, w, g, b, mm, mv, eps=eps, stride=stride,
                                 pad=pad, dilate=dilate,
                                 num_group=num_group, layout=layout,
                                 act=act_type, training=training)

    return _apply(f, [data, weight, gamma, beta, moving_mean, moving_var],
                  name="ConvBNReLU")


def Pooling(data, pool_type="max", kernel=(2, 2), stride=None, pad=None,
            global_pool=False, count_include_pad=True, layout="NCHW",
            ceil_mode=False):
    if _symbolic(data):
        return _sym_call("Pooling", data=data, pool_type=pool_type,
                         kernel=kernel, stride=stride, pad=pad,
                         global_pool=global_pool,
                         count_include_pad=count_include_pad, layout=layout,
                         ceil_mode=ceil_mode)
    return _apply(lambda x: _raw.pooling(x, pool_type, kernel, stride, pad,
                                         global_pool, count_include_pad, layout,
                                         ceil_mode),
                  [data], name="Pooling")


def BatchNorm(data, gamma, beta, moving_mean, moving_var, *, axis=1, eps=1e-5,
              momentum=0.9, fix_gamma=False, use_global_stats=False,
              output_mean_var=False):
    """Eager BatchNorm. In training mode (autograd.is_training) uses batch
    stats and updates moving_mean/var NDArrays in place (outside the tape),
    like the reference's in-place aux update. Single pass: y and new moving
    stats come from one recorded op."""
    training = autograd.is_training()

    def fwd(x, g, b, mm, mv):
        return _raw.batch_norm(x, g, b, mm, mv, axis=axis, eps=eps,
                               momentum=momentum, training=training,
                               use_global_stats=use_global_stats,
                               fix_gamma=fix_gamma)

    out, nm, nv = _apply(fwd, [data, gamma, beta, moving_mean, moving_var],
                         n_out=3, name="BatchNorm")
    if training and not use_global_stats:
        moving_mean._data = nm._data
        moving_var._data = nv._data
    return out


def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5):
    if _symbolic(data):
        return _sym_call("LayerNorm", data=data, gamma=gamma, beta=beta,
                         axis=axis, eps=eps)
    return _apply(lambda x, g, b: _raw.layer_norm(x, g, b, axis, eps),
                  [data, gamma, beta], name="LayerNorm")


def InstanceNorm(data, gamma, beta, eps=1e-5):
    if _symbolic(data):
        return _sym_call("InstanceNorm", data=data, gamma=gamma, beta=beta,
                         eps=eps)
    return _apply(lambda x, g, b: _raw.instance_norm(x, g, b, eps),
                  [data, gamma, beta], name="InstanceNorm")


def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5):
    return _apply(lambda x, g, b: _raw.group_norm(x, g, b, num_groups, eps),
                  [data, gamma, beta], name="GroupNorm")


def Activation(data, act_type="relu"):
    if _symbolic(data):
        return _sym_call("Activation", data=data, act_type=act_type)
    return _apply(lambda x: _raw.activation(x, act_type), [data], name="Activation")


def Dropout(data, p=0.5, mode="training", axes=()):
    if _symbolic(data):
        return _sym_call("Dropout", data=data, p=p, mode=mode, axes=axes)
    training = autograd.is_training() or mode == "always"
    if not training or p == 0.0:
        return data
    key = ndrandom._key()
    return _apply(lambda x: _raw.dropout(x, key, p, True, axes), [data],
                  name="Dropout")


def L2Normalization(data, eps=1e-10, mode="instance"):
    return _apply(lambda x: _raw.l2_normalization(x, eps, mode), [data],
                  name="L2Normalization")


def softmax_cross_entropy(data, label, axis=-1, sparse_label=True):
    label = _as_nd(label)
    return _apply(lambda x, l: _raw.softmax_cross_entropy(x, l, axis, sparse_label),
                  [data, label], name="softmax_cross_entropy")


def smooth_l1(data, scalar=1.0):
    return _apply(lambda x: _raw.smooth_l1(x, scalar), [data], name="smooth_l1")


def UpSampling(data, scale=2, sample_type="nearest", num_filter=None,
               layout="NCHW"):
    """Parity: mx.nd.UpSampling (src/operator/nn/upsampling.cc); `bilinear`
    is the reference's fixed-weight Deconvolution path (num_filter accepted
    for API parity; channels are inferred)."""
    if _symbolic(data):
        return _sym_call("UpSampling", data=data, scale=scale,
                         sample_type=sample_type, num_filter=num_filter,
                         layout=layout)
    return _apply(lambda x: _raw.upsampling(x, scale, sample_type, layout),
                  [data], name="UpSampling")


def ROIPooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    """ROI max pooling (reference: mx.nd.ROIPooling). data NCHW; rois (R,5)
    rows [batch_idx, x0, y0, x1, y1] image coords."""
    if _symbolic(data):
        return _sym_call("ROIPooling", data=data, rois=rois,
                         pooled_size=pooled_size,
                         spatial_scale=spatial_scale)
    return _apply(lambda x, r: _raw.roi_pooling(x, r, pooled_size,
                                                spatial_scale),
                  [data, _as_nd(rois)], name="ROIPooling")


def ROIAlign(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
             sample_ratio=-1):
    """ROIAlign (reference: mx.nd.contrib.ROIAlign,
    src/operator/contrib/roi_align.cc). data NCHW; rois (R,5)
    [batch_idx, x0, y0, x1, y1] image coords."""
    if _symbolic(data):
        return _sym_call("ROIAlign", data=data, rois=rois,
                         pooled_size=pooled_size,
                         spatial_scale=spatial_scale,
                         sample_ratio=sample_ratio)
    return _apply(lambda x, r: _raw.roi_align(x, r, pooled_size,
                                              spatial_scale, sample_ratio),
                  [data, _as_nd(rois)], name="ROIAlign")


def BilinearResize2D(data, height=None, width=None):
    """Bilinear resize, align-corners (reference:
    mx.nd.contrib.BilinearResize2D, src/operator/contrib/
    bilinear_resize.cc). Two MXU matrix contractions, no gathers."""
    height, width = _raw.validate_resize_sizes(height, width)
    if _symbolic(data):
        return _sym_call("BilinearResize2D", data=data, height=height,
                         width=width)
    return _apply(lambda x: _raw.bilinear_resize(x, height, width),
                  [data], name="BilinearResize2D")


def AdaptiveAvgPooling2D(data, output_size=1):
    """Adaptive average pooling (reference:
    mx.nd.contrib.AdaptiveAvgPooling2D)."""
    if _symbolic(data):
        return _sym_call("AdaptiveAvgPooling2D", data=data,
                         output_size=output_size)
    return _apply(lambda x: _raw.adaptive_avg_pool(x, output_size),
                  [data], name="AdaptiveAvgPooling2D")


def im2col(data, kernel, stride=None, dilate=None, pad=None):
    """Patch unfolding (reference: mx.nd.im2col)."""
    return _apply(lambda x: _raw.im2col(x, kernel, stride, dilate, pad),
                  [data], name="im2col")


def SliceChannel(data, num_outputs, axis=1, squeeze_axis=False):
    """Parity alias: mx.nd.SliceChannel == split."""
    from .. import ndarray as nd
    return nd.split(data, num_outputs, axis=axis, squeeze_axis=squeeze_axis)


def multihead_attention(q, k, v, num_heads, mask=None, dropout_rate=0.0,
                        scale=None, causal=False, num_kv_heads=None,
                        window=None):
    """Attention on projected (B, L, heads * head_dim) tensors; `num_kv_heads`
    gives k and v fewer heads than q (query head h reads key/value head
    h // group), `window` (with `causal`) the keys a row sees up to its own.
    Causal, window and grouped heads stay in the flash kernel
    (ops/_raw.py `multihead_attention`, ops/select.py)."""
    if _symbolic(q):
        if num_kv_heads is not None or window is not None:
            raise NotImplementedError(
                "symbol trace of multihead_attention has no num_kv_heads= "
                "or window=")
        if dropout_rate and dropout_rate > 0.0:
            import warnings
            warnings.warn(
                "symbol trace of multihead_attention drops attention-"
                "weight dropout (the reference's symbol attention ops "
                "carry none either); residual/FFN Dropout nodes still "
                "honor is_train", stacklevel=3)
        return _sym_call("multihead_attention", queries=q, keys=k, values=v,
                         num_heads=num_heads, mask=mask, scale=scale,
                         causal=causal)
    training = autograd.is_training()
    key = ndrandom._key() if (dropout_rate > 0.0 and training) else None
    inputs = [q, k, v] + ([mask] if mask is not None else [])

    def f(qq, kk, vv, *rest):
        m = rest[0] if rest else None
        return _raw.multihead_attention(qq, kk, vv, num_heads, m, dropout_rate,
                                        key, training, scale, causal,
                                        num_kv_heads, window)
    return _apply(f, inputs, name="multihead_attention")


def latent_attention(q_n, q_r, kv, k_r, num_heads):
    """Causal attention of a latent layer on its parts as the products
    wrote them (ops/_raw.py `latent_attention`): q_n (B, L, H dn), q_r (B,
    L, H dr), kv (B, L, H (dn + dv)) with head h's key part and values side
    by side, and k_r (B, L, dr), which every head shares; returns (B, L, H
    dv)."""
    return _apply(lambda *a: _raw.latent_attention(*a, num_heads),
                  [q_n, q_r, kv, k_r], name="latent_attention")


def RMSNorm(data, gamma, eps=1e-6):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, float32
    statistic (ops/_raw.py `rms_norm`)."""
    return _apply(lambda x, g: _raw.rms_norm(x, g, eps), [data, gamma],
                  name="RMSNorm")


def rope(data, inv_freq, num_heads, factor=1.0, rotary_dim=None,
         interleaved=False):
    """Rotary positions on (B, L, heads * head_dim); `inv_freq` and `factor`
    as `ops._raw.rope_frequencies` gives them; `rotary_dim`: the leading
    channels of every head that are rotated (default: all of them);
    `interleaved`: adjacent channels turn together (whole heads)."""
    return _apply(lambda x: _raw.rope(x, inv_freq, num_heads, factor,
                                      rotary_dim, interleaved),
                  [data], name="rope")


def compressed_attention(q, k, v, conv0, conv1, temp, inv_freq, num_heads,
                         num_kv_heads, rotary_dim=None, factor=1.0):
    """`ops._raw.compressed_attention`: between a CCA mixer's down
    projections and its output projection, the value shift, the two causal
    convolutions, the q-k mean, the unit norms, rotary positions on part
    of a head and causal attention over grouped heads."""
    def f(*arrays):
        return _raw.compressed_attention(*arrays, inv_freq, num_heads,
                                         num_kv_heads, rotary_dim, factor)
    return _apply(f, [q, k, v, conv0, conv1, temp],
                  name="compressed_attention")


def router_mlp(data, previous, down, gamma, w1, w2, w3):
    """(logits, state) of `ops._raw.router_mlp`: a router that is a down
    projection, an average with the `previous` layer's state (None, with
    `gamma` None, for a model's first layer) and three products with GELU
    between; `sparse_experts(logits=)` routes by the logits."""
    if previous is None:
        return _apply(lambda x, d, *w: _raw.router_mlp(x, None, d, None, *w),
                      [data, down, w1, w2, w3], n_out=2, name="router_mlp")
    return _apply(_raw.router_mlp, [data, previous, down, gamma, w1, w2, w3],
                  n_out=2, name="router_mlp")


def sparse_experts(data, router, gate, up, down, top_k, first=0,
                   norm_topk_prob=True, scoring="softmax", bias=None,
                   scale=1.0, logits=None):
    """(y, load) of `ops._raw.sparse_experts`: the held experts' part of a
    dropless top-k expert layer, and the assignments each expert got. The
    router is one matrix `router`, or None beside the `logits` of one
    computed outside (`router_mlp`)."""
    given = {"x": data, "router": router, "gate": gate, "up": up,
             "down": down, "bias": bias, "logits": logits}
    names = [name for name, array in given.items() if array is not None]

    def f(*arrays):
        a = dict(zip(names, arrays))
        y, load = _raw.sparse_experts(
            a["x"], a.get("router"), a["gate"], a["up"], a["down"], top_k,
            first, norm_topk_prob, scoring, a.get("bias"), scale,
            a.get("logits"))
        # the tape wants a cotangent of every output's own dtype
        return y, load.astype(jnp.float32)
    y, load = _apply(f, [given[name] for name in names], n_out=2,
                     name="sparse_experts")
    return y, NDArray(load._data.astype(jnp.int32))


def gated_ffn(data, gate, up, down):
    """(silu(x gate) * (x up)) down (ops/_raw.py `gated_ffn`)."""
    return _apply(_raw.gated_ffn, [data, gate, up, down], name="gated_ffn")


def linear_attention(data, weights, num_heads, eps=1e-5):
    """(out, lowest cumulated log-decay) of `ops._raw.linear_attention`:
    Kimi Delta Attention (projections, short convolutions, the chunked
    gated delta rule, the gated output norm) on a block's input (B, L, D);
    `weights` in that function's order, Wq to gamma."""
    def f(*arrays):
        return _raw.linear_attention(*arrays, num_heads, eps)
    return _apply(f, [data, *weights], n_out=2, name="linear_attention")


def SequenceMask(data, sequence_length=None, use_sequence_length=False,
                 value=0.0, axis=0):
    """Parity: mx.nd.SequenceMask (src/operator/sequence_mask.cc)."""
    if sequence_length is None:
        return _apply(lambda x: _raw.sequence_mask(x, None, False, value,
                                                   axis),
                      [data], name="SequenceMask")
    sequence_length = _as_nd(sequence_length)
    return _apply(lambda x, ln: _raw.sequence_mask(x, ln,
                                                   use_sequence_length,
                                                   value, axis),
                  [data, sequence_length], name="SequenceMask")


def SequenceLast(data, sequence_length=None, use_sequence_length=False,
                 axis=0):
    """Parity: mx.nd.SequenceLast (src/operator/sequence_last.cc)."""
    if sequence_length is None:
        return _apply(lambda x: _raw.sequence_last(x, None, False, axis),
                      [data], name="SequenceLast")
    sequence_length = _as_nd(sequence_length)
    return _apply(lambda x, ln: _raw.sequence_last(x, ln,
                                                   use_sequence_length, axis),
                  [data, sequence_length], name="SequenceLast")


def SequenceReverse(data, sequence_length=None, use_sequence_length=False,
                    axis=0):
    """Parity: mx.nd.SequenceReverse (src/operator/sequence_reverse.cc)."""
    if sequence_length is None:
        return _apply(lambda x: _raw.sequence_reverse(x, None, False, axis),
                      [data], name="SequenceReverse")
    sequence_length = _as_nd(sequence_length)
    return _apply(lambda x, ln: _raw.sequence_reverse(
        x, ln, use_sequence_length, axis),
        [data, sequence_length], name="SequenceReverse")


def GridGenerator(data, transform_type="affine", target_shape=None):
    """Parity: mx.nd.GridGenerator (src/operator/grid_generator.cc)."""
    return _apply(lambda d: _raw.grid_generator(d, transform_type,
                                                target_shape),
                  [data], name="GridGenerator")


def BilinearSampler(data, grid):
    """Parity: mx.nd.BilinearSampler (src/operator/bilinear_sampler.cc)."""
    return _apply(_raw.bilinear_sampler, [data, grid],
                  name="BilinearSampler")


def SpatialTransformer(data, loc, target_shape=None,
                       transform_type="affine", sampler_type="bilinear"):
    """Parity: mx.nd.SpatialTransformer (src/operator/spatial_transformer.cc)
    = GridGenerator(loc) + BilinearSampler, fused in one recorded op."""
    if sampler_type != "bilinear":
        raise ValueError("only bilinear sampler_type is supported")

    def f(x, theta):
        grid = _raw.grid_generator(theta, transform_type, target_shape)
        return _raw.bilinear_sampler(x, grid)

    return _apply(f, [data, loc], name="SpatialTransformer")


def Correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """Parity: mx.nd.Correlation (src/operator/correlation.cc, FlowNet)."""
    return _apply(lambda a, b: _raw.correlation(
        a, b, kernel_size, max_displacement, stride1, stride2, pad_size,
        is_multiply),
        [data1, data2], name="Correlation")


def _as_nd_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


from ..base import make_loop_caller as _make_loop_caller  # noqa: E402


def foreach(body, data, init_states):
    """Parity: mx.nd.contrib.foreach (src/operator/control_flow.cc).
    body(data_slice, states) -> (outputs, new_states); iterates over axis 0
    of `data`.

    Two execution modes, matching the reference's imperative semantics:
    while `autograd.record()` is active the loop runs eagerly step by step
    (the tape sees every op, so gradients flow to closure variables too);
    otherwise it lowers to ONE compiled lax.scan. Under hybridize/jit
    tracing the eager path simply unrolls into the trace."""
    from .. import ndarray as nd
    data_list = _as_nd_list(data)
    if not data_list:
        raise ValueError("foreach requires non-empty `data`")
    states_list = _as_nd_list(init_states)
    n_data = len(data_list)
    single_data = not isinstance(data, (list, tuple))
    single_states = not isinstance(init_states, (list, tuple))
    T = data_list[0].shape[0]

    if autograd.is_recording():
        states = init_states
        outs_acc = None
        single_out = True
        for t in range(T):
            xs = [d[t] for d in data_list]
            outs, states = body(xs[0] if single_data else xs, states)
            single_out = not isinstance(outs, (list, tuple))
            outs = _as_nd_list(outs)
            if outs_acc is None:
                outs_acc = [[] for _ in outs]
            for acc, o in zip(outs_acc, outs):
                acc.append(o)
        stacked = [nd.stack(*acc, axis=0) for acc in (outs_acc or [])]
        return (stacked[0] if single_out and stacked else stacked, states)

    import jax.lax as _lax
    meta = {}

    def fn(*raws):
        d_raws, s_raws = raws[:n_data], raws[n_data:]

        def step(carry, xs):
            s_nd = [NDArray(c) for c in carry]
            x_nd = [NDArray(x) for x in xs]
            outs, new_s = body(x_nd[0] if single_data else x_nd,
                               s_nd[0] if single_states else s_nd)
            meta["single_out"] = not isinstance(outs, (list, tuple))
            outs = _as_nd_list(outs)
            new_s = _as_nd_list(new_s)
            meta["n_out"] = len(outs)
            return (tuple(o._data for o in new_s),
                    tuple(o._data for o in outs))

        final, stacked = _lax.scan(step, tuple(s_raws), tuple(d_raws))
        return tuple(stacked) + tuple(final)

    all_in = data_list + states_list
    # probe ONE step (not the whole scan) just to learn the output count
    carry_avals = tuple(jax.ShapeDtypeStruct(s.shape, s._data.dtype)
                        for s in states_list)
    slice_avals = tuple(jax.ShapeDtypeStruct(d.shape[1:], d._data.dtype)
                        for d in data_list)

    def _one_step(c, xs):
        s_nd = [NDArray(r) for r in c]
        x_nd = [NDArray(r) for r in xs]
        outs, new_s = body(x_nd[0] if single_data else x_nd,
                           s_nd[0] if single_states else s_nd)
        meta["single_out"] = not isinstance(outs, (list, tuple))
        meta["n_out"] = len(_as_nd_list(outs))
        return tuple(o._data for o in _as_nd_list(new_s))

    jax.eval_shape(_one_step, carry_avals, slice_avals)
    n_out = meta["n_out"]
    res = _apply(fn, all_in, n_out=n_out + len(states_list), name="foreach")
    res = _as_nd_list(res)
    out_part = res[:n_out]
    state_part = res[n_out:]
    return (out_part[0] if meta["single_out"] else out_part,
            state_part[0] if single_states and len(state_part) == 1
            else state_part)


def while_loop(cond, func, loop_vars, max_iterations):
    """Parity: mx.nd.contrib.while_loop. func(loop_vars) ->
    (step_output, new_loop_vars); runs while cond(loop_vars) is true, at
    most max_iterations steps. Outputs are stacked padded to
    max_iterations (reference shape semantics).

    Calling convention: with multiple loop vars both the reference style
    `def func(a, b)` (called func(*loop_vars)) and this repo's list style
    `def func(vs)` are supported — the signature decides
    (base.make_loop_caller).

    Eager Python loop while recording (tape/closure gradients exact);
    otherwise a cond-gated lax.scan of static length — XLA-compilable AND
    reverse-mode differentiable (a raw while_loop is not). NOTE (matches
    the reference's imperative behavior): in recording mode a loop whose
    condition is false on entry returns an empty outputs list — output
    shapes are unknowable without running the body."""
    from .. import ndarray as nd
    lv = _as_nd_list(loop_vars)
    single = not isinstance(loop_vars, (list, tuple))
    n_lv = len(lv)
    call_cond = _make_loop_caller(cond, n_lv, single)
    call_func = _make_loop_caller(func, n_lv, single)

    if autograd.is_recording():
        cur = loop_vars
        outs_acc = None
        n_steps = 0
        while n_steps < max_iterations:
            pred = call_cond([cur] if single else _as_nd_list(cur))
            if not bool(np.asarray(pred._data if isinstance(pred, NDArray)
                                   else pred)):
                break
            outs, cur = call_func([cur] if single else _as_nd_list(cur))
            outs = _as_nd_list(outs)
            if outs_acc is None:
                outs_acc = [[] for _ in outs]
            for acc, o in zip(outs_acc, outs):
                acc.append(o)
            n_steps += 1
        stacked = []
        for acc in (outs_acc or []):
            pad = [nd.zeros_like(acc[0])] * (max_iterations - len(acc))
            stacked.append(nd.stack(*(acc + pad), axis=0))
        return stacked, cur

    import jax.lax as _lax
    meta = {}

    def fn(*raws):
        def step(carry, _):
            vars_raw, active = carry
            v_nd = [NDArray(r) for r in vars_raw]
            pred = call_cond(v_nd)
            pred_raw = pred._data if isinstance(pred, NDArray) else pred
            go = jnp.logical_and(
                active, jnp.asarray(pred_raw).astype(bool).reshape(()))
            outs, new_vars = call_func(v_nd)
            outs = _as_nd_list(outs)
            new_vars = _as_nd_list(new_vars)
            meta["n_out"] = len(outs)
            kept = tuple(jnp.where(go, nv._data, ov)
                         for nv, ov in zip(new_vars, vars_raw))
            out_raw = tuple(jnp.where(go, o._data,
                                      jnp.zeros_like(o._data))
                            for o in outs)
            return (kept, go), out_raw

        (final, _), stacked = _lax.scan(
            step, (tuple(raws), jnp.bool_(True)), None,
            length=max_iterations)
        return tuple(stacked) + tuple(final)

    def _one_step(raws):
        v_nd = [NDArray(r) for r in raws]
        outs, new_vars = call_func(v_nd)
        meta["n_out"] = len(_as_nd_list(outs))
        return tuple(o._data for o in _as_nd_list(new_vars))

    jax.eval_shape(_one_step,
                   tuple(jax.ShapeDtypeStruct(v.shape, v._data.dtype)
                         for v in lv))
    n_out = meta["n_out"]
    res = _as_nd_list(_apply(fn, lv, n_out=n_out + n_lv,
                             name="while_loop"))
    out_part = res[:n_out]
    var_part = res[n_out:n_out + n_lv]
    return (out_part, var_part[0] if single and n_lv == 1 else var_part)


def cond(pred, then_func, else_func, inputs):
    """Parity: mx.nd.contrib.cond. On a concrete predicate (eager mode) the
    chosen branch runs directly — tape gradients exact, branches need not
    match shapes. On a traced predicate both branches compile into
    lax.cond and XLA picks at runtime (shapes must match)."""
    import jax.lax as _lax
    ins = _as_nd_list(inputs)
    single = not isinstance(inputs, (list, tuple))
    pred_nd = pred if isinstance(pred, NDArray) else _as_nd(pred)

    if not _is_tracer(pred_nd._data):
        branch = then_func if bool(np.asarray(pred_nd._data)) else else_func
        return branch(inputs)

    def fn(p, *raws):
        def wrap(f):
            def g(rs):
                nds = [NDArray(r) for r in rs]
                out = f(nds[0] if single else nds)
                return tuple(o._data for o in _as_nd_list(out))
            return g
        outs = _lax.cond(p.astype(bool).reshape(()), wrap(then_func),
                         wrap(else_func), tuple(raws))
        return outs if len(outs) > 1 else outs[0]

    probe = jax.eval_shape(fn, pred_nd._data, *[x._data for x in ins])
    n_out = len(probe) if isinstance(probe, tuple) else 1
    res = _as_nd_list(_apply(fn, [pred_nd] + ins, n_out=n_out, name="cond"))
    return res[0] if len(res) == 1 else res


def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    """Parity: mx.nd.contrib.arange_like — arange sized by `data`'s shape
    (whole array flattened-shape when axis is None, else that axis); with
    repeat=r, r consecutive elements share a value, total size unchanged."""
    if _symbolic(data):
        return _sym_call("arange_like", data=data, start=start, step=step,
                         repeat=repeat, axis=axis)
    def f(x):
        n = x.shape[axis] if axis is not None else int(np.prod(x.shape))
        if n % repeat:
            raise ValueError(
                f"arange_like: size {n} not divisible by repeat {repeat}")
        # exact length: index arithmetic, never float-endpoint arange
        r = start + step * jnp.arange(n // repeat, dtype=jnp.float32)
        if repeat > 1:
            r = jnp.repeat(r, repeat)
        r = r.astype(x.dtype)
        return r.reshape(x.shape) if axis is None else r
    return _apply(f, [data], name="arange_like")


def fft(data, compute_size=128):
    """Parity: mx.nd.contrib.fft (src/operator/contrib/fft.cc): real input
    (..., d) -> packed complex output (..., 2d), interleaved re/im."""
    def f(x):
        c = jnp.fft.fft(x.astype(jnp.float32), axis=-1)
        out = jnp.stack([c.real, c.imag], axis=-1)
        return out.reshape(x.shape[:-1] + (2 * x.shape[-1],)).astype(x.dtype)
    return _apply(f, [data], name="fft")


def ifft(data, compute_size=128):
    """Parity: mx.nd.contrib.ifft — input packed (..., 2d) interleaved
    re/im, output real (..., d). Matches the reference's UNNORMALIZED
    inverse: ifft(fft(x)) == d * x."""
    def f(x):
        d = x.shape[-1] // 2
        z = x.astype(jnp.float32).reshape(x.shape[:-1] + (d, 2))
        c = z[..., 0] + 1j * z[..., 1]
        return (jnp.fft.ifft(c, axis=-1).real * d).astype(x.dtype)
    return _apply(f, [data], name="ifft")


# Mirror the op namespace onto mx.nd for reference-style calls, and expose
# the box/SSD family under mx.nd.contrib.* like the reference.
def _mirror_into_nd():
    import sys
    import types
    nd_mod = sys.modules["incubator_mxnet_tpu.ndarray"]
    for name in __all__:
        setattr(nd_mod, name, globals()[name])
    contrib = types.ModuleType("incubator_mxnet_tpu.ndarray.contrib")
    for name in ["box_iou", "box_nms", "MultiBoxPrior", "MultiBoxTarget",
                 "MultiBoxDetection", "multihead_attention",
                 "foreach", "while_loop", "cond",
                 "arange_like", "fft", "ifft",
                 "ROIAlign", "BilinearResize2D", "AdaptiveAvgPooling2D"]:
        setattr(contrib, name, globals()[name])

    def _contrib_getattr(name):
        # quantization ops live with contrib.quantization (which imports
        # gluon, loaded after ops) — resolve lazily, PEP 562 style
        if name in ("quantize", "dequantize", "quantize_v2"):
            from ..contrib import quantization as _q
            return getattr(_q, name)
        raise AttributeError(
            f"module 'incubator_mxnet_tpu.ndarray.contrib' has no "
            f"attribute {name!r}")

    contrib.__getattr__ = _contrib_getattr
    nd_mod.contrib = contrib
    sys.modules["incubator_mxnet_tpu.ndarray.contrib"] = contrib


_mirror_into_nd()

