"""gluon.nn layers (parity: python/mxnet/gluon/nn/{basic_layers,conv_layers}.py).

Every layer is a HybridBlock whose forward runs through the recordable op
funnel, so the same code serves eager, taped, and jit-compiled execution.
Conv/pool accept `layout=` with NCHW default (API parity) — pass NHWC for the
TPU-preferred channels-last path (model zoo does this on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ... import autograd  # noqa: F401 (re-export convenience)
from ...ndarray import NDArray, _apply
from ... import ndarray as nd
from ... import ops
from ...ops import _raw
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Flatten",
           "Activation", "LeakyReLU", "PReLU", "ELU", "SELU", "GELU", "Swish",
           "SiLU", "Embedding", "BatchNorm", "BatchNormReLU", "LayerNorm", "InstanceNorm",
           "GroupNorm", "RMSNorm", "GatedFFN", "SparseExperts", "Conv1D", "Conv2D", "Conv3D", "Conv1DTranspose",
           "Conv2DTranspose", "Conv3DTranspose", "MaxPool1D", "MaxPool2D",
           "MaxPool3D", "AvgPool1D", "AvgPool2D", "AvgPool3D",
           "GlobalMaxPool1D", "GlobalMaxPool2D", "GlobalMaxPool3D",
           "GlobalAvgPool1D", "GlobalAvgPool2D", "GlobalAvgPool3D",
           "Lambda", "HybridLambda", "Identity", "Concatenate",
           "ReflectionPad2D"]


def _pair(x, n):
    if isinstance(x, (tuple, list)):
        assert len(x) == n
        return tuple(int(v) for v in x)
    return (int(x),) * n


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------

class Sequential(Block):
    def __init__(self, *blocks, prefix=None, params=None):
        super().__init__(prefix, params)
        for b in blocks:
            self.add(b)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)

    def forward(self, x, *args):
        for child in self._children.values():
            x = child(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, idx):
        vals = list(self._children.values())
        if isinstance(idx, slice):
            out = type(self)()
            out.add(*vals[idx])
            return out
        return vals[idx]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(Sequential, HybridBlock):
    def __init__(self, *blocks, prefix=None, params=None):
        HybridBlock.__init__(self, prefix, params)
        for b in blocks:
            self.add(b)


# ---------------------------------------------------------------------------
# basic layers
# ---------------------------------------------------------------------------

class Dense(HybridBlock):
    """FullyConnected layer; weight (units, in_units) like the reference."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._units = units
        self._flatten = flatten
        self.act = activation
        self.weight = self.params.get("weight", shape=(units, in_units),
                                      dtype=dtype, init=weight_initializer)
        self.bias = (self.params.get("bias", shape=(units,), dtype=dtype,
                                     init=bias_initializer) if use_bias else None)

    def infer_shape(self, x):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def forward(self, x):
        out = ops.FullyConnected(x, self.weight.data(),
                                 None if self.bias is None else self.bias.data(),
                                 flatten=self._flatten)
        if self.act:
            out = ops.Activation(out, self.act)
        return out


class Activation(HybridBlock):
    def __init__(self, activation, prefix=None, params=None):
        super().__init__(prefix, params)
        self._act = activation

    def forward(self, x):
        return ops.Activation(x, self._act)


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), prefix=None, params=None):
        super().__init__(prefix, params)
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        return ops.Dropout(x, p=self._rate, axes=self._axes)


class Flatten(HybridBlock):
    def forward(self, x):
        return x.flatten()


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Lambda(Block):
    def __init__(self, function, prefix=None):
        super().__init__(prefix)
        self._fn = function if callable(function) else getattr(nd, function)

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock, Lambda):
    def __init__(self, function, prefix=None):
        HybridBlock.__init__(self, prefix)
        self._fn = function if callable(function) else getattr(nd, function)

    def forward(self, *args):
        return self._fn(*args)


class Concatenate(HybridSequential):
    """Run children on the same input, concat outputs along `axis`."""

    def __init__(self, axis=-1, prefix=None):
        super().__init__(prefix=prefix)
        self._axis = axis

    def forward(self, x):
        return nd.concat(*[child(x) for child in self._children.values()],
                         dim=self._axis)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, prefix=None, params=None):
        super().__init__(prefix, params)
        self._alpha = alpha

    def forward(self, x):
        return nd.leaky_relu(x, self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, in_channels=1, prefix=None, params=None):
        super().__init__(prefix, params)
        from ... import initializer as init_mod
        self.alpha = self.params.get(
            "alpha", shape=(in_channels,),
            init=alpha_initializer or init_mod.Constant(0.25))

    def forward(self, x):
        a = self.alpha.data()
        return _apply(lambda xr, ar: jnp.where(xr >= 0, xr, ar * xr),
                      [x, a], name="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._alpha = alpha

    def forward(self, x):
        return nd.elu(x, self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return nd.selu(x)


class GELU(HybridBlock):
    def __init__(self, approximation="erf", prefix=None, params=None):
        super().__init__(prefix, params)
        self._approx = approximation != "erf"

    def forward(self, x):
        return nd.gelu(x, approximate=self._approx)


class Swish(HybridBlock):
    def forward(self, x):
        return nd.silu(x)


SiLU = Swish


class Embedding(HybridBlock):
    """Index handling follows the embedding subsystem's shared policy
    (embedding/lookup.normalize_ids): ids are rounded to int32 and
    `oor_policy` ('clip' or 'error') pins the out-of-range behavior that
    used to be backend-dependent (docs/embedding.md)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False,
                 oor_policy="clip", prefix=None, params=None):
        super().__init__(prefix, params)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        self._oor_policy = oor_policy
        self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                      dtype=dtype, init=weight_initializer)

    def forward(self, x):
        return nd.embedding(x, self.weight.data(), input_dim=self._input_dim,
                            sparse_grad=self._sparse_grad,
                            oor_policy=self._oor_policy)


# ---------------------------------------------------------------------------
# normalization layers
# ---------------------------------------------------------------------------

class BatchNorm(HybridBlock):
    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer,
                                    grad_req="write" if center else "null")
        self.running_mean = self.params.get("running_mean", shape=(in_channels,),
                                            init=running_mean_initializer,
                                            grad_req="null")
        self.running_var = self.params.get("running_var", shape=(in_channels,),
                                           init=running_variance_initializer,
                                           grad_req="null")

    def infer_shape(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def forward(self, x):
        return self._forward_impl(x, act=None)

    def _forward_impl(self, x, act=None):
        from ..symbolize import is_symbol
        if is_symbol(x):  # symbol trace (gluon/symbolize.py)
            from ..symbolize import sym_call
            out = sym_call(
                "BatchNorm", out_index=0, data=x, gamma=self.gamma.data(),
                beta=self.beta.data(), moving_mean=self.running_mean.data(),
                moving_var=self.running_var.data(), axis=self._axis,
                eps=self._eps, momentum=self._momentum,
                fix_gamma=not self._scale,
                use_global_stats=self._use_global_stats)
            return out.relu() if act == "relu" else out
        training = autograd.is_training() and not self._use_global_stats
        axis, eps, mom = self._axis, self._eps, self._momentum
        fix_gamma = not self._scale

        def f(xr, gr, br, mmr, mvr):
            return _raw.batch_norm(xr, gr, br, mmr, mvr, axis=axis, eps=eps,
                                   momentum=mom, training=training,
                                   use_global_stats=self._use_global_stats,
                                   fix_gamma=fix_gamma, act=act)

        y, nm, nv = _apply(f, [x, self.gamma.data(), self.beta.data(),
                               self.running_mean.data(), self.running_var.data()],
                           n_out=3, name="BatchNorm" if act is None
                           else "BatchNorm" + act.upper())
        if training:
            self.running_mean.update_aux(nm._data)
            self.running_var.update_aux(nv._data)
        return y


class BatchNormReLU(BatchNorm):
    """BatchNorm with a fused trailing ReLU (parity:
    gluon.nn.BatchNormReLU / the reference's fused CUDNN_BATCHNORM_OPS
    path). The normalize+affine+relu tail routes through the kernel-
    selection layer (ops/select.py): on qualifying channels-last shapes
    it runs as ONE pallas HBM pass (scale_shift_act — the stats
    reduction stays XLA in training mode); elsewhere XLA fuses the relu
    into the normalization chain, numerics unchanged."""

    def forward(self, x):
        return self._forward_impl(x, act="relu")


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._axis = axis
        self._eps = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def forward(self, x):
        return ops.LayerNorm(x, self.gamma.data(), self.beta.data(),
                             axis=self._axis, eps=self._eps)


class RMSNorm(HybridBlock):
    """x / sqrt(mean(x^2) + epsilon) * gamma over the last axis, the
    statistic in float32 (ops/_raw.py `rms_norm`)."""

    def __init__(self, epsilon=1e-6, gamma_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._eps = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer)

    def infer_shape(self, x):
        self.gamma.shape = (x.shape[-1],)

    def forward(self, x):
        return ops.RMSNorm(x, self.gamma.data(), eps=self._eps)


class GatedFFN(HybridBlock):
    """(silu(x gate) * (x up)) down, `units` -> `hidden_size` -> `units`, no
    bias (ops/_raw.py `gated_ffn`): a decoder's dense feed-forward, or the
    shared expert of a sparse layer."""

    def __init__(self, units, hidden_size, weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        get = self.params.get
        self.gate = get("gate", shape=(units, hidden_size),
                        init=weight_initializer)
        self.up = get("up", shape=(units, hidden_size),
                      init=weight_initializer)
        self.down = get("down", shape=(hidden_size, units),
                        init=weight_initializer)

    def forward(self, x):
        return ops.gated_ffn(x, self.gate.data(), self.up.data(),
                             self.down.data())


class SparseExperts(HybridBlock):
    """Sparse-expert feed-forward layer, as ONE holder of an expert-parallel
    deployment runs it: the router scores all `num_experts`, every token
    takes its `top_k`, and this block computes the part of the result that
    the experts it holds give, `held=(first, count)` (default: all of them).
    Dropless: no assignment to a held expert is lost, whatever the routing
    (ops/_raw.py `sparse_experts`). Expert e is (silu(x gate_e) * (x up_e))
    down_e, `units` -> `hidden_size` -> `units`, no bias.

    `scoring="sigmoid"`, `selection_bias=True` (a parameter `bias`, one
    float32 an expert, zeros, `grad_req="null"`: it moves the choice and
    not the weights, and whoever balances the load writes it) and
    `scale` are the router of the families that score experts one by one;
    `shared_hidden_size` adds a `GatedFFN` that every token passes, whole on
    every holder, under the op scope `moe/shared`.

    `router_hidden_size` makes the router an MLP of that width in place of
    the one matrix `router` (ops/_raw.py `router_mlp`): `router_down`, then
    `router_w1`, `router_w2` and `router_w3` with GELU between. Such a
    router has a state, its down projection, that travels from layer to
    layer: forward(x, state) takes the layer before's (None for a model's
    first) and returns (y, its own). `previous` says that a layer with
    such a router comes before this one (a model works it out, it is not a
    choice): the layer then owns `router_gamma` (zeros) and adds gamma x
    the state it is given to its own. With `norm_topk_prob=False` and one expert a token the weight is
    the chosen expert's probability.

    `load` (num_experts int32, `grad_req="null"`) holds the assignments each
    expert got in the last training step; it is updated as BatchNorm's
    running statistics are, keeps its type under `cast`, and `read_load()`
    puts it on the profiler's counters (`moe.live_rows`,
    `moe.load_max_over_mean`, and `moe.row_capacity`: the rows of the
    buffers that step walked, a quarter over the even share while the
    routing is near its balance, tokens x top_k when it is not)."""

    def __init__(self, units, hidden_size, num_experts, top_k, held=None,
                 norm_topk_prob=True, weight_initializer=None,
                 scoring="softmax", selection_bias=False, scale=1.0,
                 shared_hidden_size=None, router_hidden_size=None,
                 previous=False, prefix=None, params=None):
        super().__init__(prefix, params)
        first, count = held if held is not None else (0, num_experts)
        if not 0 <= first <= first + count <= num_experts or count < 1:
            raise ValueError(f"held={held!r} of {num_experts} experts")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={scoring!r}")
        self._top_k = top_k
        self._first = first
        self._norm = norm_topk_prob
        self._scoring = scoring
        self._scale = scale
        get = self.params.get
        self.router_gamma = None
        if router_hidden_size is None:
            self.router = get("router", shape=(num_experts, units),
                              init=weight_initializer)
            self._router = [self.router]
        else:
            wide = router_hidden_size
            self._router = [
                get("router_" + name, shape=shape, init=weight_initializer)
                for name, shape in (("down", (wide, units)),
                                    ("w1", (wide, wide)), ("w2", (wide, wide)),
                                    ("w3", (num_experts, wide)))]
            if previous:
                self.router_gamma = get("router_gamma", shape=(wide,),
                                        init="zeros")
        self._mlp = router_hidden_size is not None
        self.gate = get("gate", shape=(count, units, hidden_size),
                        init=weight_initializer)
        self.up = get("up", shape=(count, units, hidden_size),
                      init=weight_initializer)
        self.down = get("down", shape=(count, hidden_size, units),
                        init=weight_initializer)
        self.load = get("load", shape=(num_experts,), dtype="int32",
                        init="zeros", grad_req="null")
        self.bias = (get("bias", shape=(num_experts,), init="zeros",
                         grad_req="null") if selection_bias else None)
        self.shared = (GatedFFN(units, shared_hidden_size, weight_initializer)
                       if shared_hidden_size else None)

    def cast(self, dtype):
        # `load` counts: it stays int32 (bfloat16 cannot count past 256);
        # `bias` is added to float32 scores and stays float32
        for p in (*self._router, self.router_gamma, self.gate, self.up,
                  self.down):
            if p is not None:
                p.cast(dtype)
        if self.shared is not None:
            self.shared.cast(dtype)
        self._dtype = dtype

    def forward(self, x, state=None):
        router = logits = None
        if self._mlp:
            down, *mlp = (p.data() for p in self._router)
            average = self.router_gamma is not None and state is not None
            logits, state = ops.router_mlp(
                x, state if average else None, down,
                self.router_gamma.data() if average else None, *mlp)
        else:
            router = self.router.data()
        y, load = ops.sparse_experts(
            x, router, self.gate.data(), self.up.data(),
            self.down.data(), self._top_k, self._first, self._norm,
            self._scoring, None if self.bias is None else self.bias.data(),
            self._scale, logits)
        if autograd.is_training():
            self.load.update_aux(load._data)
        if self.shared is not None:
            with jax.named_scope("moe"), jax.named_scope("shared"):
                y = y + self.shared(x)
        return (y, state) if self._mlp else y

    def read_load(self):
        """{live_rows, load_max_over_mean, row_capacity} of the last training
        step: the assignments to the experts held here, the largest expert's
        load over the mean, and the rows of the buffers that step walked
        (the rung of `ops._raw.row_capacities` that held the live rows; the
        host applies the function the program traced). Read from `load` (a
        device-to-host copy) and set as the counters `moe.live_rows`,
        `moe.load_max_over_mean` and `moe.row_capacity`."""
        from ... import profiler as _prof
        from ...ops import _raw
        load = np.asarray(self.load.data().asnumpy(), np.int64)
        count = self.gate.shape[0]
        live = int(load[self._first:self._first + count].sum())
        # every assignment is counted once: `load` sums to tokens x top_k
        ladder = _raw.row_capacities(int(load.sum()), count, load.size)
        got = {"live_rows": live,
               "load_max_over_mean": float(load.max() / max(load.mean(),
                                                            1e-9)),
               "row_capacity": ladder[_raw.row_capacity(live, ladder)]}
        for name, value in got.items():
            _prof.set_gauge("moe." + name, value)
        return got


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._eps = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x):
        self.gamma.shape = (x.shape[1],)
        self.beta.shape = (x.shape[1],)

    def forward(self, x):
        return ops.InstanceNorm(x, self.gamma.data(), self.beta.data(), eps=self._eps)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix, params)
        self._ng = num_groups
        self._eps = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init=gamma_initializer,
                                     grad_req="write" if scale else "null")
        self.beta = self.params.get("beta", shape=(in_channels,),
                                    init=beta_initializer,
                                    grad_req="write" if center else "null")

    def infer_shape(self, x):
        self.gamma.shape = (x.shape[1],)
        self.beta.shape = (x.shape[1],)

    def forward(self, x):
        return ops.GroupNorm(x, self.gamma.data(), self.beta.data(),
                             num_groups=self._ng, eps=self._eps)


# ---------------------------------------------------------------------------
# convolution layers
# ---------------------------------------------------------------------------

class _Conv(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 op=ops.Convolution, adj=None, prefix=None, params=None):
        super().__init__(prefix, params)
        nsp = len(kernel_size)
        self._channels = channels
        self._in_channels = in_channels
        self._kernel = kernel_size
        self._stride = _pair(strides, nsp)
        self._pad = _pair(padding, nsp)
        self._dilate = _pair(dilation, nsp)
        self._groups = groups
        self._layout = layout
        self._op = op
        self._adj = adj
        self.act = activation
        self.weight = self.params.get("weight",
                                      shape=self._weight_shape(in_channels),
                                      init=weight_initializer)
        self.bias = (self.params.get("bias", shape=(channels,),
                                     init=bias_initializer) if use_bias else None)

    def _weight_shape(self, in_channels):
        k = tuple(self._kernel)
        if self._op is ops.Deconvolution:
            if self._layout.startswith("NC"):
                return (in_channels, self._channels // self._groups) + k
            return k + (self._channels // self._groups, in_channels)
        if self._layout.startswith("NC"):
            return (self._channels, in_channels // self._groups if in_channels else 0) + k
        return k + (in_channels // self._groups if in_channels else 0, self._channels)

    def infer_shape(self, x):
        c_axis = 1 if self._layout.startswith("NC") else x.ndim - 1
        self._in_channels = x.shape[c_axis]
        self.weight.shape = self._weight_shape(self._in_channels)

    def forward(self, x):
        kw = dict(kernel=self._kernel, stride=self._stride, pad=self._pad,
                  dilate=self._dilate, num_group=self._groups,
                  layout=self._layout)
        if self._op is ops.Deconvolution:
            kw.pop("kernel")
            kw["adj"] = self._adj
        out = self._op(x, self.weight.data(),
                       None if self.bias is None else self.bias.data(), **kw)
        if self.act:
            out = ops.Activation(out, self.act)
        return out


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout="NCW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,)
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, **kwargs)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout="NCHW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 2
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, **kwargs)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout="NCDHW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 3
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, **kwargs)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,)
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, op=ops.Deconvolution,
                         adj=_pair(output_padding, 1), **kwargs)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCHW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 2
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, op=ops.Deconvolution,
                         adj=_pair(output_padding, 2), **kwargs)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCDHW", **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 3
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, op=ops.Deconvolution,
                         adj=_pair(output_padding, 3), **kwargs)


# ---------------------------------------------------------------------------
# pooling layers
# ---------------------------------------------------------------------------

class _Pool(HybridBlock):
    def __init__(self, pool_type, pool_size, strides, padding, global_pool,
                 layout, count_include_pad=True, ceil_mode=False,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._type = pool_type
        self._kernel = pool_size
        self._stride = strides
        self._pad = padding
        self._global = global_pool
        self._layout = layout
        self._cip = count_include_pad
        self._ceil = ceil_mode

    def forward(self, x):
        return ops.Pooling(x, pool_type=self._type, kernel=self._kernel,
                           stride=self._stride, pad=self._pad,
                           global_pool=self._global,
                           count_include_pad=self._cip, layout=self._layout,
                           ceil_mode=self._ceil)


def _mkpool(name, ptype, ndim, global_pool):
    default_layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim]

    class P(_Pool):
        def __init__(self, pool_size=2, strides=None, padding=0,
                     layout=default_layout, count_include_pad=True,
                     ceil_mode=False, prefix=None, params=None):
            ks = _pair(pool_size, ndim)
            st = None if strides is None else _pair(strides, ndim)
            pd = _pair(padding, ndim)
            super().__init__(ptype, ks, st, pd, global_pool, layout,
                             count_include_pad, ceil_mode, prefix, params)

    P.__name__ = P.__qualname__ = name
    return P


MaxPool1D = _mkpool("MaxPool1D", "max", 1, False)
MaxPool2D = _mkpool("MaxPool2D", "max", 2, False)
MaxPool3D = _mkpool("MaxPool3D", "max", 3, False)
AvgPool1D = _mkpool("AvgPool1D", "avg", 1, False)
AvgPool2D = _mkpool("AvgPool2D", "avg", 2, False)
AvgPool3D = _mkpool("AvgPool3D", "avg", 3, False)
GlobalMaxPool1D = _mkpool("GlobalMaxPool1D", "max", 1, True)
GlobalMaxPool2D = _mkpool("GlobalMaxPool2D", "max", 2, True)
GlobalMaxPool3D = _mkpool("GlobalMaxPool3D", "max", 3, True)
GlobalAvgPool1D = _mkpool("GlobalAvgPool1D", "avg", 1, True)
GlobalAvgPool2D = _mkpool("GlobalAvgPool2D", "avg", 2, True)
GlobalAvgPool3D = _mkpool("GlobalAvgPool3D", "avg", 3, True)


class ReflectionPad2D(HybridBlock):
    """Reflection padding on H/W of NCHW input (parity:
    gluon.nn.ReflectionPad2D / src/operator/pad.cc mode='reflect').
    padding: int, the reference's 8-tuple NCHW pad_width
    (0, 0, 0, 0, top, bottom, left, right), or — as an extension — a
    4-tuple (left, right, top, bottom)."""

    def __init__(self, padding=0, prefix=None, params=None):
        super().__init__(prefix, params)
        if isinstance(padding, int):
            padding = (padding,) * 4
        elif len(padding) == 8:
            if any(int(p) != 0 for p in padding[:4]):
                raise ValueError(
                    "8-tuple pad_width must not pad N/C axes: leading four "
                    "entries must be 0, got " + repr(padding))
            t, b, l, r = (int(p) for p in padding[4:])
            padding = (l, r, t, b)
        if len(padding) != 4:
            raise ValueError("padding must be an int, an NCHW 8-tuple "
                             "pad_width, or a 4-tuple "
                             "(left, right, top, bottom)")
        self._padding = tuple(int(p) for p in padding)

    def forward(self, x):
        l, r, t, b = self._padding
        return _apply(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (t, b), (l, r)),
                              mode="reflect"),
            [x], name="reflection_pad2d")
