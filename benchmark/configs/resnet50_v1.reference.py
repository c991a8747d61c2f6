"""Plain reference of resnet50_v1: the forward pass and loss of the
published network (He et al. 2015, table 1; stride on a block's first 1x1;
BatchNorm on batch statistics, as in training) in straightforward float32
jax.numpy with lax convolutions: no kernel, no fusion.

`params` are the model's parameters as float32 arrays in the order of
`net.collect_params()`: for the stem and then every block (its three
convolutions, then its projection shortcut if it has one) a convolution
weight (H, W, in, out) followed by BatchNorm's gamma, beta, running mean
and running variance (the last two unused here); then the classifier's
weight (classes, 2048) and bias.
"""
import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv_bn(x, params, stride, relu=True):
    weight, gamma, beta, _, _ = (next(params) for _ in range(5))
    pad = weight.shape[0] // 2
    x = lax.conv_general_dilated(
        x, weight, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    x = (x - mean) / jnp.sqrt(var + EPS) * gamma + beta
    return jax.nn.relu(x) if relu else x


def loss(doc, params, images, labels):
    with jax.default_matmul_precision("highest"):
        params = iter(params)
        x = _conv_bn(images.astype(jnp.float32), params,
                     doc["stem"]["stride"])
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, doc["stem"]["pool_stride"],
                               doc["stem"]["pool_stride"], 1),
                              [(0, 0), (1, 1), (1, 1), (0, 0)])
        for blocks, stride in zip(doc["stage_blocks"], doc["stage_strides"]):
            for b in range(blocks):
                s = stride if b == 0 else 1
                y = _conv_bn(x, params, s)
                y = _conv_bn(y, params, 1)
                y = _conv_bn(y, params, 1, relu=False)
                if b == 0:
                    x = _conv_bn(x, params, s, relu=False)
                x = jax.nn.relu(x + y)
        weight, bias = next(params), next(params)
        logits = x.mean((1, 2)) @ weight.T + bias
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, labels[:, None].astype(jnp.int32), -1)
        return -picked.mean()
