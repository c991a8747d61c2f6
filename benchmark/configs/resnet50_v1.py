"""ResNet-50 v1 as the model zoo builds it, through the package's public
API, and the operations one sample needs, from the shapes in
resnet50_v1.json."""
import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.models import get_model


def net(doc, seed):
    mx.random.seed(seed)
    model = get_model(doc["name"], classes=doc["classes"],
                      layout=doc["layout"])
    model.initialize(init=mx.init.Xavier())
    model.cast(doc["dtype"])
    return model


def loss(doc):
    return gluon.loss.SoftmaxCrossEntropyLoss()


def optimizer(doc):
    opt = dict(doc["optimizer"])
    return mx.optimizer.create(opt.pop("name"), **opt)


def batch(doc, traffic, seed):
    """One batch made on the device in one call: images ~N(0,1) in the
    model's type, labels uniform over the classes."""
    n, hw = traffic["batch"], doc["image"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (n, hw, hw, doc["in_channels"]),
                              jnp.dtype(doc["dtype"]))
        y = jax.random.randint(ky, (n,), 0, doc["classes"])
        return x, y.astype(jnp.float32)

    x, y = make(jax.random.PRNGKey(seed))
    return nd.array(x), nd.array(y)


def forward_macs(doc):
    """Multiply-adds of one image's forward pass through the convolutions
    and the classifier, from the published shapes."""
    stem = doc["stem"]
    hw = doc["image"] // stem["stride"]
    macs = hw * hw * stem["kernel"] ** 2 * doc["in_channels"] \
        * stem["channels"]
    hw //= stem["pool_stride"]
    cin = stem["channels"]
    for blocks, cout, stride in zip(doc["stage_blocks"],
                                    doc["stage_channels"],
                                    doc["stage_strides"]):
        mid = cout // doc["bottleneck_ratio"]
        for b in range(blocks):
            if b == 0:
                hw //= stride                 # the first 1x1 carries it
                macs += hw * hw * cin * cout  # the projection shortcut
            macs += hw * hw * (cin * mid + 9 * mid * mid + mid * cout)
            cin = cout
    return macs + cin * doc["classes"]


def flops_per_sample(doc, traffic):
    """Forward and backward: 3 x forward, 2 operations to a multiply-add.
    (bench.py's 3 x 4.09e9 counts a multiply-add of the v1.5 network as
    ONE operation, so its utilisations are half of these.) BatchNorm,
    ReLU, pooling and the optimizer are not counted: they are bytes."""
    return 3 * 2 * forward_macs(doc)
