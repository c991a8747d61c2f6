"""Int8 quantized inference (reference workflow:
example/quantization/imagenet_gen_qsym.py + contrib.quantization).

Train LeNet briefly on synthetic MNIST-shaped data, calibrate + quantize
it to int8 (symmetric, per-channel weight scales — the MXU-native form),
and compare fp32 vs int8 predictions and latency shape.

Run:  python examples/quantize_inference.py          (TPU if available)
      JAX_PLATFORMS=cpu python examples/quantize_inference.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.contrib import quantization as q


def main():
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(6, 5, in_channels=1, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 5, in_channels=6, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(),
            gluon.nn.Dense(120, activation="relu"),
            gluon.nn.Dense(84, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(init=mx.init.Xavier())

    rng = np.random.RandomState(0)
    data = rng.rand(512, 1, 28, 28).astype(np.float32)
    labels = rng.randint(0, 10, 512)
    net(nd.array(data[:1]))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    for epoch in range(2):
        tot = 0.0
        for i in range(0, 512, 64):
            with mx.autograd.record():
                loss = L(net(nd.array(data[i:i + 64])),
                         nd.array(labels[i:i + 64]))
            loss.backward()
            trainer.step(64)
            tot += float(loss.mean().asnumpy())
        print(f"epoch {epoch}: loss {tot / 8:.4f}")

    fp32_pred = net(nd.array(data)).asnumpy().argmax(1)

    # calibrate on a held-out slice, quantize in place
    calib = [nd.array(data[i:i + 64]) for i in range(0, 256, 64)]
    qnet = q.quantize_net(net, calib_data=calib)
    int8_pred = qnet(nd.array(data)).asnumpy().argmax(1)
    agree = (int8_pred == fp32_pred).mean()
    print(f"int8 vs fp32 top-1 agreement: {agree:.1%}")

    x = nd.array(data[:64])
    for name, f in (("int8", qnet),):
        f(x).asnumpy()                      # warm
        t0 = time.time()
        for _ in range(10):
            out = f(x)
        np.asarray(out.asnumpy()[:1])       # host fetch = barrier
        print(f"{name}: {64 * 10 / (time.time() - t0):.0f} img/s")
    assert agree >= 0.98


if __name__ == "__main__":
    main()
