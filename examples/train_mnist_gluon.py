"""Gluon MNIST training (the reference's image-classification starter,
example/gluon/mnist). Runs on the TPU where there is one;
`JAX_PLATFORMS=cpu` runs it anywhere.

    python examples/train_mnist_gluon.py --epochs 2
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.models import get_model


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--num-examples", type=int, default=4096)
    args = p.parse_args()

    mx.random.seed(0)
    # MNISTIter falls back to a deterministic synthetic set when the idx
    # files are absent (zero-egress pods)
    train = mx.io.MNISTIter(batch_size=args.batch_size, flat=False,
                            num_examples=args.num_examples)

    net = get_model("lenet", classes=10, layout="NCHW")
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        train.reset()
        metric.reset()
        tic = time.time()
        n = 0
        for batch in train:
            x, y = batch.data[0], batch.label[0]
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(args.batch_size)
            metric.update(y, out)
            n += args.batch_size
        name, acc = metric.get()
        print(f"epoch {epoch}: {name}={acc:.4f} "
              f"({n / (time.time() - tic):.0f} img/s)")

    net.save_parameters("/tmp/lenet_mnist.params")
    print("saved /tmp/lenet_mnist.params")


if __name__ == "__main__":
    main()
