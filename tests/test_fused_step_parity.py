"""The fused step against the eager step, optimizer by optimizer.

``FusedTrainStep.__call__`` (forward, backward and update in ONE jitted
program: the path every cell of the benchmark times) is held here to the
eager path (``autograd.record`` -> ``backward`` -> ``Trainer.step``) on
one small MLP with a BatchNorm: three steps from the same weights on the
same batches, the eager side taking the mean of the loss and ``step(1)``
as the fused step does. Both share ``Optimizer.update_step``; what
differs is everything around it: how the gradient is made, the step
count, the learning-rate and weight-decay multipliers, the rescale, the
clip, where BatchNorm's running statistics and the masters land.

Tolerances come from the dtype. float32: both sides compute the same
float32 expressions, fused differently: 1e-5 relative, element by
element. bfloat16 with ``multi_precision``: the eager side rounds every
intermediate to bfloat16 (2^-8 relative) where XLA keeps a fusion's in
float32, BatchNorm's backward subtracts means from that, and the
adaptive rules divide by what is left; so the float32 MASTERS are
compared, loosely: each tensor's three-step UPDATE and each state leaf
in the L2 norm (over six seeds the worst readings were 0.18 and 0.28 of
the norm; a multiplier, a step count or a rescale left out moves an
update by half its norm or more), the running statistics to 5%, and the
bfloat16 weights exactly against their masters.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu import optimizer as opt_mod
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import FusedTrainStep

# every registered optimizer but sgld, whose noise is drawn on the host
# outside update_step (test_the_list_is_the_registry holds the list true)
OPTIMIZERS = ("adadelta", "adagrad", "adam", "adamax", "adamw", "dcasgd",
              "ftml", "ftrl", "lamb", "lars", "nadam", "nag", "rmsprop",
              "sgd", "signum")

STEPS = 3
BATCH, IN, HIDDEN, OUT = 16, 8, 16, 4


def _net():
    net = nn.HybridSequential()
    # no bias under the BatchNorm: its gradient is rounding noise, which
    # the adaptive rules normalise to steps of either sign
    net.add(nn.Dense(HIDDEN, in_units=IN, use_bias=False),
            nn.BatchNorm(in_channels=HIDDEN),
            nn.Activation("relu"),
            nn.Dense(OUT, in_units=HIDDEN))
    net.initialize()
    return net


def _params(net):
    return list(net.collect_params().values())


def _twin(values, dtype):
    """A fresh net holding ``values``, cast to ``dtype``, one parameter
    with multipliers of its own."""
    net = _net()
    for p, v in zip(_params(net), values):
        p.set_data(nd.array(v))
    if dtype != "float32":
        net.cast(dtype)
    first = next(p for p in _params(net) if p.grad_req != "null")
    first.lr_mult, first.wd_mult = 0.5, 2.0
    return net


def _optimizer(name, dtype):
    return opt_mod.create(name, learning_rate=0.01, wd=0.01,
                          clip_gradient=0.05,
                          multi_precision=dtype != "float32")


def _batches(dtype):
    rng = np.random.RandomState(7)
    return [(nd.array(rng.randn(BATCH, IN).astype(np.float32)).astype(dtype),
             nd.array(rng.randn(BATCH, OUT).astype(np.float32)).astype(dtype))
            for _ in range(STEPS)]


def _f32(raw):
    return np.asarray(raw).astype(np.float32)


def _apart(got, want):
    """|got - want| over |want|, in the L2 norm."""
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-12))


def _end_state(net, states):
    """(trainable weights, BatchNorm's running statistics, the optimizer's
    state leaf by leaf), as float32 arrays."""
    train = [_f32(p.data()._data) for p in _params(net)
             if p.grad_req != "null"]
    aux = [_f32(p.data()._data) for p in _params(net)
           if p.grad_req == "null"]
    return train, aux, [[_f32(leaf) for leaf in s] for s in states]


def _run_eager(values, name, dtype):
    net, loss_fn = _twin(values, dtype), gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), _optimizer(name, dtype),
                            kvstore=None)
    losses = []
    for x, y in _batches(dtype):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    return losses, _end_state(net, trainer._states)


def _run_fused(values, name, dtype):
    net = _twin(values, dtype)
    step = FusedTrainStep(net, gluon.loss.L2Loss(), _optimizer(name, dtype))
    losses = [float(step(x, y).asnumpy()) for x, y in _batches(dtype)]
    return losses, _end_state(net, step._states)


def test_the_list_is_the_registry():
    registered = {n for n, cls in opt_mod.registry._map.items()
                  if cls.__module__ == opt_mod.__name__}
    assert registered - {"sgld"} == set(OPTIMIZERS)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", OPTIMIZERS)
def test_fused_step_matches_eager_step(name, dtype):
    mx.random.seed(0)
    start = _net()
    values = [_f32(p.data()._data) for p in _params(start)]
    trained = [v for p, v in zip(_params(start), values)
               if p.grad_req != "null"]
    eager_losses, eager = _run_eager(values, name, dtype)
    fused_losses, fused = _run_fused(values, name, dtype)
    assert [len(part) for part in fused] == [len(trained), 2, len(trained)]
    assert [len(part) for part in eager] == [len(part) for part in fused]
    assert [len(s) for s in fused[2]] == [len(s) for s in eager[2]]
    moved = max(np.abs(a - v).max() for a, v in zip(fused[0], trained))
    assert moved > 1e-4, "the three steps must have trained"
    if dtype == "float32":
        tol = dict(rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(fused_losses, eager_losses, **tol)
        for what, got, want in (
                ("weight", fused[0], eager[0]),
                ("running statistic", fused[1], eager[1]),
                ("state leaf", sum(fused[2], []), sum(eager[2], []))):
            for i, (g, w) in enumerate(zip(got, want)):
                np.testing.assert_allclose(
                    g, w, err_msg=f"{name}: {what} {i}", **tol)
        return
    np.testing.assert_allclose(fused_losses, eager_losses, rtol=2e-2)
    for i, (g, w) in enumerate(zip(fused[1], eager[1])):
        assert _apart(g, w) < 0.05, f"{name}: running statistic {i}"
    for i, (gs, ws, w0) in enumerate(zip(fused[2], eager[2], trained)):
        assert _apart(gs[0] - w0, ws[0] - w0) < 0.4, \
            f"{name}: update of master {i}"
        for j, (g, w) in enumerate(zip(gs[1:], ws[1:])):
            assert _apart(g, w) < 0.6, f"{name}: state {i}, leaf {j + 1}"
    for side in (eager, fused):
        for weight, state in zip(side[0], side[2]):
            # the weights are the masters, rounded once
            np.testing.assert_array_equal(
                weight, _f32(state[0].astype(jnp.bfloat16)))
