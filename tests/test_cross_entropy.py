"""The sparse-label softmax cross-entropy's own rule (ops/_raw.py
`softmax_cross_entropy`: a float32 log-sum-exp kept a row, the gradient
one elementwise pass) against log_softmax + take_along_axis, and the
shifted LM loss (models/transformer_lm.py `lm_loss`: the targets move, not
the logits) against slicing and flattening the logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.models import TransformerLM
from incubator_mxnet_tpu.models.transformer_lm import lm_loss
from incubator_mxnet_tpu.ops import _raw

BF16_EPS = 2.0 ** -8            # a bf16 ulp at 1, relative


def _reference(x, label, axis=-1):
    logp = jax.nn.log_softmax(x, axis=axis)
    return -jnp.take_along_axis(logp, jnp.expand_dims(label, axis),
                                axis=axis).squeeze(axis)


def _cases(shape, axis, seed=0, scale=3.0):
    kx, kl, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, shape, jnp.float32) * scale
    lshape = tuple(n for i, n in enumerate(shape) if i != axis % len(shape))
    label = jax.random.randint(kl, lshape, 0, shape[axis])
    weights = jax.random.normal(kw, lshape, jnp.float32)
    return x, label, weights


def _value_and_grad(fn, x, label, weights, axis):
    """The loss a row and the gradient of a weighted sum of it: each row's
    cotangent differs, as the mean's and the step's do."""
    def total(x):
        loss = fn(x, label, axis)
        return jnp.sum(loss.astype(jnp.float32) * weights), loss
    (_, loss), grad = jax.value_and_grad(total, has_aux=True)(x)
    return loss, grad


@pytest.mark.parametrize("shape,axis", [((4, 9, 37), -1), ((6, 50), 1),
                                        ((5, 11, 7), 1), ((13, 4, 3), 0)],
                         ids=["rows", "matrix", "middle-axis", "first-axis"])
def test_float32_matches_log_softmax(shape, axis):
    x, label, weights = _cases(shape, axis)
    loss, grad = _value_and_grad(_raw.softmax_cross_entropy, x, label,
                                 weights, axis)
    want, want_grad = _value_and_grad(_reference, x, label, weights, axis)
    assert loss.dtype == jnp.float32 and loss.shape == want.shape
    np.testing.assert_allclose(loss, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,axis", [((4, 9, 300), -1), ((7, 64, 5), 1)],
                         ids=["rows", "middle-axis"])
def test_bfloat16_within_one_ulp(shape, axis):
    """bf16 logits: the loss is the float32 loss of the same logits rounded
    once, within one bf16 ulp of it; the gradient is rounded twice, its
    cotangent to the loss's bf16 and the result to the logits', so within
    two."""
    x, label, weights = _cases(shape, axis)
    xb = x.astype(jnp.bfloat16)
    loss, grad = _value_and_grad(_raw.softmax_cross_entropy, xb, label,
                                 weights, axis)
    want, want_grad = _value_and_grad(_reference, xb.astype(jnp.float32),
                                      label, weights, axis)
    assert loss.dtype == grad.dtype == jnp.bfloat16
    loss, grad = np.float32(loss), np.float32(grad)
    assert np.all(np.abs(loss - want) <= BF16_EPS * np.abs(want) + 1e-6)
    assert np.all(np.abs(grad - want_grad)
                  <= 2 * BF16_EPS * np.abs(want_grad) + 1e-6)


def test_labels_at_both_ends_and_negative_ids():
    x, _, weights = _cases((2, 8, 31), -1, seed=3)
    label = jnp.zeros((2, 8), jnp.int32).at[:, ::2].set(30)
    for ids in (label, label - 31):     # -1 counts from the end: 30 again
        loss, grad = _value_and_grad(_raw.softmax_cross_entropy, x, ids,
                                     weights, -1)
        want, want_grad = _value_and_grad(_reference, x, label, weights, -1)
        np.testing.assert_allclose(loss, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(grad, want_grad, rtol=1e-6, atol=1e-6)
    # an id outside [-V, V) reads NaN where it stands, as a gather's does
    wrong = label.at[1, 3].set(31).at[0, 5].set(-32)
    loss = _raw.softmax_cross_entropy(x, wrong)
    assert np.isnan(loss[1, 3]) and np.isnan(loss[0, 5])
    assert np.isfinite(loss).sum() == loss.size - 2


def test_stable_far_from_zero():
    """Logits offset by 1e4: nothing overflows, and the loss and gradient
    are the reference's on the same logits and, to the offset's rounding,
    those of the logits without it."""
    x, label, weights = _cases((3, 5, 40), -1, seed=4)
    for shifted in (x + 1e4, x - 1e4):
        loss, grad = _value_and_grad(_raw.softmax_cross_entropy, shifted,
                                     label, weights, -1)
        want, want_grad = _value_and_grad(_reference, shifted, label,
                                          weights, -1)
        assert np.all(np.isfinite(loss)) and np.all(np.isfinite(grad))
        np.testing.assert_allclose(loss, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(grad, want_grad, rtol=1e-6, atol=1e-6)
        plain, plain_grad = _value_and_grad(_raw.softmax_cross_entropy, x,
                                            label, weights, -1)
        np.testing.assert_allclose(loss, plain, atol=1e-2)
        np.testing.assert_allclose(grad, plain_grad, atol=1e-2)


def test_dense_labels_keep_their_formula():
    x, label, _ = _cases((4, 6, 10), -1, seed=5)
    target = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9),
                                              x.shape))
    got = _raw.softmax_cross_entropy(x, target, sparse_label=False)
    want = -jnp.sum(target * jax.nn.log_softmax(x), axis=-1)
    np.testing.assert_array_equal(got, want)
    grad = jax.grad(lambda x: _raw.softmax_cross_entropy(
        x, target, sparse_label=False).sum())(x)
    want_grad = jax.grad(lambda x: -jnp.sum(
        target * jax.nn.log_softmax(x)))(x)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-6, atol=1e-6)


def test_a_program_split_over_a_mesh_keeps_log_softmax():
    """One device takes the rule; inside a program that GSPMD partitions
    over several (ops/select.py `partitioned`) class ids keep jax's
    log-softmax and gather, which GSPMD splits as before (the FSDP
    signature of tests/test_commscope.py)."""
    import types

    from incubator_mxnet_tpu.ops import select
    x, label, _ = _cases((4, 6, 10), -1, seed=6)

    def traced():
        return str(jax.make_jaxpr(
            lambda x: _raw.softmax_cross_entropy(x, label))(x))
    assert "custom_vjp_call" in traced()
    with select.partitioned(types.SimpleNamespace(size=1)):
        assert "custom_vjp_call" in traced()
    with select.partitioned(types.SimpleNamespace(size=4)):
        split = traced()
        np.testing.assert_allclose(_raw.softmax_cross_entropy(x, label),
                                   _reference(x, label), rtol=1e-6)
    assert "custom_vjp_call" not in split and "gather" in split


# -- the shifted LM loss ------------------------------------------------------

def _sliced_lm_loss(logits, targets):
    """The formula lm_loss replaced: the logits sliced and flattened."""
    v = logits.shape[-1]
    return gluon.loss.SoftmaxCrossEntropyLoss()(
        logits[:, :-1].reshape(-1, v), targets[:, 1:].reshape(-1))


# B > 1 and L - 1 = 12: no multiple of 8
B, L, V = 3, 13, 29


def _logits_and_targets(seed=0):
    rng = np.random.RandomState(seed)
    logits = rng.randn(B, L, V).astype(np.float32) * 2
    targets = rng.randint(0, V, (B, L)).astype(np.int32)
    return logits, targets


def _eager(loss_fn, logits, targets):
    x = nd.array(logits)
    x.attach_grad()
    with autograd.record():
        loss = loss_fn(x, nd.array(targets))
        total = (loss * nd.array(np.arange(loss.shape[0], dtype=np.float32)
                                 / loss.shape[0])).sum()
    total.backward()
    return loss, x.grad.asnumpy()


def test_lm_loss_eager_matches_the_sliced_formula():
    logits, targets = _logits_and_targets()
    loss, grad = _eager(lm_loss, logits, targets)
    want, want_grad = _eager(_sliced_lm_loss, logits, targets)
    assert loss.shape == want.shape == (B * (L - 1),)
    assert loss.dtype == want.dtype
    np.testing.assert_allclose(loss.asnumpy(), want.asnumpy(), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-6, atol=1e-6)
    # the last position's loss is dropped: its gradient is exactly zero
    assert not np.any(grad[:, -1])


def test_lm_loss_keeps_the_logits_dtype():
    logits, targets = _logits_and_targets(seed=1)
    x = nd.array(logits).astype("bfloat16")
    loss, want = lm_loss(x, nd.array(targets)), _sliced_lm_loss(
        x, nd.array(targets))
    assert loss.dtype == want.dtype == jnp.bfloat16
    assert loss.shape == want.shape
    ref = np.float32(_reference(jnp.asarray(x.asnumpy(), jnp.float32)[:, :-1],
                                jnp.asarray(targets)[:, 1:])).reshape(-1)
    got = np.float32(loss.asnumpy())
    assert np.all(np.abs(got - ref) <= BF16_EPS * np.abs(ref) + 1e-6)


def _step_loss_and_weights(loss_fn, ids):
    from incubator_mxnet_tpu.parallel import FusedTrainStep
    mx.random.seed(7)
    net = TransformerLM(V, num_layers=1, units=16, hidden_size=32,
                        num_heads=2, max_length=16, dropout=0.0)
    net.initialize(init=mx.init.Normal(0.2))
    step = FusedTrainStep(net, lambda out, y: loss_fn(out, y).mean(),
                          mx.optimizer.create("sgd", learning_rate=0.5))
    x = nd.array(ids)
    losses = [float(step(x, x).asnumpy()) for _ in range(2)]
    return losses, [p.data().asnumpy()
                    for p in net.collect_params().values()]


def test_lm_loss_in_a_fused_step_matches_the_sliced_formula():
    """Two SGD steps of a one-layer LM at B = 3, L = 13: the losses and the
    weights after them are the sliced formula's."""
    _, ids = _logits_and_targets(seed=2)
    losses, weights = _step_loss_and_weights(lm_loss, ids)
    want, want_weights = _step_loss_and_weights(_sliced_lm_loss, ids)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    assert losses[1] < losses[0]
    assert len(weights) == len(want_weights)
    for i, (value, wanted) in enumerate(zip(weights, want_weights)):
        np.testing.assert_allclose(value, wanted, rtol=1e-4, atol=1e-5,
                                   err_msg=str(i))
