"""The expert layer's sum by token as the Pallas kernel
(ops/pallas/token_sum.py, interpreted on the CPU) against XLA's scatter-add
(`ops/_raw.py` `_sum_by_token`), on rungs whose rows past the live ones
hold NaN, and through `sparse_experts`'s forward and backward; and the
selection row `sum_by_token` (ops/select.py)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import _raw, select

D = 128         # the kernel's columns are lanes: the width a multiple of 128


def _routing(tokens, top_k, experts, held, seed, lift=0.0):
    """(x, router, gate, up, down) float32 whose softmax top-k routing puts
    token 0 on every held expert it can (all of them where top_k >= held)
    and token 1 on none; the held experts' columns of x raised by `lift`."""
    rng = np.random.RandomState(seed)
    x = rng.randn(tokens, D).astype(np.float32) * 0.3
    x[:, :experts] = rng.rand(tokens, experts) + lift * (
        np.arange(experts) < held)
    x[0, :held] += 5.0
    x[1, :experts] = np.where(np.arange(experts) < held, -5.0, 0.0)
    router = rng.randn(experts, D).astype(np.float32) * 0.01
    router[:, :experts] += np.eye(experts, dtype=np.float32) * 4.0
    gate, up = (rng.randn(held, D, 64).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.randn(held, 64, D).astype(np.float32) * 0.2
    return tuple(jnp.asarray(a) for a in (x, router, gate, up, down))


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("top_k,experts,held,lift", [
    (1, 16, 8, 0.0), (2, 8, 2, 0.0), (8, 32, 8, 0.0), (2, 8, 2, 3.0)],
    ids=["top1", "top2", "top8", "top2-top-rung"])
def test_the_kernel_is_the_scatter_add(monkeypatch, fresh_traces, top_k,
                                       experts, held, lift):
    """The sum alone on the rung the routing takes, forward (float32
    weights) and backward (no weight): rows past the live ones are NaN and
    are never added, a token with no live row reads exactly 0, the token
    that owns a row in every held expert sums them all; then `sparse_experts`
    with the kernel chosen against XLA's form: the output and all five
    gradients, with grouped products that leave NaN past the groups."""
    tokens = 512
    x, router, gate, up, down = _routing(tokens, top_k, experts, held, 3,
                                         lift)
    chosen = np.asarray(jax.lax.top_k(jax.nn.softmax(x @ router.T), top_k)[1])
    local = chosen.reshape(-1)
    order = np.argsort(np.where(local < held, local, held), kind="stable")
    live = int((local < held).sum())
    ladder = _raw.row_capacities(tokens * top_k, held, experts)
    capacity = ladder[_raw.row_capacity(live, ladder)]
    assert live < capacity and (len(ladder) == 1 or capacity ==
                                ladder[1 if lift else 0])
    assert (chosen[0] < held).sum() == min(top_k, held)
    assert not (chosen[1] < held).any()
    rng = np.random.RandomState(top_k)
    rows = rng.randn(capacity, D).astype(np.float32)
    rows[live:] = np.nan
    weight = rng.rand(capacity).astype(np.float32)
    picked = jnp.asarray(order[:capacity], jnp.int32)
    owners = set(order[:live] // top_k)
    empty = np.array([t not in owners for t in range(tokens)])
    assert empty[1]
    for w in (weight, None):
        want = np.zeros((tokens, D))
        np.add.at(want, order[:live] // top_k,
                  rows[:live] * (1.0 if w is None else w[:live, None]))
        for kernel in (False, True):
            got = np.asarray(_raw._sum_by_token(
                jnp.asarray(rows), picked, top_k, tokens, jnp.int32(live),
                None if w is None else jnp.asarray(w), kernel))
            assert np.isfinite(got).all() and (got[empty] == 0.0).all()
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    product = _raw.grouped_matmul

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def poisoned(lhs, rhs, group_sizes, kernel):
        return forward(lhs, rhs, group_sizes, kernel)[0]

    def beyond(sizes, rows):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

    def forward(lhs, rhs, sizes, kernel):
        out, pull = jax.vjp(lambda a, b: product(a, b, sizes, kernel),
                            lhs, rhs)
        return jnp.where(beyond(sizes, lhs), jnp.nan, out), (pull, sizes)

    def backward(kernel, res, grad):
        pull, sizes = res
        d_lhs, d_rhs = pull(jnp.where(beyond(sizes, grad), 0.0, grad))
        return jnp.where(beyond(sizes, d_lhs), jnp.nan, d_lhs), d_rhs, None
    poisoned.defvjp(forward, backward)
    monkeypatch.setattr(_raw, "grouped_matmul", poisoned)
    monkeypatch.setattr(select, "grouped_matmul", lambda *a: False)
    cotangent = jnp.asarray(rng.randn(tokens, D), jnp.float32)

    def layer(*operands):
        y, _ = _raw.sparse_experts(*operands, top_k, 0)
        return jnp.sum(y * cotangent), y
    results = []
    for kernel in (False, True):
        monkeypatch.setattr(select, "sum_by_token",
                            lambda rows, tokens, kernel=kernel: kernel)
        (_, y), grads = jax.jit(jax.value_and_grad(
            layer, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, router, gate, up, down)
        results.append((y,) + grads)
    for name, want, got in zip(("y", "x", "router", "gate", "up", "down"),
                               *results):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * np.abs(want).max(),
                                   err_msg=name)


@pytest.mark.parametrize("devices,mode,decision", [
    (1, "force", "selected"), (2, "force", "rejected"), (1, "0", None)],
    ids=["one-device", "mesh-of-two", "pallas-off"])
def test_the_sum_is_chosen_once_a_layer(monkeypatch, fresh_traces, devices,
                                        mode, decision):
    """`sparse_experts` asks the row once a layer, in its forward, for the
    combine and for the backward's sum: one count a traced layer. Under a
    mesh it is rejected, with `MXTPU_PALLAS=0` not asked; both hold XLA's
    scatter-add and no kernel, forward and backward alike."""
    monkeypatch.setenv("MXTPU_PALLAS", mode)
    monkeypatch.setattr(select, "grouped_matmul", lambda *a: False)
    x, router, gate, up, down = _routing(256, 2, 8, 2, 4)

    def layer(*operands):
        return _raw.sparse_experts(*operands, 2, 0)[0]
    operands = (x, router, gate, up, down)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("dp",))
    before = dict(profiler.counters())
    with select.partitioned(mesh):
        y, pull = jax.vjp(layer, *operands)
        moved = {k.split("/")[-1]: v - before.get(k, 0)
                 for k, v in profiler.counters().items()
                 if "sum_by_token" in k and v != before.get(k, 0)}
        forward = jax.make_jaxpr(layer)(*operands)
    assert moved == ({} if decision is None else
                     {f"pallas.{decision}.sum_by_token": 1}), moved
    # the scope has closed, as it has when jax transposes a step's loss
    backward = jax.make_jaxpr(pull)(y)
    for program in (forward, backward):
        text = str(program)
        assert ("sum_by_token" in text) == (decision == "selected")
        # XLA's sum: a scatter-add into the (tokens, D) float32 sums
        assert bool(re.search(r"f32\[256,128\] = scatter-add", text)) != (
            decision == "selected")


def test_the_plan_keeps_the_sums_inside_vmem(monkeypatch):
    """The cells' shapes take the widest column block that divides the
    width and fits the budget; the row rejects a width off the lanes,
    another dtype, or more tokens than 128 columns of float32 sums can
    hold."""
    from incubator_mxnet_tpu.ops.pallas import token_sum
    bf16 = jnp.bfloat16
    mellum2 = token_sum.plan(8192, 10240, 2304, bf16, bf16)
    assert (mellum2.columns, mellum2.rows) == (1152, 1024)
    assert token_sum.plan(8192, 5120, 2048, bf16, bf16).columns == 1024
    assert token_sum.plan(2 ** 20, 10240, 2304, bf16, bf16) is None
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    cases = [((10240, 2304), 8192, bf16, True),
             ((10240, 2300), 8192, bf16, False),
             ((10240, 2304), 8192, jnp.float16, False),
             ((10240, 2304), 2 ** 20, bf16, False)]
    for rows, tokens, dtype, ok in cases:
        assert select.sum_by_token(jax.ShapeDtypeStruct(rows, dtype),
                                   tokens) == ok
