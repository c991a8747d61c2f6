"""What the Kimi-Linear configuration added to models.MoeLM (the chunked gated
delta rule and its block, latent attention without positions, the sigmoid
router with a selection bias and a shared expert, a dense feed-forward where
the config marks a layer dense) against the plain reference of the
benchmark's configuration, at toy sizes in float32 on the CPU, where matrix
products are true float32 and only the order of sums differs."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.models import (LatentAttentionCell,
                                        LinearAttentionCell, MoeLM)
from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.parallel import FusedTrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
NAME = "kimi_linear_48b_a3b_ep32"


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "kimi_" + os.path.basename(path).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _module(os.path.join(CONFIGS, NAME + ".reference.py"))


@pytest.fixture(scope="module")
def toy():
    """The file's own toy sizes: 64 wide, 4 KDA heads of 16, latent heads of
    16 + 8 over a latent of 32, 16 experts top-4 of which 4 are held, a
    shared expert, the dense layer and the four that follow; float32."""
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        doc = json.load(f)
    doc.update(doc.pop("rehearse"), dtype="float32")
    return doc


def _float32(net):
    return [jnp.asarray(np.asarray(p.data().jax(), np.float32))
            for p in net.collect_params().values()]


@pytest.fixture(scope="module")
def built(toy):
    """(model module, net, tokens, the net's parameters as float32 copies)."""
    model = _module(os.path.join(CONFIGS, NAME + ".py"))
    net = model.net(toy, 11)
    tokens, _ = model.batch(toy, {"batch": 2, "seq": 100}, 11)
    with autograd.pause():
        net(tokens)
    return model, net, tokens, _float32(net)


# -- the chunked op against the recurrence ----------------------------------

def _delta_inputs(length, seed=0, heads=3, dk=8, dv=12, batch=2):
    rng = np.random.RandomState(seed)
    q = rng.randn(batch, length, heads, dk)
    k = rng.randn(batch, length, heads, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(batch, length, heads, dv)
    # log-decays of -0.1 to -30 a token: a chunk cumulates down to -500,
    # whose exp float32 cannot hold, let alone its inverse
    g = -np.exp(rng.randn(batch, length, heads, dk) * 1.5 + 0.5)
    beta = 1 / (1 + np.exp(-rng.randn(batch, length, heads)))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


def _moved(before, kernel="gated_delta_rule"):
    """The selection counters of `kernel` that moved since `before`."""
    return {k.split("/")[-1]: v - before.get(k, 0)
            for k, v in profiler.counters().items()
            if k.endswith("." + kernel) and v != before.get(k, 0)}


@pytest.mark.parametrize("pallas", ["force", "0"], ids=["kernels", "xla"])
@pytest.mark.parametrize("length", [150, 64, 700],
                         ids=["ragged", "one-chunk", "two-steps"])
def test_the_chunked_delta_rule_is_the_recurrence(reference, monkeypatch,
                                                  length, pallas):
    """Outputs and all five gradients of `gated_delta_rule` against the
    reference's token-by-token scan, at a length that is no multiple of
    the chunk (150 = 2 x 64 + 22), at one chunk, and over two steps of the
    outer scan (700 > 512), with decays that underflow a cumulated
    product: 1e-4 of the largest entry. Through the XLA form, and through
    the Pallas kernels (interpreted; heads of 128, which they take)."""
    monkeypatch.setenv("MXTPU_PALLAS", pallas)
    kernels = pallas == "force"
    heads, dk, dv = (2, 128, 128) if kernels else (3, 8, 12)
    inputs = _delta_inputs(length, heads=heads, dk=dk, dv=dv)
    cotangent = jnp.asarray(np.random.RandomState(1).randn(
        2, length, heads, dv), jnp.float32)

    def plain(*a):
        return jax.vmap(lambda *s: reference._recurrence(*s, None, None))(*a)

    def chunked(*a):
        return _raw.gated_delta_rule(*a)[0]

    before = dict(profiler.counters())
    got, lowest = _raw.gated_delta_rule(*inputs)
    assert _moved(before) == ({"pallas.selected.gated_delta_rule": 1}
                              if kernels else {})
    want = plain(*inputs)
    assert float(lowest) < -200        # exp underflows float32 below -104
    by_chunk = np.asarray(inputs[3])[:, :length // 64 * 64].reshape(
        2, -1, 64, heads, dk).sum(2)
    assert float(lowest) <= by_chunk.min() + 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-4 * float(jnp.max(jnp.abs(want))))

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * cotangent),
                        argnums=(0, 1, 2, 3, 4))(*inputs)
    for name, mine, theirs in zip("q k v g beta".split(), grads(chunked),
                                  grads(plain)):
        assert bool(jnp.all(jnp.isfinite(mine))), name
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(theirs), rtol=0, err_msg=name,
            atol=1e-4 * float(jnp.max(jnp.abs(theirs))))


@pytest.mark.parametrize("pallas", ["force", "0"], ids=["kernels", "xla"])
def test_the_backward_keeps_a_state_a_step_and_no_chunk(reference, pallas):
    """The op's own rule: what the forward hands the backward is the five
    inputs and one (dk, dv) state a head for each step of 8 chunks
    (transposed and by head where the kernels wrote it), nothing of a
    chunk's shape."""
    from incubator_mxnet_tpu.ops.pallas import gated_delta_rule as kernels
    if pallas == "force":
        inputs = _delta_inputs(1100, heads=2, dk=128, dv=256)
        assert kernels.padded_length(1100) == 1536
        _, residuals = jax.eval_shape(_raw._delta_kernel_fwd, *inputs)
        assert [r.shape for r in residuals] == [
            (2, 1100, 2, 128), (2, 1100, 2, 128), (2, 1100, 2, 256),
            (2, 1100, 2, 128), (2, 1100, 2), (2, 2, 3, 256, 128)]
        return
    inputs = _delta_inputs(1100, heads=2)       # 18 chunks: 3 steps of 8
    _, residuals = jax.eval_shape(_raw._delta_fwd, *(
        jnp.moveaxis(jnp.pad(x, ((0, 0), (0, 1536 - 1100))
                             + ((0, 0),) * (x.ndim - 2)), 2, 1)
        for x in inputs))
    assert [r.shape for r in residuals] == [
        (2, 2, 1536, 8), (2, 2, 1536, 8), (2, 2, 1536, 12), (2, 2, 1536, 8),
        (2, 2, 1536), (3, 2, 2, 8, 12)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_backward_written_by_hand_is_the_vjp_of_a_step(dtype):
    """The rule the backward kernel runs (`head_backward`: one head, a step
    of 8 chunks, as plain `jax.numpy`) against `jax.vjp(_delta_group)`
    from a state that is not zero: the gradients of q, k, v, g and beta and
    the carried dS, in float32 to 1e-5 of each one's largest entry; in
    bfloat16 (operands rounded where `_delta_group` rounds them, its
    transposes rounding cotangents besides) to a hundredth."""
    from incubator_mxnet_tpu.ops.pallas import gated_delta_rule as kernels
    rng = np.random.RandomState(5)
    tokens, dk, dv = 512, 128, 128
    q, k, v, g, beta = (x[0, :, 0] for x in _delta_inputs(
        tokens, seed=5, heads=1, dk=dk, dv=dv, batch=1))
    g = g * 0.2                      # down to -100 a chunk: float32 holds
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    beta = beta[:, None]
    state, d_state = (jnp.asarray(rng.randn(dk, dv) * scale, jnp.float32)
                      for scale in (0.5, 1.0))
    d_o = jnp.asarray(rng.randn(tokens, dv), dtype)

    def group(state, q, k, v, g, beta):
        return _raw._delta_group(*(x[None, None] for x in (
            state, q, k, v, g, beta[:, 0])))
    (after, o), pull = jax.vjp(group, state, q, k, v, g, beta)
    want = pull((d_state[None, None], d_o[None, None]))
    dtype = jnp.dtype(dtype)
    got_after, got_o = kernels.head_forward(dtype, state.T, q, k, v, g, beta)
    got = kernels.head_backward(dtype, state.T, d_state.T, q, k, v, g, beta,
                                d_o)
    got = [got_after.T, got_o, got[0].T, *got[1:5], got[5].T]
    want = [after[0, 0], o[0, 0], *want]
    for name, mine, theirs in zip(
            "after o dS dq dk dv dg dbeta".split(), got, want):
        theirs = np.asarray(theirs, np.float32).reshape(mine.shape)
        np.testing.assert_allclose(
            np.asarray(mine, np.float32), theirs, rtol=0, err_msg=name,
            atol=(1e-5 if dtype == "float32" else 1e-2) * np.abs(theirs).max())


@pytest.mark.parametrize("why", ["mesh", "dk64", "float16"])
def test_a_rejected_selection_runs_the_xla_form(monkeypatch, why):
    """Under a mesh program, with heads that are no multiple of 128 lanes,
    or in a dtype the kernels do not take, `gated_delta_rule` and the
    mixer trace the scans of `_delta_group` and no kernel, and the
    rejection is counted."""
    from incubator_mxnet_tpu.ops import select
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    dk = 64 if why == "dk64" else 128
    inputs = _delta_inputs(100, heads=2, dk=dk, dv=128, batch=1)
    if why == "float16":
        inputs[:3] = [x.astype(jnp.float16) for x in inputs[:3]]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2 if why == "mesh"
                                                     else 1]), ("dp",))
    before = dict(profiler.counters())
    with select.partitioned(mesh):
        program = jax.make_jaxpr(
            lambda *a: jax.vjp(_raw.gated_delta_rule, *a)[1](
                (jnp.ones((1, 100, 2, 128), a[0].dtype), jnp.float32(0))))(
                    *inputs)
    assert _moved(before) == {"pallas.rejected.gated_delta_rule": 1}
    text = str(program)
    assert "pallas_call" not in text and text.count("scan[") >= 2
    with select.partitioned(None):
        program = jax.make_jaxpr(_raw.gated_delta_rule)(*(
            x.astype(jnp.float32) if why == "float16" else x
            for x in inputs))
    assert ("pallas_call" in str(program)) == (why != "dk64")


def test_short_conv_is_causal_and_depthwise():
    x = jnp.asarray(np.random.RandomState(2).randn(1, 9, 5), jnp.float32)
    taps = jnp.asarray(np.random.RandomState(3).randn(4, 5), jnp.float32)
    got = np.asarray(_raw.short_conv(x, taps))
    want = np.zeros((9, 5), np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += np.asarray(taps)[j] * np.asarray(x)[0, t - 3 + j]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)


# -- the blocks against the reference ---------------------------------------

def _block_against(reference_fn, cell, doc, x):
    cell.initialize(init=mx.init.Normal(0.3))
    with autograd.pause():
        got = cell(nd.array(x)).asnumpy()
    params = tuple(_float32(cell))
    want = np.stack([np.asarray(reference_fn(doc, jnp.asarray(seq), params,
                                             None, None)) for seq in x])
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(np.max(np.abs(want))))


def test_linear_attention_cell_is_the_references(toy, reference):
    kda = toy["linear_attn_config"]
    x = np.random.RandomState(6).randn(2, 90, 64).astype(np.float32)
    cell = LinearAttentionCell(64, kda["num_heads"], kda["head_dim"],
                               kda["short_conv_kernel_size"],
                               toy["rms_norm_eps"])
    _block_against(reference._linear_attention, cell, toy, x)
    # the decay's start: a memory of 1 to 1000 tokens
    rate = np.exp(cell.a_log.data().asnumpy())
    assert rate.min() >= 1 and rate.max() <= 16
    step = np.log1p(np.exp(cell.dt_bias.data().asnumpy()))
    assert step.min() >= 0.99e-3 and step.max() <= 0.101


def test_latent_attention_cell_is_the_references(toy, reference):
    """Keys of 16 + 8 (the 8 shared by the heads, nothing rotated) beside
    values of 16, through the flash kernel and through XLA."""
    x = np.random.RandomState(7).randn(2, 40, 64).astype(np.float32)
    cell = LatentAttentionCell(64, toy["num_attention_heads"],
                               toy["kv_lora_rank"], toy["qk_nope_head_dim"],
                               toy["qk_rope_head_dim"], toy["v_head_dim"],
                               toy["rms_norm_eps"])
    _block_against(reference._latent_attention, cell, toy, x)


@pytest.mark.parametrize("pallas", ["force", "0"], ids=["flash", "xla"])
def test_attention_takes_values_of_their_own_head_size(monkeypatch, pallas):
    """multihead_attention with keys of 24 and values of 16 a head, causal,
    grouped 4 : 2: both branches against a plain softmax, outputs and
    gradients."""
    monkeypatch.setenv("MXTPU_PALLAS", pallas)
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(2, 50, 4 * 24), jnp.float32)
    k = jnp.asarray(rng.randn(2, 50, 2 * 24), jnp.float32)
    v = jnp.asarray(rng.randn(2, 50, 2 * 16), jnp.float32)
    cotangent = jnp.asarray(rng.randn(2, 50, 4 * 16), jnp.float32)

    def plain(q, k, v):
        qh = q.reshape(2, 50, 4, 24)
        kh = jnp.repeat(k.reshape(2, 50, 2, 24), 2, axis=2)
        vh = jnp.repeat(v.reshape(2, 50, 2, 16), 2, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(24)
        mask = jnp.tril(jnp.ones((50, 50), bool))
        weights = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, vh).reshape(2, 50, 64)

    def ours(q, k, v):
        return _raw.multihead_attention(q, k, v, 4, causal=True,
                                        num_kv_heads=2)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * cotangent), argnums=(0, 1, 2))(
                q, k, v)
    (got, got_grads), (want, want_grads) = both(ours), both(plain)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for mine, theirs in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                   rtol=1e-4, atol=1e-5)


# -- the router -------------------------------------------------------------

def _expert_weights(d=64, f=32, experts=16, seed=4):
    rng = np.random.RandomState(seed)
    router = rng.randn(experts, d).astype(np.float32) * 0.5
    gate, up = (rng.randn(experts, d, f).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.randn(experts, f, d).astype(np.float32) * 0.2
    shared = [rng.randn(*shape).astype(np.float32) * 0.2
              for shape in ((d, f), (d, f), (f, d))]
    return router, gate, up, down, shared


def test_a_selection_bias_changes_the_choice_and_not_the_weights():
    """Sigmoid scores; with a bias that lifts expert 5 over every other,
    each token chooses 5 and its three best others, and its weights are
    still the SCORES of the chosen over their sum, times the scale: the
    bias is in none of them, and no gradient reaches it."""
    router, gate, up, down, _ = _expert_weights()
    x = np.random.RandomState(9).randn(30, 64).astype(np.float32)
    score = 1 / (1 + np.exp(-(x @ router.T)))
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0

    def run(bias):
        return _raw.sparse_experts(
            jnp.asarray(x), jnp.asarray(router), jnp.asarray(gate),
            jnp.asarray(up), jnp.asarray(down), 4, 0, True, "sigmoid",
            None if bias is None else jnp.asarray(bias), 2.446)

    (plain, plain_load), (lifted, load) = run(None), run(bias)
    assert int(load[5]) == 30 and int(plain_load[5]) < 30
    assert int(load.sum()) == int(plain_load.sum()) == 30 * 4
    want = np.zeros_like(x)
    for t in range(30):
        others = [e for e in np.argsort(-score[t]) if e != 5][:3]
        chosen = [5] + others
        weights = score[t, chosen] / score[t, chosen].sum() * 2.446
        for e, w in zip(chosen, weights):
            hidden = x[t] @ gate[e]
            want[t] += w * ((hidden / (1 + np.exp(-hidden))
                             * (x[t] @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(lifted), want, rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(lifted - plain))) > 1e-3
    # zeros change nothing; no gradient reaches the bias
    np.testing.assert_allclose(np.asarray(run(np.zeros(16, np.float32))[0]),
                               np.asarray(plain), rtol=1e-6, atol=1e-7)
    d_bias = jax.grad(lambda b: jnp.sum(_raw.sparse_experts(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(gate),
        jnp.asarray(up), jnp.asarray(down), 4, 0, True, "sigmoid", b,
        2.446)[0]))(jnp.asarray(bias))
    assert float(jnp.max(jnp.abs(d_bias))) == 0.0


def test_the_softmax_router_is_traced_as_before():
    """Mellum2's router (softmax, no bias, no scale) traces what it traced
    before the options came: one top_k of the softmax itself, where the
    sigmoid router scores (one `logistic` more) and gathers the chosen
    scores."""
    router, gate, up, down, _ = _expert_weights()
    args = [jnp.zeros((8, 64)), *(jnp.asarray(a)
                                  for a in (router, gate, up, down))]

    def traced(*options):
        return str(jax.make_jaxpr(
            lambda *a: _raw.sparse_experts(*a, 4, 0, True, *options))(*args))
    plain, scored = traced(), traced("sigmoid", None, 2.446)
    assert plain == traced("softmax", None, 1.0)
    assert plain.count("top_k") == scored.count("top_k") == 1
    assert scored.count("logistic") == plain.count("logistic") + 1
    assert "exp " in plain and scored.count("gather") > plain.count("gather")


def test_the_shares_with_a_shared_expert_add_up_to_the_whole(toy, reference):
    """16 experts in 4 shares of 4, a shared expert on every holder: the
    routed parts that the shares give, summed, and the shared expert
    counted ONCE, equal what the uncut layer gives (the reference holding
    all 16)."""
    router, gate, up, down, shared = _expert_weights()
    x = np.random.RandomState(5).randn(2, 24, 64).astype(np.float32)

    def block(first, count):
        layer = nn.SparseExperts(64, 32, 16, 4, held=(first, count),
                                 scoring="sigmoid", selection_bias=True,
                                 scale=2.446, shared_hidden_size=32)
        layer.initialize()
        for p, value in ((layer.router, router),
                         (layer.gate, gate[first:first + count]),
                         (layer.up, up[first:first + count]),
                         (layer.down, down[first:first + count]),
                         (layer.shared.gate, shared[0]),
                         (layer.shared.up, shared[1]),
                         (layer.shared.down, shared[2])):
            p.set_data(nd.array(value))
        return layer

    def shared_part(seq):
        return np.asarray(reference._gated(jnp.asarray(seq), *map(
            jnp.asarray, shared), None))

    alike = np.stack([shared_part(seq) for seq in x])
    total = np.zeros_like(x)
    for first in range(0, 16, 4):
        # a holder's result, less what every holder computes alike
        total += block(first, 4)(nd.array(x)).asnumpy() - alike
    total += alike
    whole = dict(toy, num_experts_held={"first": 0, "count": 16})
    params = tuple(jnp.asarray(a) for a in (
        router, gate, up, down, np.zeros(16), np.zeros(16), *shared))
    want = np.stack([np.asarray(reference._sparse(
        whole, jnp.asarray(seq), params, None, None)) for seq in x])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(block(0, 16)(nd.array(x)).asnumpy(), want,
                               rtol=1e-4, atol=1e-5)


# -- the model --------------------------------------------------------------

def test_logits_and_loss_are_the_references(toy, built, reference):
    model, net, tokens, params = built
    with autograd.pause():
        got = net(tokens).jax()
    want = reference.logits(toy, params, tokens.jax())
    assert got.shape == want.shape == (2, 100, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    loss = model.loss(toy)(net(tokens), tokens)
    want_loss = reference.loss(toy, params, tokens.jax(), tokens.jax())
    assert float(loss.asscalar()) == pytest.approx(float(want_loss),
                                                   rel=1e-5)
    # in row blocks, a layer at a time in backward: the same numbers
    blocked = jax.jit(lambda p: reference.loss(
        toy, p, tokens.jax(), tokens.jax(), rows=25))(params)
    assert float(blocked) == pytest.approx(float(want_loss), rel=1e-5)


def test_every_gradient_is_the_references(toy, built, reference):
    """One FusedTrainStep of plain SGD at rate 1: a weight's change is
    minus its gradient, held to the reference's float32 gradient at 1e-4 of
    the parameter's largest gradient entry plus what float32 resolves of
    the weight itself. The counters move and the selection bias does not."""
    model, net, tokens, params = built
    want_loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        toy, p, tokens.jax(), tokens.jax(), rows=25))(params)
    step = FusedTrainStep(net, model.loss(toy),
                          mx.optimizer.create("sgd", learning_rate=1.0))
    loss = float(step(tokens, tokens).asscalar())
    assert loss == pytest.approx(float(want_loss), rel=1e-4)
    checked = 0
    for (name, p), before, want in zip(net.collect_params().items(),
                                       params, grads):
        after = np.asarray(p.data().jax(), np.float32)
        if name.endswith("load"):
            assert after.sum() == 2 * 100 * 4
        elif name.endswith("log_decay_min"):
            assert -64 * 16 * 0.11 < after[0] < 0
        elif p.grad_req == "null":       # the selection bias
            assert name.endswith("bias") and not after.any()
        else:
            got = np.asarray(before) - after
            scale = float(np.max(np.abs(want)))
            assert scale > 0, name
            resolved = (np.finfo(np.float32).eps
                        * float(np.max(np.abs(before))))
            np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                                       atol=1e-4 * scale + resolved,
                                       err_msg=name)
            checked += 1
    # embedding; 4 KDA layers of 2 + 15, 1 MLA of 2 + 5; dense 3, 4 x 7; 2
    assert checked == 1 + 4 * 17 + 7 + 3 + 4 * 7 + 2
    decay = net.read_decay()
    assert len(decay) == 4 and len(net.read_load()) == 4
    assert all(layer["chunks"] == 2 * 2 for layer in decay)
    assert profiler.counters()["mxtpu/linear_attention.chunks"] == 4
    assert profiler.counters()["mxtpu/linear_attention.log_decay_min"] == \
        pytest.approx(decay[-1]["log_decay_min"])


def test_all_four_mixers_and_a_dense_layer_in_one_model(toy, reference):
    """A model of every layer kind MoeLM knows (window, full, linear,
    latent) with a dense first layer: logits, loss and every gradient
    against the two configurations' references joined layer by layer (the
    Mellum2 reference's attention under the Kimi reference's block)."""
    mellum = _module(os.path.join(CONFIGS,
                                  "mellum2_12b_a2.5b_ep8.reference.py"))
    kinds = ["sliding_attention", "linear_attention", "full_attention",
             "latent_attention"]
    mlps = ["dense", "sparse", "sparse", "sparse"]
    rope = {"sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000},
            "full_attention": {"rope_type": "default", "rope_theta": 500000}}
    doc = dict(toy, layer_types=kinds, mlp_layer_types=mlps,
               num_hidden_layers=4, num_key_value_heads=2, head_dim=16,
               sliding_window=16, rope_parameters=rope)
    mx.random.seed(5)
    net = MoeLM(256, kinds, units=64, num_heads=4, num_kv_heads=2,
                head_dim=16, moe_hidden_size=32, num_experts=16, top_k=4,
                held=(4, 4), rope_parameters=rope, sliding_window=16,
                rms_norm_eps=1e-5, mlp_layer_types=mlps, hidden_size=128,
                linear_attention=toy["linear_attn_config"],
                latent_attention=toy,
                router={"scoring": "sigmoid", "selection_bias": True,
                        "scale": 2.446, "shared_hidden_size": 32})
    net.initialize(init=mx.init.Normal(0.05))
    tokens = nd.array(np.random.RandomState(3).randint(
        0, 256, (2, 70)).astype(np.int32))
    with autograd.pause():
        got = net(tokens).jax()
    params = _float32(net)
    eps = doc["rms_norm_eps"]

    def hidden(params, seq):
        x, at = params[0][seq], 1
        for kind, mlp in zip(kinds, mlps):
            grouped = kind in ("sliding_attention", "full_attention")
            n = 4 if grouped else reference.COUNT[kind]
            g1, mixer, g2 = params[at], params[at + 1:at + 1 + n], \
                params[at + 1 + n]
            ffn = params[at + 2 + n:at + 2 + n + reference.COUNT[mlp]]
            h = reference._rms_norm(x, g1, eps)
            if grouped:
                x = x + mellum._attention(doc, kind, h, *mixer, None, None)
            else:
                x = x + getattr(reference, "_" + kind)(
                    doc, h, tuple(mixer), None, None)
            h = reference._rms_norm(x, g2, eps)
            x = x + (reference._gated(h, *ffn, None) if mlp == "dense"
                     else reference._sparse(doc, h, tuple(ffn), None, None))
            at += 2 + n + reference.COUNT[mlp]
        assert at == len(params) - 2
        return reference._rms_norm(x, params[-2], eps) @ params[-1].T

    def loss(params):
        total = 0.0
        for seq in tokens.jax():
            logp = jax.nn.log_softmax(hidden(params, seq)[:-1])
            total -= jnp.take_along_axis(logp, seq[1:, None], -1).sum()
        return total / (2 * 69)

    want = jnp.stack([hidden(params, seq) for seq in tokens.jax()])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    want_loss, grads = jax.value_and_grad(loss)(params)
    from incubator_mxnet_tpu.models.transformer_lm import lm_loss
    step = FusedTrainStep(net, lambda out, y: lm_loss(out, y).mean(),
                          mx.optimizer.create("sgd", learning_rate=1.0))
    assert float(step(tokens, tokens).asscalar()) == pytest.approx(
        float(want_loss), rel=1e-4)
    for (name, p), before, grad in zip(net.collect_params().items(), params,
                                       grads):
        if p.grad_req == "null":
            continue
        moved = np.asarray(before) - np.asarray(p.data().jax(), np.float32)
        scale = float(np.max(np.abs(grad)))
        resolved = np.finfo(np.float32).eps * float(np.max(np.abs(before)))
        np.testing.assert_allclose(moved, np.asarray(grad), rtol=0,
                                   atol=1e-4 * scale + resolved,
                                   err_msg=name)


def test_unknown_layer_kinds_are_errors():
    common = dict(units=32, num_heads=2, num_kv_heads=2, head_dim=16,
                  moe_hidden_size=16, num_experts=4, top_k=2)
    with pytest.raises(ValueError, match="layer_types"):
        MoeLM(64, ["state_space"], **common)
    with pytest.raises(ValueError, match="mlp_layer_types"):
        MoeLM(64, ["full_attention"], mlp_layer_types=["shared"], **common)
    with pytest.raises(ValueError, match="scoring"):
        nn.SparseExperts(32, 16, 4, 2, scoring="tanh")
