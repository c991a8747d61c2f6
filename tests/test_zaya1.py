"""What the ZAYA1 configuration added to models.MoeLM (attention inside a
compressed latent: the value shift, the two causal convolutions, the q-k
mean, the unit norms and rotary positions on part of a head; a top-1 router
that is an MLP whose state travels from layer to layer; a tied table)
against the plain reference of the benchmark's configuration, at toy sizes
in float32 on the CPU, where matrix products are true float32 and only the
order of sums differs."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, ops
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.models import CompressedAttentionCell, MoeLM
from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.parallel import FusedTrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
NAME = "zaya1_8b_ep2"


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "zaya1_" + os.path.basename(path).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _module(os.path.join(CONFIGS, NAME + ".reference.py"))


@pytest.fixture(scope="module")
def toy():
    """The file's own toy sizes: 64 wide, 4 query heads over 2 of 16, 8
    experts of 32 of which 4 are held, a router 16 wide, three layers, 256
    rows of the table; float32."""
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        doc = json.load(f)
    doc.update(doc.pop("rehearse"), dtype="float32")
    return doc


def _float32(net):
    return [jnp.asarray(np.asarray(p.data().jax(), np.float32))
            for p in net.collect_params().values()]


def _stirred(net, seed=5):
    """The parameters that start at a constant moved off it, so that a
    missing temperature, average or bias cannot hide: gamma (zeros), temp
    (ones) and the norms' gains."""
    rng = np.random.RandomState(seed)
    for name, p in net.collect_params().items():
        if name.endswith(("router_gamma", "temp", "gamma")):
            centre = 0.0 if name.endswith("router_gamma") else 1.0
            p.set_data(nd.array((centre + 0.3 * rng.randn(*p.shape)).astype(
                np.float32)))
    return net


@pytest.fixture(scope="module")
def built(toy):
    """(model module, net, tokens, the net's parameters as float32 copies)."""
    model = _module(os.path.join(CONFIGS, NAME + ".py"))
    net = _stirred(model.net(toy, 11))
    tokens, _ = model.batch(toy, {"batch": 2, "seq": 100}, 11)
    with autograd.pause():
        net(tokens)
    return model, net, tokens, _float32(net)


# -- the operations ---------------------------------------------------------

def test_rope_on_part_of_a_head_leaves_the_rest_bit_for_bit():
    """`rope(rotary_dim=64)` on heads of 128: the upper 64 channels of every
    head pass bit for bit, in bfloat16 too; the lower 64 are what `rope`
    gives heads of 64 with the same frequencies; the whole head and no
    `rotary_dim` trace what they traced before."""
    inv_freq, factor = _raw.rope_frequencies(64, rope_theta=5e6)
    assert inv_freq.shape == (32,) and factor == 1.0
    assert inv_freq[1] == pytest.approx(5e6 ** (-1 / 32))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3 * 128))
    for dtype in (jnp.float32, jnp.bfloat16):
        xd = x.astype(dtype)
        got = _raw.rope(xd, inv_freq, 3, factor, rotary_dim=64)
        assert got.dtype == dtype
        heads, turned = xd.reshape(2, 40, 3, 128), got.reshape(2, 40, 3, 128)
        assert jnp.array_equal(turned[..., 64:], heads[..., 64:])
        assert not jnp.array_equal(turned[:, 1:, :, :64], heads[:, 1:, :, :64])
        lower = _raw.rope(heads[..., :64].reshape(2, 40, 3 * 64), inv_freq,
                          3, factor)
        np.testing.assert_array_equal(
            np.asarray(turned[..., :64].astype(jnp.float32)),
            np.asarray(lower.reshape(2, 40, 3, 64).astype(jnp.float32)))
    whole, _ = _raw.rope_frequencies(128, rope_theta=5e6)

    def traced(*more):
        return str(jax.make_jaxpr(
            lambda a: _raw.rope(a, whole, 3, 1.0, *more))(x))
    assert traced() == traced(None) == traced(128)


def test_the_mixing_convolution_is_causal_and_by_head():
    """`head_conv`: y_t of head h is sum_j x_(t - K + 1 + j)[h] w[j, h]; no
    head reads another's channels and no token a later one."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 9, 3 * 4).astype(np.float32)
    w = rng.randn(2, 3, 4, 4).astype(np.float32)
    got = np.asarray(_raw.head_conv(jnp.asarray(x), jnp.asarray(w)))
    heads = np.pad(x, ((0, 0), (1, 0), (0, 0))).reshape(2, 10, 3, 4)
    want = sum(np.einsum("blhc,hcd->blhd", heads[:, j:j + 9], w[j])
               for j in range(2)).reshape(2, 9, 12)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    moved = x.copy()
    moved[:, 5, 4:8] += 1.0             # token 5, head 1
    after = np.asarray(_raw.head_conv(jnp.asarray(moved), jnp.asarray(w)))
    changed = np.abs(after - got).reshape(2, 9, 3, 4).max((0, 3))
    assert (changed[:5] == 0).all() and (changed[7:] == 0).all()
    assert (changed[5:7, 1] > 0).all() and (changed[:, [0, 2]] == 0).all()


def _mixer(toy, seed=3):
    cell = CompressedAttentionCell(
        toy["hidden_size"], toy["num_attention_heads"],
        toy["num_key_value_heads"], toy["head_dim"],
        toy["rope_parameters"]["hybrid"],
        (toy["cca_time0"], toy["cca_time1"]))
    mx.random.seed(seed)
    cell.initialize(init=mx.init.Normal(0.2))
    return _stirred(cell)


def test_the_mixer_is_the_references(toy, reference):
    cell = _mixer(toy)
    x = np.random.RandomState(2).randn(2, 50, 64).astype(np.float32)
    with autograd.pause():
        got = cell(nd.array(x)).asnumpy()
    params = tuple(_float32(cell))
    for rows in (None, 16):
        want = np.stack([np.asarray(reference._mixer(
            toy, jnp.asarray(seq), params, rows, None)) for seq in x])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_shift_and_both_convolutions_are_causal(toy):
    """A change at token t moves nothing before t and every token from t
    on: the value shift reads t - 1, the convolutions t - 1 and t,
    attention the tokens up to its own."""
    cell = _mixer(toy)
    x = np.random.RandomState(4).randn(1, 40, 64).astype(np.float32)
    moved = x.copy()
    moved[0, 17] += 1.0
    with autograd.pause():
        before = cell(nd.array(x)).asnumpy()
        after = cell(nd.array(moved)).asnumpy()
    assert np.array_equal(before[0, :17], after[0, :17])
    assert np.abs(before[0, 17:] - after[0, 17:]).max(-1).min() > 0


# -- the router -------------------------------------------------------------

def _layer(held=(0, 8), average=True, seed=7):
    layer = nn.SparseExperts(64, 32, 8, 1, held=held, norm_topk_prob=False,
                             selection_bias=True, router_hidden_size=16,
                             previous=average)
    mx.random.seed(seed)
    layer.initialize(init=mx.init.Normal(0.3))
    if average:
        layer.router_gamma.set_data(nd.array(
            np.random.RandomState(seed).randn(16).astype(np.float32)))
    return layer


def test_the_weight_is_the_chosen_experts_probability():
    """One expert a token: the weight is softmax's own value for it, not 1,
    so the router has a gradient; the load counts one assignment a token."""
    layer = _layer(average=False)
    x = nd.array(np.random.RandomState(0).randn(2, 30, 64).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y, state = layer(x)
        total = (y * y).sum()
    total.backward()
    assert state.shape == (2, 30, 16)
    assert int(layer.load.data().asnumpy().sum()) == 60
    grads = {name: p.grad().asnumpy()
             for name, p in layer.collect_params().items()
             if p.grad_req != "null"}
    for name in ("router_down", "router_w1", "router_w2", "router_w3"):
        assert np.abs(grads[next(n for n in grads if n.endswith(name))]
                      ).max() > 0, name
    with pytest.raises(StopIteration):
        next(n for n in grads if n.endswith("router_gamma"))
    logits, _ = ops.router_mlp(x, None, *(
        p.data() for p in layer._router[:1]), None,
        *(p.data() for p in layer._router[1:]))
    prob = jax.nn.softmax(logits.jax().reshape(60, 8), -1)
    normalised, _ = ops.sparse_experts(
        x, None, layer.gate.data(), layer.up.data(), layer.down.data(), 1,
        0, True, "softmax", layer.bias.data(), 1.0, logits)
    ratio = (y.asnumpy().reshape(60, 64)
             / normalised.asnumpy().reshape(60, 64))
    np.testing.assert_allclose(ratio, np.broadcast_to(
        np.asarray(prob.max(-1))[:, None], (60, 64)), rtol=2e-4)


def test_a_layers_choice_follows_the_layer_befores_state():
    """Layer l's choice changes when layer l-1's `W_down` does and gamma_l
    is not zero; with gamma_l zero, or no state handed over, it does not
    look at it."""
    first, second = _layer(average=False, seed=1), _layer(seed=2)
    x = nd.array(np.random.RandomState(3).randn(1, 200, 64).astype(np.float32))

    def choice(state):
        with autograd.record(train_mode=True):
            second(x, state)
        load = second.load.data().asnumpy().copy()
        return load

    with autograd.pause():
        _, state = first(x)
        first._router[0].set_data(first._router[0].data() * -1.5)
        _, other = first(x)
    assert np.abs(state.asnumpy() - other.asnumpy()).max() > 0.1
    assert (choice(state) != choice(other)).any()
    alone = choice(None)
    second.router_gamma.set_data(nd.zeros((16,)))
    assert (choice(state) == choice(other)).all()
    assert (choice(state) == alone).all()


def test_the_two_shares_add_up_to_the_uncut_layer(toy, reference):
    """16 experts in the deployment's 2 shares of 8 (here 8 in 2 of 4): the
    parts that the two holders give, summed, equal what the uncut layer
    gives (the reference holding all of them); the router, whole on both,
    hands on the same state."""
    x = np.random.RandomState(5).randn(2, 24, 64).astype(np.float32)
    before = np.random.RandomState(6).randn(2, 24, 16).astype(np.float32)
    whole = _layer((0, 8))
    values = {name[len(whole.prefix):]: p.data().asnumpy()
              for name, p in whole.collect_params().items()}

    def share(first, count):
        layer = _layer((first, count))
        for name, p in layer.collect_params().items():
            name = name[len(layer.prefix):]
            value = values[name]
            if name in ("gate", "up", "down"):
                value = value[first:first + count]
            p.set_data(nd.array(value))
        return layer

    total, states = np.zeros_like(x), []
    for first in (0, 4):
        with autograd.pause():
            y, state = share(first, 4)(nd.array(x), nd.array(before))
        total += y.asnumpy()
        states.append(state.asnumpy())
    np.testing.assert_array_equal(states[0], states[1])
    doc = dict(toy, num_experts_held={"first": 0, "count": 8})
    params = tuple(jnp.asarray(values[name]) for name in (
        "router_down", "router_w1", "router_w2", "router_w3", "router_gamma",
        "gate", "up", "down", "load", "bias"))
    want, want_state = zip(*(reference._experts(
        doc, jnp.asarray(seq), params, jnp.asarray(r), None)
        for seq, r in zip(x, before)))
    np.testing.assert_allclose(total, np.stack(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(states[0], np.stack(want_state), rtol=1e-4,
                               atol=1e-5)
    with autograd.pause():
        uncut, _ = whole(nd.array(x), nd.array(before))
    np.testing.assert_allclose(uncut.asnumpy(), np.stack(want), rtol=1e-4,
                               atol=1e-5)


def test_the_linear_routers_are_traced_as_before():
    """The routers of one matrix trace what they traced before `logits=`
    came, and an MLP router's logits route as a matrix's would."""
    rng = np.random.RandomState(4)
    router, gate, up, down = (jnp.asarray(rng.randn(*shape) * 0.3,
                                          jnp.float32)
                              for shape in ((8, 64), (8, 64, 32), (8, 64, 32),
                                            (8, 32, 64)))
    x = jnp.asarray(rng.randn(2, 12, 64), jnp.float32)
    y, load = _raw.sparse_experts(x, router, gate, up, down, 2)
    same, same_load = _raw.sparse_experts(
        x, None, gate, up, down, 2, logits=jnp.dot(
            x, router.T, preferred_element_type=jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(same), rtol=1e-6)
    assert jnp.array_equal(load, same_load)
    text = str(jax.make_jaxpr(lambda *a: _raw.sparse_experts(*a, 2))(
        x, router, gate, up, down))
    assert "erf" not in text and text.count("dot_general") >= 1


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
    the weight itself; the tied table's is the sum of the lookup's and the
    head's. The counters move and the selection bias does not."""
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
            assert after.sum() == 2 * 100
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
    # the table; 3 layers of 2 norms + 7 + 7 and, behind the first, a gamma;
    # the last norm
    assert checked == 1 + 3 * 16 + 2 + 1
    assert len(net.read_load()) == 3


def test_the_tied_tables_gradient_sums_both_uses(toy, built, reference):
    """The table's gradient is the lookup's plus the head's: with the head
    held apart (the table passed twice to a reference that reads rows from
    one and logits from the other) the two parts add up to it."""
    _, _, tokens, params = built
    seq = tokens.jax()

    def apart(lookup, head):
        with jax.default_matmul_precision("highest"):
            x = reference._hidden(toy, [lookup, *params[1:]], seq[0], None,
                                  None)[:-1]
            logp = jax.nn.log_softmax(reference._rms_norm(
                x, params[-1], toy["rms_norm_eps"]) @ head.T)
            return -jnp.take_along_axis(
                logp, seq[0, 1:, None].astype(jnp.int32), -1).mean()
    g_lookup, g_head = jax.grad(apart, (0, 1))(params[0], params[0])
    _, grads = reference.loss_and_grads(toy, params, seq[:1], seq[:1])
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    np.testing.assert_allclose(np.asarray(g_lookup + g_head),
                               np.asarray(grads[0]), rtol=1e-4, atol=1e-7)


def test_one_adam_update_is_the_references(toy, reference):
    """The configuration's own optimizer, one update from the seed's
    weights: every trained parameter lands where the reference's Adam puts
    it from the reference's gradient (the step is the rate times g / (|g|
    + 1e-8): the gradient's sign where it is large, so an entry whose
    gradient is rounding noise may land on the other side, and less than
    the rate in a router whose gradients are of 1e-9 here: all but a
    thousandth of the entries agree to a tenth of the rate)."""
    model = _module(os.path.join(CONFIGS, NAME + ".py"))
    doc = dict(toy, optimizer={"name": "adam", "learning_rate": 1e-3})
    net = _stirred(model.net(doc, 13))
    tokens, _ = model.batch(doc, {"batch": 2, "seq": 64}, 13)
    with autograd.pause():
        net(tokens)
    params = _float32(net)
    _, grads = reference.loss_and_grads(doc, params, tokens.jax(),
                                        tokens.jax())
    want = reference.adam_step(doc, params, grads)
    step = FusedTrainStep(net, model.loss(doc), model.optimizer(doc))
    step(tokens, tokens).wait_to_read()
    for (name, p), before, after in zip(net.collect_params().items(), params,
                                        want):
        if p.grad_req == "null":
            continue
        moved = np.asarray(p.data().jax(), np.float32) - np.asarray(before)
        wanted = np.asarray(after - before)
        assert 0 < np.abs(moved).max() < 1.001e-3, name
        assert np.mean(np.abs(moved - wanted) > 1e-4) < 1e-3, name


def test_a_router_state_only_where_the_router_has_one():
    """Models whose router is one matrix hand nothing from layer to layer:
    their cells map x to x as before."""
    common = dict(units=32, num_heads=2, num_kv_heads=2, head_dim=16,
                  moe_hidden_size=16, num_experts=4, top_k=2,
                  rope_parameters={"full_attention": {"rope_theta": 1e4},
                                   "hybrid": {"rope_theta": 1e4,
                                              "partial_rotary_factor": 0.5}})
    plain = MoeLM(64, ["full_attention"] * 2, **common)
    plain.initialize()
    x = nd.array(np.random.RandomState(0).randn(1, 8, 32).astype(np.float32))
    assert isinstance(plain.layers[0](x), nd.NDArray)
    assert plain.head is not None
    carried = MoeLM(64, ["hybrid"] * 2, **common,
                    compressed_attention={"cca_time0": 2, "cca_time1": 2},
                    router={"router_hidden_size": 8},
                    tie_word_embeddings=True)
    carried.initialize()
    out, state = carried.layers[0](x)
    assert out.shape == x.shape and state.shape == (1, 8, 8)
    assert carried.head is None
    assert not hasattr(carried.layers[0].ffn, "router")
    assert carried.layers[0].ffn.router_gamma is None
    assert carried.layers[1].ffn.router_gamma.shape == (8,)
    tokens = nd.array(np.zeros((1, 8), np.int32))
    assert carried(tokens).shape == (1, 8, 64)


def test_unknown_layer_kinds_name_the_kinds_built():
    common = dict(units=32, num_heads=2, num_kv_heads=2, head_dim=16,
                  moe_hidden_size=16, num_experts=4, top_k=2)
    with pytest.raises(ValueError, match="'hybrid'") as raised:
        MoeLM(64, ["state_space"], **common)
    for kind in ("sliding_attention", "full_attention", "linear_attention",
                 "latent_attention"):
        assert kind in str(raised.value)
    with pytest.raises(ValueError, match="compressed_attention="):
        MoeLM(64, ["hybrid"], **common)
