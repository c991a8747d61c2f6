"""What the JoyAI-LLM-Flash configuration added to models.MoeLM (latent
attention through a query rank with rotated positions on the queries' and
the shared keys' last channels, adjacent pairs together; a multi-token-
prediction module that shares the embedding and the head, whose loss joins
the step's) against the plain reference of the benchmark's configuration,
at toy sizes in float32 on the CPU, where matrix products are true float32
and only the order of sums differs."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.models import MTP, LatentAttentionCell, MoeLM
from incubator_mxnet_tpu.models import moe_lm
from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.parallel import FusedTrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
NAME = "joyai_llm_flash_ep32"
L = 48


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "joyai_" + os.path.basename(path).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _module(os.path.join(CONFIGS, NAME + ".reference.py"))


@pytest.fixture(scope="module")
def model():
    return _module(os.path.join(CONFIGS, NAME + ".py"))


@pytest.fixture(scope="module")
def toy():
    """The file's own toy sizes: 64 wide, 4 heads of 16 + 8 through a query
    rank of 48 and a latent of 32 + 8, 16 experts top-4 of which 4 are held,
    a shared expert, the dense layer, four expert layers and the MTP
    module's; float32."""
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        doc = json.load(f)
    doc.update(doc.pop("rehearse"), dtype="float32")
    return doc


def _float32(net):
    return [jnp.asarray(np.asarray(p.data().jax(), np.float32))
            for p in net.collect_params().values()]


@pytest.fixture
def built(toy, model):
    """(net, tokens, the net's parameters as float32 copies)."""
    net = model.net(toy, 11)
    tokens, _ = model.batch(toy, {"batch": 2, "seq": L}, 11)
    return net, tokens, _float32(net)


# -- the rotation -----------------------------------------------------------

def test_the_interleaved_rotation_is_the_complex_pair_form(reference):
    """ops.rope(interleaved=True) turns channels (2j, 2j + 1) together by
    frequency j, as the reference's complex product of adjacent pairs does,
    and writes them de-interleaved: the same order for q and k, so q . k is
    the reference's. Rotating halves (HF's default) is another function."""
    rng = np.random.RandomState(3)
    q = rng.randn(2, 40, 3 * 8).astype(np.float32)
    k = rng.randn(2, 40, 8).astype(np.float32)
    inv_freq, factor = _raw.rope_frequencies(8, rope_theta=3.2e7)
    got_q = np.asarray(_raw.rope(jnp.asarray(q), inv_freq, 3, factor,
                                 interleaved=True)).reshape(2, 40, 3, 8)
    got_k = np.asarray(_raw.rope(jnp.asarray(k), inv_freq, 1, factor,
                                 interleaved=True)).reshape(2, 40, 1, 8)
    want_q = np.stack([np.asarray(reference._rotated(
        jnp.asarray(seq.reshape(40, 3, 8)), 3.2e7)) for seq in q])
    want_k = np.stack([np.asarray(reference._rotated(
        jnp.asarray(seq.reshape(40, 1, 8)), 3.2e7)) for seq in k])
    apart = [0, 2, 4, 6, 1, 3, 5, 7]            # evens, then odds
    np.testing.assert_allclose(got_q, want_q[..., apart], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_k, want_k[..., apart], rtol=1e-5,
                               atol=1e-5)
    scores = np.einsum("bqhd,bkhd->bhqk", got_q, got_k)
    np.testing.assert_allclose(scores, np.einsum(
        "bqhd,bkhd->bhqk", want_q, want_k), rtol=1e-4, atol=1e-4)
    halves = np.asarray(_raw.rope(jnp.asarray(q), inv_freq, 3, factor))
    assert np.max(np.abs(halves.reshape(got_q.shape) - got_q)) > 0.1
    # position 0 is not turned at all
    np.testing.assert_allclose(got_q[:, 0], q.reshape(2, 40, 3, 8)[:, 0][
        ..., apart], rtol=1e-6)


# -- the blocks against the reference ---------------------------------------

def test_the_latent_cell_with_a_query_rank_and_rotations_is_the_references(
        toy, reference):
    """q through its rank and its norm, the last 8 channels of every query
    head and the shared key part rotated by pairs: the cell against the
    reference's mixer, and the parameters in the reference's order."""
    x = np.random.RandomState(7).randn(2, 40, 64).astype(np.float32)
    cell = LatentAttentionCell(
        64, toy["num_attention_heads"], toy["kv_lora_rank"],
        toy["qk_nope_head_dim"], toy["qk_rope_head_dim"], toy["v_head_dim"],
        toy["rms_norm_eps"], q_rank=toy["q_lora_rank"],
        rope={"rope_type": "default", "rope_theta": toy["rope_theta"]},
        interleaved=True)
    cell.initialize(init=mx.init.Normal(0.3))
    assert [p.shape for p in cell.collect_params().values()] == [
        (48, 64), (48,), (4 * 24, 48), (32 + 8, 64), (32,), (4 * 32, 32),
        (64, 4 * 16)]
    with autograd.pause():
        got = cell(nd.array(x)).asnumpy()
    params = tuple(_float32(cell))
    want = np.stack([np.asarray(reference._latent_attention(
        toy, jnp.asarray(seq), params, None, None)) for seq in x])
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(np.max(np.abs(want))))


def test_the_shares_with_a_bias_and_a_scale_add_up_to_the_whole(toy,
                                                                reference):
    """16 experts in 4 shares of 4, a shared expert on every holder, a
    selection bias that moves the choice and the weights x 2.5: the routed
    parts that the shares give, summed, and the shared expert counted ONCE,
    equal what the uncut layer gives (the reference holding all 16)."""
    rng = np.random.RandomState(4)
    router = rng.randn(16, 64).astype(np.float32) * 0.5
    gate, up = (rng.randn(16, 64, 32).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.randn(16, 32, 64).astype(np.float32) * 0.2
    shared = [rng.randn(*shape).astype(np.float32) * 0.2
              for shape in ((64, 32), (64, 32), (32, 64))]
    bias = rng.randn(16).astype(np.float32)
    x = np.random.RandomState(5).randn(2, 24, 64).astype(np.float32)

    def block(first, count):
        layer = nn.SparseExperts(64, 32, 16, 4, held=(first, count),
                                 scoring="sigmoid", selection_bias=True,
                                 scale=toy["routed_scaling_factor"],
                                 shared_hidden_size=32)
        layer.initialize()
        for p, value in ((layer.router, router), (layer.bias, bias),
                         (layer.gate, gate[first:first + count]),
                         (layer.up, up[first:first + count]),
                         (layer.down, down[first:first + count]),
                         (layer.shared.gate, shared[0]),
                         (layer.shared.up, shared[1]),
                         (layer.shared.down, shared[2])):
            p.set_data(nd.array(value))
        return layer

    alike = np.stack([np.asarray(reference._gated(
        jnp.asarray(seq), *map(jnp.asarray, shared), None)) for seq in x])
    total = alike.copy()
    for first in range(0, 16, 4):
        # a holder's result, less what every holder computes alike
        total += block(first, 4)(nd.array(x)).asnumpy() - alike
    whole = dict(toy, num_experts_held={"first": 0, "count": 16})
    params = tuple(jnp.asarray(a) for a in (
        router, gate, up, down, np.zeros(16), bias, *shared))
    want = np.stack([np.asarray(reference._sparse(
        whole, jnp.asarray(seq), params, None, None)) for seq in x])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    unbiased = tuple(jnp.zeros(16) if i == 5 else a
                     for i, a in enumerate(params))
    assert np.max(np.abs(want - np.stack([np.asarray(reference._sparse(
        whole, jnp.asarray(seq), unbiased, None, None)) for seq in x]))) > 1e-3


# -- the model --------------------------------------------------------------

def test_both_sets_of_logits_and_the_summed_loss_are_the_references(
        toy, model, built, reference):
    net, tokens, params = built
    with autograd.pause():
        got = net(tokens).jax()
    want = reference.logits(toy, params, tokens.jax())
    assert got.shape == want.shape == (2, L, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    with autograd.record():
        main, ahead = net(tokens)
    np.testing.assert_allclose(np.asarray(main.jax()), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ahead.jax()),
        np.asarray(reference.mtp_logits(toy, params, tokens.jax())),
        rtol=1e-4, atol=1e-5)
    loss = float(model.loss(toy)((main, ahead), tokens).asscalar())
    want_loss = float(reference.loss(toy, params, tokens.jax(),
                                     tokens.jax()))
    assert loss == pytest.approx(want_loss, rel=1e-5)
    # the MTP's share: 0.3 x its own mean over L - 2 positions
    alone = float(model.loss(toy)(main, tokens).asscalar())
    from incubator_mxnet_tpu.models.transformer_lm import lm_loss
    mtp = float(lm_loss(ahead, tokens, shift=2).mean().asscalar())
    assert loss == pytest.approx(alone + 0.3 * mtp, rel=1e-6)
    # in row blocks, a layer at a time in backward: the same numbers
    blocked = jax.jit(lambda p: reference.loss(
        toy, p, tokens.jax(), tokens.jax(), rows=20))(params)
    assert float(blocked) == pytest.approx(want_loss, rel=1e-5)


def test_every_gradient_and_an_adam_update_are_the_references(
        toy, model, built, reference):
    """One FusedTrainStep of plain SGD at rate 1: a weight's change is minus
    its gradient, held to the reference's float32 gradient of the SUMMED
    loss (the table's and the head's gradients sum both uses). Then one
    step of Adam from a fresh net: each weight's change against the
    reference's Adam on the reference's gradient. The counters of all five
    expert layers move, the selection biases do not."""
    net, tokens, params = built
    want_loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        toy, p, tokens.jax(), tokens.jax(), rows=20))(params)
    step = FusedTrainStep(net, model.loss(toy),
                          mx.optimizer.create("sgd", learning_rate=1.0))
    loss = float(step(tokens, tokens).asscalar())
    assert loss == pytest.approx(float(want_loss), rel=1e-4)
    checked = 0
    for (name, p), before, want in zip(net.collect_params().items(),
                                       params, grads):
        after = np.asarray(p.data().jax(), np.float32)
        if name.endswith("load"):
            assert after.sum() == 2 * L * 4
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
    # the table; the dense layer's 2 + 7 + 3, four expert layers' 2 + 7 + 7;
    # the last norm and the head; the MTP's norms and eh_proj, its expert
    # layer, its head norm
    assert checked == 1 + 12 + 4 * 16 + 2 + 3 + 16 + 1 == 99
    assert len(net.read_load()) == 5

    doc = dict(toy, optimizer={"name": "adam", "learning_rate": 1e-3})
    fresh = model.net(doc, 11)
    FusedTrainStep(fresh, model.loss(doc), model.optimizer(doc))(
        tokens, tokens)
    moved = reference.adam_step(doc, params, grads)
    for (name, p), before, want in zip(fresh.collect_params().items(),
                                       params, moved):
        if p.grad_req == "null":
            continue
        got = np.asarray(p.data().jax(), np.float32) - np.asarray(before)
        want = np.asarray(want) - np.asarray(before)
        # Adam's first step is the rate times the gradient's sign: a few
        # entries whose gradient is rounding noise may turn either way
        assert np.linalg.norm(got - want) <= 0.05 * np.linalg.norm(want), \
            name


def test_a_token_moves_no_mtp_logit_before_the_one_ahead_of_it(built):
    """The MTP's position i reads tokens 0..i+1: changing token t moves its
    logits at t - 1 and after, none before."""
    net, tokens, _ = built
    changed = tokens.asnumpy().copy()
    t = 30
    changed[:, t] = (changed[:, t] + 1) % 256
    with autograd.record():
        before = net(tokens)[1].asnumpy()
        after = net(nd.array(changed))[1].asnumpy()
    moved = np.max(np.abs(after - before), axis=(0, 2))
    assert not moved[:t - 1].any()
    assert moved[t - 1] > 1e-4 and moved[t:].min() > 0


def test_the_pad_id_moves_no_loss(toy, model, built, monkeypatch):
    """Position L - 1 has no token after it; the id that stands in is seen
    by no other position and its loss is left out."""
    net, tokens, _ = built

    def loss():
        with autograd.record():
            return float(model.loss(toy)(net(tokens), tokens).asscalar())
    with_zero = loss()
    monkeypatch.setattr(moe_lm.nd, "zeros_like",
                        lambda x: nd.ones_like(x) * 97)
    assert loss() == with_zero
    with autograd.record():
        assert net(tokens)[1].shape == (2, L, 256)


def test_predict_mode_returns_one_array_and_trains_nothing(built):
    net, tokens, params = built
    net.hybridize()
    with autograd.pause():
        out = net(tokens)
    assert isinstance(out, nd.NDArray) and out.shape == (2, L, 256)
    # the MTP module did not run: no counter moved
    assert all(not np.asarray(p.data().jax()).any()
               for name, p in net.collect_params().items()
               if name.endswith("load"))
    for before, after in zip(params, _float32(net)):
        np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


def test_every_parameter_is_shaped_without_a_forward(toy, model):
    """A predict-mode forward never enters the MTP module, so nothing of it
    may wait for one to learn its shape."""
    net = model.net(toy, 3)
    shapes = {name: p.shape for name, p in net.collect_params().items()}
    assert all(shape and 0 not in shape for shape in shapes.values())
    mtp = {name for name in net.mtp.collect_params()}
    assert len(mtp) == 3 + 2 + 7 + 9 + 1
    assert len(shapes) == 1 + (2 + 7 + 3) + 4 * (2 + 7 + 9) + 2 + len(mtp)
    assert isinstance(net.mtp, MTP) and net.mtp.name.startswith("mtp")


@pytest.mark.parametrize("change", [
    {"n_group": 8}, {"num_nextn_predict_layers": 2},
    {"rope_scaling": {"type": "yarn", "factor": 4}},
    {"tie_word_embeddings": True}])
def test_what_the_configuration_cannot_build_is_refused(toy, model, change):
    with pytest.raises(ValueError):
        model.net(dict(toy, **change), 1)


def test_moe_lm_builds_one_mtp_depth_or_none():
    with pytest.raises(ValueError, match="one MTP depth"):
        MoeLM(32, ["full_attention"], 16, 2, 2, 8, 8, 4, 2,
              rope_parameters={"full_attention": {"rope_theta": 1e4}},
              num_nextn_predict_layers=2)
    net = MoeLM(32, ["full_attention"], 16, 2, 2, 8, 8, 4, 2,
                rope_parameters={"full_attention": {"rope_theta": 1e4}})
    assert net.mtp is None
