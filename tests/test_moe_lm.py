"""models.MoeLM and its blocks (RMS norm, rotary positions, grouped heads
with a window, dropless sparse experts that hold a share) against the plain
reference of the benchmark's Mellum2 configuration, at toy sizes in float32
on the CPU, where matrix products are true float32 and only the order of
sums differs: 1e-4 (a bfloat16 pass is off by 1e-3 to 4e-2)."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.models import GroupedQueryAttentionCell, MoeLM
from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.parallel import FusedTrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
NAME = "mellum2_12b_a2.5b_ep8"


def _module(path):
    spec = importlib.util.spec_from_file_location(
        "moe_lm_" + os.path.basename(path).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _module(os.path.join(CONFIGS, NAME + ".reference.py"))


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(CONFIGS, NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy(published):
    """The file's own toy sizes: 64 wide, 8 heads over 2 key/value heads of
    16, window 16, 16 experts top-4 of which 4 are held (share 1 of 4), one
    period of 4 layers, 256 rows of the vocabulary; float32."""
    doc = dict(published)
    doc.update(doc.pop("rehearse"), dtype="float32")
    return doc


@pytest.fixture(scope="module")
def built(toy):
    """(model module, net, tokens, the net's parameters as float32 copies)."""
    model = _module(os.path.join(CONFIGS, NAME + ".py"))
    net = model.net(toy, 11)
    tokens, _ = model.batch(toy, {"batch": 2, "seq": 64}, 11)
    with autograd.pause():
        net(tokens)
    params = [jnp.asarray(np.asarray(p.data().jax(), np.float32))
              for p in net.collect_params().values()]
    return model, net, tokens, params


# -- (a) the model against the reference ------------------------------------

def test_logits_and_loss_are_the_references(toy, built, reference):
    model, net, tokens, params = built
    with autograd.pause():
        got = net(tokens).jax()
    want = reference.logits(toy, params, tokens.jax())
    assert got.shape == want.shape == (2, 64, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    with autograd.pause():
        loss = model.loss(toy)(net(tokens), tokens).asscalar()
    assert float(loss) == pytest.approx(
        float(reference.loss(toy, params, tokens.jax(), tokens.jax())),
        rel=1e-4)
    # in row blocks, a layer at a time in backward: the same numbers
    blocked = jax.jit(lambda p: reference.loss(
        toy, p, tokens.jax(), tokens.jax(), rows=16))(params)
    assert float(blocked) == pytest.approx(float(loss), rel=1e-5)


def test_every_gradient_is_the_references(toy, built, reference):
    """One FusedTrainStep of plain SGD at rate 1: a weight's change is
    minus its gradient. Held to the reference's float32 gradient at 1e-4
    of the parameter's largest gradient entry (sums in another order),
    plus what float32 can resolve of the weight itself: a gain of 1.0 that
    moves by 6e-5 is read back to 6e-8."""
    model, net, tokens, params = built
    want_loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        toy, p, tokens.jax(), tokens.jax(), rows=32))(params)
    step = FusedTrainStep(net, model.loss(toy),
                          mx.optimizer.create("sgd", learning_rate=1.0))
    loss = float(step(tokens, tokens).asscalar())
    assert loss == pytest.approx(float(want_loss), rel=1e-4)
    checked = 0
    for (name, p), before, want in zip(net.collect_params().items(),
                                       params, grads):
        after = np.asarray(p.data().jax(), np.float32)
        if name.endswith("load"):
            # the counter: every token's 4 assignments, over all 16 experts
            assert after.dtype == np.float32 and after.sum() == 2 * 64 * 4
            continue
        got = np.asarray(before) - after
        scale = float(np.max(np.abs(want)))
        assert scale > 0, name
        resolved = np.finfo(np.float32).eps * float(np.max(np.abs(before)))
        np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                                   atol=1e-4 * scale + resolved,
                                   err_msg=name)
        checked += 1
    assert checked == 1 + 4 * 10 + 2


def test_load_stays_an_integer_under_cast(toy):
    model = _module(os.path.join(CONFIGS, NAME + ".py"))
    net = model.net(dict(toy, dtype="bfloat16"), 3)
    kinds = {name.rsplit("_", 1)[-1].lstrip("0123456789"): p.data().dtype
             for name, p in net.collect_params().items()}
    assert kinds["load"] == np.int32
    assert all(dtype == jnp.bfloat16 for kind, dtype in kinds.items()
               if kind != "load")


# -- (b) the shares add up --------------------------------------------------

def test_the_shares_of_the_expert_layer_add_up_to_the_whole(toy, reference):
    """16 experts in 4 shares of 4: every share routes over all 16 with the
    same router and computes its own experts' part; the parts, summed,
    equal what the uncut layer gives (the reference holding all 16)."""
    rng = np.random.RandomState(4)
    d, f, experts, top_k = 64, 32, 16, 4
    x = rng.randn(2, 24, d).astype(np.float32)
    router = rng.randn(experts, d).astype(np.float32) * 0.5
    gate, up = (rng.randn(experts, d, f).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.randn(experts, f, d).astype(np.float32) * 0.2

    def block(first, count):
        layer = nn.SparseExperts(d, f, experts, top_k, held=(first, count))
        layer.initialize()
        for p, value in ((layer.router, router),
                         (layer.gate, gate[first:first + count]),
                         (layer.up, up[first:first + count]),
                         (layer.down, down[first:first + count])):
            p.set_data(nd.array(value))
        return layer

    total = np.zeros_like(x)
    for first in range(0, experts, 4):
        total += block(first, 4)(nd.array(x)).asnumpy()
    whole = dict(toy, num_experts_held={"first": 0, "count": experts})
    want = np.stack([np.asarray(reference._experts(
        whole, jnp.asarray(seq), jnp.asarray(router), jnp.asarray(gate),
        jnp.asarray(up), jnp.asarray(down), None)) for seq in x])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # and one holder of everything gives the same
    np.testing.assert_allclose(block(0, experts)(nd.array(x)).asnumpy(),
                               want, rtol=1e-4, atol=1e-5)


# -- (d) rotary tables ------------------------------------------------------

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


def test_default_rotary_frequencies():
    inv_freq, factor = _raw.rope_frequencies(128, "default", 500000)
    assert factor == 1.0 and inv_freq.shape == (64,)
    for j in (0, 1, 31, 63):
        assert inv_freq[j] == pytest.approx(500000 ** (-2 * j / 128),
                                            rel=1e-12)


def test_yarn_frequencies_by_hand(published, reference):
    """HF's `_compute_yarn_parameters` at the published section: dim(r) =
    128 ln(8192 / (2 pi r)) / (2 ln 500000) gives low = floor(18.07) = 18
    and high = ceil(34.97) = 35; below `low` the frequency is the default's,
    from `high` on it is divided by 16, with a linear ramp between."""
    section = published["rope_parameters"]["full_attention"]
    assert section == YARN
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    inv_freq, factor = _raw.rope_frequencies(128, **section)
    assert factor == 1.2772588722239782
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    extra = [500000 ** (-2 * j / 128) for j in range(64)]
    assert inv_freq[0] == pytest.approx(1.0, rel=1e-12)
    assert inv_freq[low] == pytest.approx(extra[low], rel=1e-12)
    assert inv_freq[high] == pytest.approx(extra[high] / 16, rel=1e-12)
    assert inv_freq[63] == pytest.approx(extra[63] / 16, rel=1e-12)
    j = 27                                    # on the ramp: (27 - 18) / 17
    ramp = (j - low) / (high - low)
    assert inv_freq[j] == pytest.approx(
        extra[j] / 16 * ramp + extra[j] * (1 - ramp), rel=1e-12)
    # the reference writes the same table down on its own
    theirs, their_factor = reference.inv_freq(128, section)
    np.testing.assert_allclose(inv_freq, theirs, rtol=1e-12)
    assert their_factor == factor


def test_rope_rotates_pairs_and_scales_by_the_factor():
    """Position p turns the pair (x_j, x_{j + half}) by p * inv_freq_j, and
    the factor multiplies cos and sin: norms grow by it, position 0 by
    nothing else."""
    rng = np.random.RandomState(0)
    x = rng.randn(1, 5, 2 * 8).astype(np.float32)       # 2 heads of 8
    inv_freq = np.array([1.0, 0.1, 0.01, 0.001])
    got = np.asarray(_raw.rope(jnp.asarray(x), inv_freq, 2, factor=1.5))
    np.testing.assert_allclose(got[0, 0], 1.5 * x[0, 0], rtol=1e-6)
    head = x[0, 3, 8:]                                   # position 3, head 1
    for j in range(4):
        c, s = math.cos(3 * inv_freq[j]), math.sin(3 * inv_freq[j])
        assert got[0, 3, 8 + j] == pytest.approx(
            1.5 * (head[j] * c - head[j + 4] * s), abs=1e-5)
        assert got[0, 3, 8 + 4 + j] == pytest.approx(
            1.5 * (head[j + 4] * c + head[j] * s), abs=1e-5)
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               1.5 * np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_unknown_rope_type_is_an_error():
    with pytest.raises(ValueError, match="rope_type"):
        _raw.rope_frequencies(64, "longrope")


def test_rms_norm_statistic_is_float32():
    x = jnp.asarray(np.random.RandomState(1).randn(4, 256) * 300,
                    jnp.bfloat16)
    gamma = jnp.full((256,), 2.0, jnp.bfloat16)
    got = _raw.rms_norm(x, gamma, 1e-6)
    assert got.dtype == jnp.bfloat16
    xf = np.asarray(x, np.float32)
    want = xf / np.sqrt((xf * xf).mean(-1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2)


# -- (e) dropless -----------------------------------------------------------

def test_every_token_to_one_held_expert_loses_none(toy, reference):
    """A router that sends every token's first choice to expert 5 (held)
    and spreads the rest: the held expert gets all T tokens, more than any
    capacity a balanced routing would grant, the output still equals the
    dense reference, and `load` reads T there."""
    rng = np.random.RandomState(8)
    d, f, experts, top_k = 64, 32, 16, 4
    tokens = 2 * 40
    x = np.abs(rng.randn(2, 40, d)).astype(np.float32)  # positive entries
    router = rng.randn(experts, d).astype(np.float32) * 0.01
    router[5] = 1.0                                      # wins every token
    layer = nn.SparseExperts(d, f, experts, top_k, held=(4, 4))
    layer.initialize(init=mx.init.Normal(0.2))
    layer.router.set_data(nd.array(router))
    with autograd.record():
        got = layer(nd.array(x))
    load = layer.load.data().asnumpy()
    assert load.dtype == np.int32
    assert load[5] == tokens and load.sum() == tokens * top_k
    doc = dict(toy, num_experts_held={"first": 4, "count": 4})
    weights = [jnp.asarray(p.data().jax()) for p in
               (layer.router, layer.gate, layer.up, layer.down)]
    want = np.stack([np.asarray(reference._experts(
        doc, jnp.asarray(seq), *weights, None)) for seq in x])
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-4, atol=1e-5)
    read = layer.read_load()
    assert read["live_rows"] == int(load[4:8].sum()) >= tokens
    assert read["load_max_over_mean"] == pytest.approx(tokens / top_k
                                                       / (tokens / 16))
    assert profiler.counters()["mxtpu/moe.live_rows"] == read["live_rows"]


def test_every_assignment_held_fills_every_row(toy):
    """All top_k choices of every token on held experts: the row buffers
    are full, T x top_k live rows, and nothing is dropped."""
    d, f, experts, top_k = 32, 16, 8, 2
    layer = nn.SparseExperts(d, f, experts, top_k, held=(2, 2))
    layer.initialize(init=mx.init.Normal(0.2))
    router = np.zeros((experts, d), np.float32)
    router[2], router[3] = 1.0, 0.9
    layer.router.set_data(nd.array(router))
    x = nd.array(np.abs(np.random.RandomState(1).randn(1, 20, d))
                 .astype(np.float32))
    with autograd.record():
        layer(x)
    assert layer.read_load() == {"live_rows": 40,
                                 "load_max_over_mean": pytest.approx(4.0)}


def test_held_must_lie_inside_the_experts():
    with pytest.raises(ValueError, match="held"):
        nn.SparseExperts(8, 8, 4, 2, held=(2, 4))


# -- blocks -----------------------------------------------------------------

def test_attention_cell_window_and_grouped_heads(toy, reference):
    """The cell alone against the reference's attention: 8 heads over 2
    key/value heads, YaRN on a full layer and default rotary with window 16
    on a sliding one."""
    rng = np.random.RandomState(6)
    x = rng.randn(48, 64).astype(np.float32)
    for kind, window in (("full_attention", None),
                         ("sliding_attention", 16)):
        cell = GroupedQueryAttentionCell(
            64, 8, 2, 16, rope=toy["rope_parameters"][kind], window=window)
        cell.initialize(init=mx.init.Normal(0.2))
        weights = [jnp.asarray(b.weight.data().jax())
                   for b in (cell.q, cell.k, cell.v, cell.proj)]
        want = reference._attention(toy, kind, jnp.asarray(x), *weights,
                                    None, None)
        got = cell(nd.array(x[None])).asnumpy()[0]
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4,
                                   atol=1e-5)


def test_model_builds_layer_by_layer_type_with_an_untied_head():
    net = MoeLM(64, ["full_attention", "sliding_attention"], 32, 4, 2, 8,
                moe_hidden_size=16, num_experts=4, top_k=2, sliding_window=4)
    net.initialize(init=mx.init.Normal(0.02))
    tokens = nd.array(np.arange(24, dtype=np.int32).reshape(2, 12))
    assert net(tokens).shape == (2, 12, 64)
    assert [cell.attention._window for cell in net.layers] == [None, 4]
    assert net.head.weight.shape == net.embedding.weight.shape == (64, 32)
    assert net.head.weight is not net.embedding.weight
    assert len(net.read_load()) == 2
    with pytest.raises(ValueError, match="layer_types"):
        MoeLM(64, ["linear_attention"], 32, 4, 2, 8, 16, 4, 2)
