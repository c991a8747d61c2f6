"""models.MoeLM and its blocks (RMS norm, rotary positions, grouped heads
with a window, dropless sparse experts that hold a share) against the plain
reference of the benchmark's Mellum2 configuration, at toy sizes in float32
on the CPU, where matrix products are true float32 and only the order of
sums differs: 1e-4 (a bfloat16 pass is off by 1e-3 to 4e-2)."""
import functools
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
    # 40 assignments are fewer than the 128 rows a first rung has: one rung
    assert layer.read_load() == {"live_rows": 40,
                                 "load_max_over_mean": pytest.approx(4.0),
                                 "row_capacity": 40}


# -- (f) the ladder of row capacities ---------------------------------------
# 512 tokens, top-2 of 16 experts, experts 4 and 5 held: 1024 assignments,
# an even share of 128, rungs of 256 and 1024 rows

LADDER = {"tokens": 512, "d": 64, "f": 32, "experts": 16, "top_k": 2,
          "first": 4, "count": 2}


def _forced_routing(live, seed=5):
    """(x, router, gate, up, down) float32 whose top-2 routing sends exactly
    `live` of the 1024 assignments to the held experts 4 and 5: x carries
    its two experts in its first 16 entries, which the router reads."""
    c = LADDER
    rng = np.random.RandomState(seed)
    both = max(live - c["tokens"], 0)        # tokens with both choices held
    one = live - 2 * both                    # tokens with one
    picks = np.empty((c["tokens"], 2), np.int64)
    others = [e for e in range(c["experts"]) if e not in (4, 5)]
    for t in range(c["tokens"]):
        if t < both:
            picks[t] = (4, 5)
        elif t < both + one:
            picks[t] = (4 + t % 2, others[t % len(others)])
        else:
            picks[t] = (others[t % len(others)], others[(t + 3) % len(others)])
    picks = picks[rng.permutation(c["tokens"])]
    x = rng.randn(c["tokens"], c["d"]).astype(np.float32) * 0.3
    x[:, :c["experts"]] = 0.0
    x[np.arange(c["tokens"]), picks[:, 0]] = 3.0
    x[np.arange(c["tokens"]), picks[:, 1]] = 2.5
    router = rng.randn(c["experts"], c["d"]).astype(np.float32) * 0.02
    router[:, :c["experts"]] = np.eye(c["experts"], dtype=np.float32)
    gate, up = (rng.randn(c["count"], c["d"], c["f"]).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.randn(c["count"], c["f"], c["d"]).astype(np.float32) * 0.2
    return tuple(jnp.asarray(a) for a in (x, router, gate, up, down))


def _against_the_dense_reference(toy, reference, operands, live):
    """y, load and the five gradients of `_raw.sparse_experts` against the
    dense float32 reference, under one cotangent."""
    c = LADDER
    doc = dict(toy, num_experts_per_tok=c["top_k"], norm_topk_prob=True,
               num_experts_held={"first": c["first"], "count": c["count"]})
    w = jnp.asarray(np.random.RandomState(9).randn(c["tokens"], c["d"]),
                    jnp.float32)

    def ours(*operands):
        y, load = _raw.sparse_experts(*operands, c["top_k"], c["first"])
        return jnp.sum(y * w), (y, load)

    def dense(*operands):
        y = reference._experts(doc, *operands, None)
        return jnp.sum(y * w), y
    every = tuple(range(5))
    (_, (y, load)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=every, has_aux=True))(*operands)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        dense, argnums=every, has_aux=True))(*operands)
    assert int(load.sum()) == c["tokens"] * c["top_k"]
    assert int(load[4] + load[5]) == live
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    for name, got, ref in zip(("x", "router", "gate", "up", "down"),
                              grads, want_grads):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=2e-5,
                                   err_msg=name)
    return load


@pytest.fixture
def fresh_traces():
    """The rungs are traced once for every call of their shapes (an inner
    `jit`): a test that swaps `_raw.grouped_matmul` under them starts and
    ends with no trace kept."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("live,capacity", [
    (0, 256), (200, 256), (255, 256), (256, 256), (257, 1024), (512, 1024),
    (1024, 1024)], ids=["none", "under-the-first", "one-under", "on-the-first",
                        "one-over", "twice-the-first", "every-assignment"])
def test_each_rung_of_the_ladder_is_the_dense_reference(
        toy, reference, monkeypatch, fresh_traces, live, capacity):
    """The routing forced to each side of the first rung, from no
    assignment held to every one: the program runs on the smallest buffer
    that holds the live rows (seen from inside the grouped product, which
    only the branch taken calls), and on each the result, `load` and every
    gradient are the dense reference's."""
    c = LADDER
    assert _raw.row_capacities(c["tokens"] * c["top_k"], c["count"],
                               c["experts"]) == (256, 1024)
    product, walked = _raw.grouped_matmul, []

    def watched(lhs, rhs, group_sizes, kernel):
        jax.debug.callback(lambda _: walked.append(lhs.shape[0]),
                           group_sizes[0])
        return product(lhs, rhs, group_sizes, kernel)
    monkeypatch.setattr(_raw, "grouped_matmul", watched)
    operands = _forced_routing(live)
    load = _against_the_dense_reference(toy, reference, operands, live)
    jax.effects_barrier()
    # three products forward; in the backward the first two made again and
    # the third, which its pull is taken from
    assert walked == [capacity] * 6
    # and the host reads the same rung from the same function
    layer = nn.SparseExperts(c["d"], c["f"], c["experts"], c["top_k"],
                             held=(c["first"], c["count"]))
    layer.initialize()
    layer.load.set_data(nd.array(np.asarray(load), dtype="int32"))
    assert layer.read_load()["row_capacity"] == capacity
    assert profiler.counters()["mxtpu/moe.row_capacity"] == capacity


def test_rows_beyond_the_groups_may_hold_anything(toy, reference,
                                                  monkeypatch, fresh_traces):
    """A grouped product that leaves NaN beyond its groups, in the result
    and in the rows' gradient (the Mosaic kernel leaves what the buffer
    held): the layer masks those rows where it reads them, so the result
    and every gradient stay finite and equal to the dense reference's."""
    product = _raw.grouped_matmul

    def beyond(sizes, rows):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def poisoned(lhs, rhs, group_sizes, kernel):
        return forward(lhs, rhs, group_sizes, kernel)[0]

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
    _against_the_dense_reference(toy, reference, _forced_routing(200), 200)


def _subjaxprs(jaxpr):
    """Every jaxpr nested in `jaxpr`'s equations, itself first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _subjaxprs(inner)


def _conds(jaxpr):
    return [eqn for sub in _subjaxprs(jaxpr) for eqn in sub.eqns
            if eqn.primitive.name == "cond"]


@pytest.mark.parametrize("held,rungs", [((4, 2), 2), ((0, 16), 1)],
                         ids=["a-share", "every-expert"])
def test_the_traced_program_walks_no_full_buffer_below_the_top_rung(held,
                                                                    rungs):
    """The forward and the VJP of `sparse_experts` each hold ONE `cond` with
    a branch a rung, and no branch but the last has a value of tokens x
    top_k rows as wide as the experts' inner size, let alone the hidden
    one (what passes between the two has no rung's shape: the operands
    themselves). A holder of every expert has no `cond` at all."""
    c = LADDER
    rows = c["tokens"] * c["top_k"]
    x, router, gate, up, down = _forced_routing(200)
    gate, up, down = (jnp.resize(a, (held[1],) + a.shape[1:])
                      for a in (gate, up, down))

    def layer(*operands):
        return _raw.sparse_experts(*operands, c["top_k"], held[0])[0]
    operands = (x, router, gate, up, down)
    y, pull = jax.vjp(layer, *operands)
    for program in (jax.make_jaxpr(layer)(*operands),
                    jax.make_jaxpr(pull)(y)):
        conds = _conds(program.jaxpr)
        if rungs == 1:
            assert not conds
            continue
        cond, = conds
        assert len(cond.params["branches"]) == rungs
        for branch in cond.params["branches"][:-1]:
            checked = 0
            for sub in _subjaxprs(branch.jaxpr):
                for var in (*sub.invars, *sub.outvars,
                            *(v for e in sub.eqns for v in e.outvars)):
                    shape = getattr(var.aval, "shape", ())
                    checked += len(shape) == 2
                    assert not (len(shape) == 2 and shape[0] == rows
                                and shape[1] >= c["f"]), (shape, sub)
            assert checked > 20     # the rung's body was walked, not a stub


def test_a_holder_of_every_expert_makes_no_product_again(monkeypatch,
                                                         fresh_traces):
    """With every expert held the row buffers may fill, so no capacity is
    chosen: the layer is PR 28's program, whose backward finds the rows and
    the experts' products where autodiff kept them. Three grouped products
    run in a step (the rungs run six: they keep nothing and make two
    again), each over all tokens x top_k rows."""
    c = LADDER
    x, router, gate, up, down = _forced_routing(200)
    gate, up, down = (jnp.resize(a, (c["experts"],) + a.shape[1:])
                      for a in (gate, up, down))
    product, walked = _raw.grouped_matmul, []

    def watched(lhs, rhs, group_sizes, kernel):
        jax.debug.callback(lambda _: walked.append(lhs.shape[0]),
                           group_sizes[0])
        return product(lhs, rhs, group_sizes, kernel)
    monkeypatch.setattr(_raw, "grouped_matmul", watched)

    def scalar(*operands):
        return jnp.sum(_raw.sparse_experts(*operands, c["top_k"])[0])
    grads = jax.jit(jax.grad(scalar, argnums=(0, 1, 2, 3, 4)))(
        x, router, gate, up, down)
    jax.block_until_ready(grads)
    jax.effects_barrier()
    assert walked == [c["tokens"] * c["top_k"]] * 3


def _primitives(jaxpr):
    return {eqn.primitive.name for sub in _subjaxprs(jaxpr)
            for eqn in sub.eqns}


@pytest.mark.parametrize("devices,kernels", [(1, True), (2, False)],
                         ids=["one-device", "mesh-of-two"])
def test_the_backward_runs_the_product_its_forward_chose(
        monkeypatch, fresh_traces, devices, kernels):
    """The Mosaic kernel or `ragged_dot` is chosen ONCE, in the forward,
    under the scopes of whoever traces it: jax runs the backward rule after
    `select.partitioned(mesh)` has closed (parallel/trainer_step.py), and a
    kernel chosen there would sit inside a program that GSPMD partitions,
    which jax refuses to lower. With the kernels forced on, forward and VJP
    traced under a mesh of two hold none, on either rung; on one device both
    hold them. The rungs are traced once for their shapes AND that choice."""
    from incubator_mxnet_tpu.ops import select
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    c = LADDER
    x, router, gate, up, down = _forced_routing(200)
    # 128 wide: the kernel qualifies (rows, contraction and columns % 128)
    x, router = (jnp.resize(a, (a.shape[0], 128)) for a in (x, router))
    gate, up = (jnp.resize(a, (c["count"], 128, 128)) for a in (gate, up))
    down = jnp.resize(down, (c["count"], 128, 128))

    def layer(*operands):
        return _raw.sparse_experts(*operands, c["top_k"], c["first"])[0]
    operands = (x, router, gate, up, down)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("dp",))
    before = dict(profiler.counters())
    with select.partitioned(mesh):
        y, pull = jax.vjp(layer, *operands)
        forward = jax.make_jaxpr(layer)(*operands)
    # the scope has closed, as it has when jax transposes a step's loss
    backward = jax.make_jaxpr(pull)(y)
    for program in (forward, backward):
        found = _primitives(program.jaxpr)
        assert ("pallas_call" in found) == kernels, found
        assert ("ragged_dot_general" in found) != kernels, found
    moved = {k.split("/")[-1]: v - before.get(k, 0)
             for k, v in profiler.counters().items()
             if "grouped_matmul" in k and v != before.get(k, 0)}
    # one decision a traced layer, counted where it was made
    assert moved == {("pallas.selected.grouped_matmul" if kernels else
                      "pallas.rejected.grouped_matmul"): 2}, moved


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
