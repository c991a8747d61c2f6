"""tp/pp/sp/ep parallelism tests on the 8-virtual-device CPU mesh
(SURVEY.md §2.22, §4: parity of distributed vs single-device math)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.parallel import (
    make_mesh, ring_attention, ring_self_attention, pipeline_apply,
    moe_ffn, MoEFFN, annotate_bert_tp, FusedTrainStep)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _ref_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5)
    if causal:
        L = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# ---------------------------------------------------------------------------
# sp: ring attention
# ---------------------------------------------------------------------------

class TestRingAttention:
    def test_matches_dense(self):
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(2, 4, 64, 16), jnp.float32)
                   for _ in range(3))
        out = ring_attention(q, k, v, mesh, "sp")
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_causal_matches_dense(self):
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
                   for _ in range(3))
        out = ring_attention(q, k, v, mesh, "sp", causal=True)
        ref = _ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_grad_matches_dense(self):
        mesh = make_mesh({"sp": 4})
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(1, 2, 16, 8), jnp.float32)
                   for _ in range(3))

        g_ring = jax.grad(lambda a, b, c: ring_attention(
            a, b, c, mesh, "sp").sum())(q, k, v)
        g_ref = jax.grad(lambda a, b, c: _ref_attention(a, b, c).sum())(q, k, v)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                                   rtol=1e-3, atol=1e-3)

    def test_jit_sharded_inputs(self):
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(2, 2, 128, 16), jnp.float32)
                   for _ in range(3))
        spec = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh, "sp",
                                                     causal=True))(qs, ks, vs)
        ref = _ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_ring_self_attention_block(self):
        mesh = make_mesh({"sp": 4})
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(2, 32, 16), jnp.float32)
        wqkv = jnp.asarray(rng.randn(16, 48) * 0.1, jnp.float32)
        wo = jnp.asarray(rng.randn(16, 16) * 0.1, jnp.float32)
        out = ring_self_attention(x, wqkv, wo, 4, mesh, "sp")
        q, k, v = jnp.split(x @ wqkv, 3, -1)

        def heads(t):
            return t.reshape(2, 32, 4, 4).transpose(0, 2, 1, 3)
        ref = _ref_attention(heads(q), heads(k), heads(v))
        ref = ref.transpose(0, 2, 1, 3).reshape(2, 32, 16) @ wo
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# pp: pipeline
# ---------------------------------------------------------------------------

class TestPipeline:
    def _stage(self, params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def _stack(self, rng, n, d):
        return {"w": jnp.asarray(rng.randn(n, d, d) * 0.3, jnp.float32),
                "b": jnp.asarray(rng.randn(n, d) * 0.1, jnp.float32)}

    def test_matches_sequential(self):
        mesh = make_mesh({"pp": 4})
        rng = np.random.RandomState(0)
        params = self._stack(rng, 4, 8)
        x = jnp.asarray(rng.randn(16, 8), jnp.float32)
        y = pipeline_apply(self._stage, params, x, mesh, axis="pp", n_micro=4)
        ref = x
        for s in range(4):
            ref = self._stage({"w": params["w"][s], "b": params["b"][s]}, ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_more_microbatches_than_stages(self):
        mesh = make_mesh({"pp": 2})
        rng = np.random.RandomState(1)
        params = self._stack(rng, 2, 4)
        x = jnp.asarray(rng.randn(24, 4), jnp.float32)
        y = pipeline_apply(self._stage, params, x, mesh, axis="pp", n_micro=8)
        ref = x
        for s in range(2):
            ref = self._stage({"w": params["w"][s], "b": params["b"][s]}, ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_grad_flows(self):
        mesh = make_mesh({"pp": 4})
        rng = np.random.RandomState(2)
        params = self._stack(rng, 4, 8)
        x = jnp.asarray(rng.randn(8, 8), jnp.float32)

        def loss_pp(p):
            return pipeline_apply(self._stage, p, x, mesh,
                                  axis="pp", n_micro=4).sum()

        def loss_seq(p):
            h = x
            for s in range(4):
                h = self._stage({"w": p["w"][s], "b": p["b"][s]}, h)
            return h.sum()

        g_pp = jax.grad(loss_pp)(params)
        g_seq = jax.grad(loss_seq)(params)
        np.testing.assert_allclose(np.asarray(g_pp["w"]),
                                   np.asarray(g_seq["w"]),
                                   rtol=1e-4, atol=1e-4)

    def test_shape_change_rejected(self):
        mesh = make_mesh({"pp": 2})
        params = {"w": jnp.zeros((2, 4, 6))}
        with pytest.raises(ValueError, match="preserve activation shape"):
            pipeline_apply(lambda p, x: x @ p["w"], params,
                           jnp.zeros((8, 4)), mesh, axis="pp")


# ---------------------------------------------------------------------------
# ep: mixture of experts
# ---------------------------------------------------------------------------

class TestMoE:
    @pytest.mark.slow
    def test_top1_routes_to_best_expert(self):
        # gate that deterministically prefers expert = token % E
        e, d = 4, 8
        layer = MoEFFN(e, d, 16, top_k=1, capacity_factor=4.0)
        params = layer.init(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(2, 12, d), jnp.float32)
        y, aux = layer(params, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert 0.0 < float(aux) < 10.0  # balance loss ~1 near uniform routing

    def test_capacity_drops_tokens(self):
        # all tokens prefer expert 0; capacity 1 keeps only the first
        d, e = 4, 2
        gate_w = jnp.zeros((d, e)).at[:, 0].set(5.0)
        w1 = jnp.ones((e, d, 4)) * 0.1
        b1 = jnp.zeros((e, 4))
        w2 = jnp.ones((e, 4, d)) * 0.1
        b2 = jnp.zeros((e, d))
        x = jnp.ones((1, 4, d))
        y, _ = moe_ffn(x, gate_w, w1, b1, w2, b2, top_k=1,
                       capacity_factor=0.5)  # cap = 1
        y = np.asarray(y)
        assert np.abs(y[0, 0]).sum() > 0          # first token served
        assert np.abs(y[0, 2:]).sum() == 0        # overflow tokens dropped

    def test_ep_sharded_matches_local(self):
        mesh = make_mesh({"ep": 8})
        layer = MoEFFN(8, 16, 32, top_k=2)
        params = layer.init(jax.random.PRNGKey(1))
        x = jnp.asarray(np.random.RandomState(1).randn(2, 16, 16), jnp.float32)
        y_local, aux_local = layer(params, x)
        sharded = {k: jax.device_put(v, NamedSharding(mesh, s))
                   for (k, v), s in zip(params.items(),
                                        layer.shardings().values())}
        y_ep, aux_ep = jax.jit(layer)(sharded, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_local), rtol=1e-5)

    def test_grad_flows(self):
        layer = MoEFFN(4, 8, 16, top_k=2)
        params = layer.init(jax.random.PRNGKey(2))
        x = jnp.asarray(np.random.RandomState(2).randn(1, 8, 8), jnp.float32)
        g = jax.grad(lambda p: layer(p, x)[0].sum())(params)
        assert float(jnp.abs(g["w1"]).sum()) > 0
        assert float(jnp.abs(g["gate_w"]).sum()) > 0


# ---------------------------------------------------------------------------
# sp: long-context BERT on ring attention
# ---------------------------------------------------------------------------

class TestBERTRingAttention:
    def _build(self, ring):
        from incubator_mxnet_tpu.models.bert import BERTModel
        mx.random.seed(0)
        np.random.seed(0)
        return BERTModel(num_layers=2, units=16, hidden_size=32, num_heads=2,
                         max_length=64, vocab_size=40, dropout=0.0,
                         use_pooler=False, ring=ring)

    def test_matches_dense_attention(self):
        mesh = make_mesh({"sp": 8})
        ids = np.random.RandomState(0).randint(0, 40, (2, 64))
        net_d = self._build(None)
        net_d.initialize()
        seq_d = net_d(nd.array(ids)).asnumpy()
        net_r = self._build((mesh, "sp"))
        net_r.initialize()   # same seeds -> same init
        seq_r = net_r(nd.array(ids)).asnumpy()
        np.testing.assert_allclose(seq_r, seq_d, rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_ring_bert_trains_fused(self):
        mesh = make_mesh({"sp": 8})
        net = self._build((mesh, "sp"))
        head = gluon.nn.Dense(4, flatten=False, in_units=16)
        full = gluon.nn.HybridSequential()
        full.add(net)
        full.add(head)
        full.initialize()
        step = FusedTrainStep(full, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.create("adam", learning_rate=1e-2),
                              mesh=None)
        ids = nd.array(np.random.RandomState(1).randint(0, 40, (2, 64)))
        y = nd.array(np.random.RandomState(2).randint(0, 4, (2, 64)))
        l0 = float(step(ids, y))
        for _ in range(5):
            l = float(step(ids, y))
        assert np.isfinite(l) and l < l0

    def test_mask_rejected(self):
        mesh = make_mesh({"sp": 4})
        net = self._build((mesh, "sp"))
        net.initialize()
        ids = nd.array(np.zeros((1, 32), np.int32))
        vl = nd.array(np.array([10]))
        with pytest.raises(ValueError, match="ring attention"):
            net(ids, None, vl)


# ---------------------------------------------------------------------------
# tp: tensor parallel BERT
# ---------------------------------------------------------------------------

class TestTensorParallel:
    @pytest.mark.slow
    def test_bert_tp_dp_step_matches_single(self):
        """FusedTrainStep on a dp×tp mesh == single-device step (same math,
        XLA inserts the Megatron collectives)."""
        from incubator_mxnet_tpu.models.bert import BERTModel

        def build():
            mx.random.seed(0)
            np.random.seed(0)
            bert = BERTModel(num_layers=2, units=32, hidden_size=64,
                             num_heads=4, max_length=32, vocab_size=50,
                             dropout=0.0, use_pooler=True)
            net = gluon.nn.HybridSequential()
            net.add(bert)

            class Head(gluon.nn.HybridBlock):
                def __init__(self):
                    super().__init__()
                    self.out = gluon.nn.Dense(2, in_units=32)

                def forward(self, seq_pooled):
                    return self.out(seq_pooled[1])
            net.add(Head())
            net.initialize()
            return net, bert

        ids = np.random.RandomState(0).randint(0, 50, (8, 16))
        y = np.random.RandomState(1).randint(0, 2, 8)
        L = gluon.loss.SoftmaxCrossEntropyLoss()

        losses = {}
        for mode in ("single", "tp"):
            net, bert = build()
            if mode == "tp":
                annotate_bert_tp(bert)
                mesh = make_mesh({"dp": 2, "tp": 4})
            else:
                mesh = None
            step = FusedTrainStep(net, L, mx.optimizer.create(
                "sgd", learning_rate=0.1), mesh=mesh)
            ls = [float(step(nd.array(ids), nd.array(y))) for _ in range(3)]
            losses[mode] = ls
        np.testing.assert_allclose(losses["tp"], losses["single"],
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_attention_long_context_8k():
    """Long-context evidence: exact ring attention at 8192 tokens sharded
    over 8 devices matches dense attention (within bf16-free fp32
    tolerance) — per-device memory is O(L/n)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from incubator_mxnet_tpu.parallel import make_mesh, ring_attention

    mesh = make_mesh({"sp": 8})
    L, D = 8192, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, L, D), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, L, D), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, L, D), jnp.float32)
    spec = NamedSharding(mesh, P(None, None, "sp", None))
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))

    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh, "sp",
                                                 causal=True))(qs, ks, vs)
    # dense reference on a SLICE of query rows (full dense is O(L^2) host
    # memory); rows from the middle and the end cross shard boundaries
    rows = np.r_[0:64, 4080:4144, L - 64:L]
    scale = 1.0 / np.sqrt(D)
    qr = np.asarray(q)[0, 0][rows]
    scores = (qr @ np.asarray(k)[0, 0].T) * scale            # (R, L)
    mask = rows[:, None] >= np.arange(L)[None, :]            # causal
    scores = np.where(mask, scores, -1e30)
    p = np.exp(scores - scores.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    expected = p @ np.asarray(v)[0, 0]
    np.testing.assert_allclose(np.asarray(out)[0, 0][rows], expected,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# sp: Ulysses all-to-all sequence parallelism
# ---------------------------------------------------------------------------

class TestUlyssesAttention:
    def test_matches_dense(self):
        from incubator_mxnet_tpu.parallel import ulysses_attention
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(10)
        q, k, v = (jnp.asarray(rng.randn(2, 8, 64, 16), jnp.float32)
                   for _ in range(3))
        out = ulysses_attention(q, k, v, mesh, "sp")
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_causal_matches_ring(self):
        from incubator_mxnet_tpu.parallel import (ring_attention,
                                                  ulysses_attention)
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(11)
        q, k, v = (jnp.asarray(rng.randn(1, 8, 32, 8), jnp.float32)
                   for _ in range(3))
        out_u = ulysses_attention(q, k, v, mesh, "sp", causal=True)
        out_r = ring_attention(q, k, v, mesh, "sp", causal=True)
        np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_r),
                                   rtol=1e-4, atol=1e-4)

    def test_grad_matches_dense(self):
        from incubator_mxnet_tpu.parallel import ulysses_attention
        mesh = make_mesh({"sp": 4})
        rng = np.random.RandomState(12)
        q, k, v = (jnp.asarray(rng.randn(1, 4, 16, 8), jnp.float32)
                   for _ in range(3))
        g_u = jax.grad(lambda a, b, c: ulysses_attention(
            a, b, c, mesh, "sp").sum())(q, k, v)
        g_ref = jax.grad(lambda a, b, c: _ref_attention(a, b, c).sum())(
            q, k, v)
        np.testing.assert_allclose(np.asarray(g_u), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_heads_not_divisible_rejected(self):
        from incubator_mxnet_tpu.parallel import ulysses_attention
        mesh = make_mesh({"sp": 8})
        q = jnp.zeros((1, 4, 64, 8), jnp.float32)   # 4 heads < sp=8
        with pytest.raises(ValueError):
            ulysses_attention(q, q, q, mesh, "sp")

    def test_self_attention_block(self):
        from incubator_mxnet_tpu.parallel import ulysses_self_attention
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(13)
        d, heads = 32, 8
        x = jnp.asarray(rng.randn(2, 64, d), jnp.float32)
        wqkv = jnp.asarray(rng.randn(d, 3 * d) * 0.05, jnp.float32)
        wo = jnp.asarray(rng.randn(d, d) * 0.05, jnp.float32)
        out = ulysses_self_attention(x, wqkv, wo, heads, mesh, "sp")
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()


class TestBERTUlysses:
    def test_matches_dense_attention(self):
        from incubator_mxnet_tpu.models.bert import BERTModel

        def build(ring):
            mx.random.seed(0)
            np.random.seed(0)
            return BERTModel(num_layers=2, units=16, hidden_size=32,
                             num_heads=8, max_length=64, vocab_size=40,
                             dropout=0.0, use_pooler=False, ring=ring)

        mesh = make_mesh({"sp": 8})
        ids = np.random.RandomState(0).randint(0, 40, (2, 64))
        net_d = build(None)
        net_d.initialize()
        seq_d = net_d(nd.array(ids)).asnumpy()
        net_u = build((mesh, "sp", "ulysses"))
        net_u.initialize()   # same seeds -> same init
        seq_u = net_u(nd.array(ids)).asnumpy()
        np.testing.assert_allclose(seq_u, seq_d, rtol=2e-4, atol=2e-4)


class TestRunK:
    def _build_net(self):
        mx.random.seed(0)
        np.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(4, in_units=16))
        net.initialize(init=mx.init.Xavier())
        return net

    def test_run_k_matches_sequential_steps(self):
        """k micro-steps inside one lax.scan program == k separate
        dispatched steps (same math, k× fewer dispatches)."""
        rng = np.random.RandomState(0)
        xs = rng.randn(4, 8, 8).astype(np.float32)
        ys = rng.randint(0, 4, (4, 8))
        L = gluon.loss.SoftmaxCrossEntropyLoss()

        net1 = self._build_net()
        s1 = FusedTrainStep(net1, L, mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9), mesh=None)
        seq_losses = [float(s1(nd.array(xs[i]), nd.array(ys[i])))
                      for i in range(4)]

        net2 = self._build_net()
        s2 = FusedTrainStep(net2, L, mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9), mesh=None)
        k_losses = s2.run_k(xs, ys).asnumpy()

        np.testing.assert_allclose(k_losses, seq_losses, rtol=1e-5,
                                   atol=1e-6)
        # paired in the order the blocks made them: a name carries a
        # process-wide counter, and sorted names pair the wrong parameters
        # where that counter passes a power of ten inside one net
        params1 = list(net1.collect_params().values())
        params2 = list(net2.collect_params().values())
        assert len(params1) == len(params2) == 4
        for p1, p2 in zip(params1, params2):
            np.testing.assert_allclose(p2.data().asnumpy(),
                                       p1.data().asnumpy(),
                                       rtol=1e-5, atol=1e-6)

    def test_run_k_on_dp_mesh(self):
        """run_k under a dp mesh: batches shard over dp, k axis stays on
        host order; losses finite and params update."""
        mesh = make_mesh({"dp": 8})
        rng = np.random.RandomState(1)
        xs = rng.randn(3, 16, 8).astype(np.float32)
        ys = rng.randint(0, 4, (3, 16))
        net = self._build_net()
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        step = FusedTrainStep(net, L, mx.optimizer.create(
            "sgd", learning_rate=0.1), mesh=mesh)
        before = {n: p.data().asnumpy().copy()
                  for n, p in net.collect_params().items()}
        losses = step.run_k(xs, ys).asnumpy()
        assert losses.shape == (3,) and np.isfinite(losses).all()
        changed = any(not np.allclose(p.data().asnumpy(), before[n])
                      for n, p in net.collect_params().items())
        assert changed, "run_k did not update parameters"
        # mixing run_k and single steps keeps working
        l4 = float(step(nd.array(xs[0]), nd.array(ys[0])))
        assert np.isfinite(l4)

    def test_run_k_accepts_list_of_batches(self):
        rng = np.random.RandomState(2)
        batches = [(nd.array(rng.randn(8, 8).astype(np.float32)),
                    nd.array(rng.randint(0, 4, 8))) for _ in range(2)]
        net = self._build_net()
        step = FusedTrainStep(net,
                              gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.create("sgd", learning_rate=0.05))
        losses = step.run_k([b[0] for b in batches],
                            [b[1] for b in batches]).asnumpy()
        assert losses.shape == (2,) and np.isfinite(losses).all()
