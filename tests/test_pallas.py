"""Pallas kernel correctness vs XLA references (interpret mode on CPU).

Mirrors the reference's operator tests for the hand-written attention
kernels (tests/python/unittest/test_operator.py multihead attention cases).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops.pallas import flash_attention, layer_norm


def naive_attention(q, k, v, scale, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        tri = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        s = jnp.where(tri, s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lq,lk,d", [(32, 32, 16), (48, 80, 32)])
def test_flash_forward(causal, lq, lk, d):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 2, lq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, lk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, lk, d).astype(np.float32))
    scale = 1.0 / np.sqrt(d)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    ref = naive_attention(q, k, v, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads(causal):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 32, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 32, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 32, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(1, 2, 32, 16).astype(np.float32))
    scale = 0.25

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                            interpret=True)
        return jnp.sum(o * w)

    def f_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, scale, causal) * w)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_flash_causal_cross_length():
    # bottom-right-aligned causal (decode semantics): query row r sees
    # cols <= r + (lk - lq), matching the XLA path's tril(k=lk-lq)
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 48, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 48, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    scale = 1.0 / np.sqrt(8)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = naive_attention(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, block_q=16, block_k=16, interpret=True) * w),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(naive_attention(*a, scale, True) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_decode_step():
    # single-query causal decode: must attend over the whole KV cache
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(2, 2, 1, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, 33, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, 33, 8).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = naive_attention(q, k, v, 1.0 / np.sqrt(8), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_unaligned_lengths():
    # lengths that need padding to block multiples; padded KV must be masked
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 1, 23, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 1, 37, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 1, 37, 8).astype(np.float32))
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = naive_attention(q, k, v, 1.0 / np.sqrt(8), False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 2, 32, 16)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 2, 32, 16)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 2, 32, 16)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = naive_attention(q, k, v, 0.25, False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=3e-2, atol=3e-2)


def test_layer_norm_kernel():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(6, 33).astype(np.float32))
    g = jnp.asarray(rng.randn(33).astype(np.float32))
    b = jnp.asarray(rng.randn(33).astype(np.float32))

    def ref(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    out = layer_norm(x, g, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, g, b)),
                               rtol=1e-5, atol=1e-5)

    w = jnp.asarray(rng.randn(6, 33).astype(np.float32))
    g1 = jax.grad(lambda *a: jnp.sum(layer_norm(*a, interpret=True) * w),
                  argnums=(0, 1, 2))(x, g, b)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(x, g, b)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_mha_routes_to_flash(monkeypatch):
    # with the force flag, ops.multihead_attention should produce the same
    # values through the pallas path as the XLA path
    monkeypatch.setenv("MXTPU_FORCE_PALLAS", "1")
    from incubator_mxnet_tpu.ops import _raw
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 32, 32).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 32, 32).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 32, 32).astype(np.float32))
    out = _raw.multihead_attention(q, k, v, num_heads=4)
    monkeypatch.delenv("MXTPU_FORCE_PALLAS")
    monkeypatch.setenv("MXTPU_NO_PALLAS", "1")
    ref = _raw.multihead_attention(q, k, v, num_heads=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_enabled_follows_the_tpu_backend(monkeypatch):
    """auto mode: kernels are on exactly when jax's default backend is
    platform 'tpu' — no other platform name, no device-kind guessing."""
    import jax
    from incubator_mxnet_tpu.ops import pallas

    monkeypatch.delenv("MXTPU_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("MXTPU_NO_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas.enabled()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not pallas.enabled()


def test_is_tpu_consistent_across_dispatch_sites(monkeypatch):
    """One definition of "on TPU": on platform 'tpu', enabled() is True
    AND interpret-mode selection sees a real TPU (Mosaic, not interpret)
    AND runtime features report TPU."""
    import jax
    from incubator_mxnet_tpu.ops import pallas
    from incubator_mxnet_tpu.runtime import features

    monkeypatch.delenv("MXTPU_FORCE_PALLAS", raising=False)
    monkeypatch.delenv("MXTPU_NO_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas.is_tpu() and pallas.enabled()
    assert features.Features().is_enabled("TPU")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not pallas.is_tpu()
    assert not features.Features().is_enabled("TPU")


def test_kernel_that_fails_to_compile_raises(monkeypatch):
    """No self-test, no XLA stand-in: with the backend claiming to be a
    TPU the kernel is NOT interpreted, so on this CPU its Mosaic lowering
    fails — and that failure reaches the caller."""
    import jax
    from incubator_mxnet_tpu.ops import pallas

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.ones((16, 256), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(
            pallas.layer_norm(x, jnp.ones(256), jnp.zeros(256)))


