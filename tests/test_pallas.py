"""Pallas kernel correctness vs XLA references (interpret mode on CPU).

Mirrors the reference's operator tests for the hand-written attention
kernels (tests/python/unittest/test_operator.py multihead attention cases).
"""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops.pallas import flash_attention, layer_norm

# the module; the package attribute of that name is the function
fa = importlib.import_module("incubator_mxnet_tpu.ops.pallas.flash_attention")


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


# (lq, lk, d, causal, block_q, block_k, vmem_budget); None = derived
# from the shape. A budget of _TIGHT holds 64 resident positions, so 1024
# keys stream in major blocks of 512, the least one may hold.
_TIGHT = 3 * 4 * 128 * 4 * 64
PARITY = {
    "diagonal-several-query-blocks": (128, 128, 16, True, 32, 16, None),
    "diagonal-inner-wider-than-outer": (96, 96, 16, True, 16, 48, None),
    "offset-lq-below-lk": (48, 112, 16, True, 16, 16, None),
    "offset-derived": (40, 100, 32, True, None, None, None),
    "decode-one-query": (1, 33, 8, True, None, None, None),
    "decode-one-query-blocks-16": (1, 70, 8, True, 16, 16, None),
    "ragged-300-derived": (300, 300, 64, True, None, None, None),
    "ragged-300-blocks-64": (300, 300, 64, True, 64, 64, None),
    "ragged-300-full": (300, 300, 16, False, 64, 32, None),
    "d64-unpadded": (64, 64, 64, False, None, None, None),
    "d80-padded": (40, 40, 80, True, None, None, None),
    "cross-lq-above-lk-full": (80, 48, 16, False, 16, 16, None),
    "streamed-causal": (1024, 1024, 16, True, 128, 128, _TIGHT),
    "streamed-full": (1024, 1024, 16, False, 128, 128, _TIGHT),
    "streamed-offset": (512, 1024, 16, True, 128, 128, _TIGHT),
    "streamed-ragged-derived": (520, 1100, 16, True, None, None, _TIGHT),
}


@pytest.mark.parametrize("case", PARITY)
def test_flash_parity(case):
    """Output and the three gradients against the XLA formulation in
    float32, over the shapes that steer the tiling."""
    lq, lk, d, causal, block_q, block_k, budget = PARITY[case]
    rng = np.random.RandomState(11)
    q, w = (jnp.asarray(rng.randn(1, 2, lq, d).astype(np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, 2, lk, d).astype(np.float32))
            for _ in range(2))
    scale = 1.0 / np.sqrt(d)
    kw = {} if budget is None else {"vmem_budget": budget}

    def f_flash(q, k, v):
        out = fa._attention(q, k, v, causal, None, block_q, block_k, True,
                            **kw)
        return jnp.sum(out * w), out

    def f_ref(q, k, v):
        out = naive_attention(q, k, v, scale, causal)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(f_flash, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, ref), want = jax.value_and_grad(f_ref, (0, 1, 2),
                                        has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# (lq, lk, d, itemsize, interpret, block_q, block_k, budget) -> what the
# plan must say; a name of None is not checked
PLANS = {
    # the benchmark's cell on the chip: K and V resident, 512 x 512 tiles
    "gpt2-cell": ((1024, 1024, 64, 2, False, None, None, None),
                  dict(lqp=1024, lkp=1024, dp=64, bq=512, bk=512,
                       k_major=1024, dkv_bk=512, dkv_bq=512, q_major=1024)),
    # BERT at 128 tokens: one grid step a head
    "bert-128": ((128, 128, 64, 2, False, None, None, None),
                 dict(lqp=128, lkp=128, dp=64, bq=128, bk=128, k_major=128,
                      q_major=128)),
    # ragged on the chip: padded to the 128 lanes, d = 80 to 128
    "ragged-chip": ((300, 300, 80, 2, False, None, None, None),
                    dict(lqp=384, lkp=384, dp=128, bq=384, k_major=384)),
    # interpreted: 16-row alignment, no lane padding of a split of 128
    "ragged-interpreted": ((300, 300, 32, 4, True, None, None, None),
                           dict(lqp=304, lkp=304, dp=32)),
    "d128": ((256, 256, 128, 2, False, None, None, None), dict(dp=128)),
    "d256": ((256, 256, 256, 2, False, None, None, None), dict(dp=256)),
    "decode": ((1, 1000, 64, 2, False, None, None, None),
               dict(lqp=128, lkp=1024, bq=128, k_major=1024)),
    # 9 x 128 keys: blocks divide the padded length, nothing is padded
    # up to a power of two
    "odd-multiple": ((1152, 1152, 64, 2, False, None, None, None),
                     dict(lqp=1152, lkp=1152, bq=384, bk=384)),
    # 47 x 128 tokens: padded to 12 x 512 (2% more), not cut into 128s;
    # resident under the chip's budget (two blocks of 3072 under PR 26's)
    "awkward-long": ((6000, 6000, 64, 2, False, None, None, None),
                     dict(lqp=6144, lkp=6144, bq=512, bk=512, k_major=6144,
                          dkv_bk=512, q_major=6144)),
    "awkward-long-default-grant": (
        (6000, 6000, 64, 2, False, None, None, 12 * 2 ** 20),
        dict(lqp=6144, lkp=6144, bq=512, bk=512, k_major=3072,
             dkv_bk=512, q_major=3072)),
    # just over one block: nothing divides it but 128
    "just-over-a-block": ((520, 520, 64, 2, False, None, None, None),
                          dict(lqp=640, bq=128, k_major=640)),
    # the Mellum2 cell: K and V (Q and dO) of a head resident, the last
    # grid axis one step, backward one kernel
    "mellum2-cell": ((8192, 8192, 128, 2, False, None, None, None),
                     dict(lqp=8192, lkp=8192, dp=128, bq=512, bk=512,
                          k_major=8192, dkv_bk=512, dkv_bq=512,
                          q_major=8192)),
    # what Mosaic's default grant held of it (the parent's plan)
    "mellum2-cell-default-grant": (
        (8192, 8192, 128, 2, False, None, None, 12 * 2 ** 20),
        dict(k_major=4096, q_major=4096, bq=512, bk=512)),
    # the longest sequence that is resident in bf16, at d = 128 and d = 64
    # (a head of 64 takes the 128 lanes of a row all the same) ...
    "longest-resident": ((32768, 32768, 128, 2, False, None, None, None),
                         dict(lqp=32768, k_major=32768, q_major=32768)),
    "longest-resident-d64": ((32768, 32768, 64, 2, False, None, None, None),
                             dict(lqp=32768, k_major=32768, q_major=32768)),
    # ... and the first that streams: 65 blocks of 512, in five major
    # blocks of 13; in float32 half the length
    "first-streamed": ((32769, 32769, 128, 2, False, None, None, None),
                       dict(lqp=33280, k_major=6656, q_major=6656)),
    "longest-resident-float32": (
        (16384, 16384, 128, 4, False, None, None, None),
        dict(k_major=16384, q_major=16384)),
    "first-streamed-float32": (
        (16385, 16385, 128, 4, False, None, None, None),
        dict(lqp=16896, k_major=5632, q_major=5632)),
    # beyond the budget the keys stream, in major blocks of >= 512
    "long": ((65536, 65536, 128, 2, False, None, None, None),
             dict(lqp=65536, k_major=32768, q_major=32768)),
    "overrides": ((100, 100, 16, 4, True, 16, 16, None),
                  dict(lqp=112, lkp=112, bq=16, bk=16, dkv_bk=16,
                       dkv_bq=16, k_major=112)),
    "overrides-on-chip-round-up": ((100, 100, 16, 4, False, 16, 16, None),
                                   dict(lqp=128, bq=128, bk=128)),
    "tight-budget": ((1024, 1024, 16, 4, True, 128, 128, _TIGHT),
                     dict(k_major=512, q_major=512, bq=128)),
}


@pytest.mark.parametrize("case", PLANS)
def test_flash_plan_follows_the_shape(case):
    (lq, lk, d, itemsize, interpret, bq, bk, budget), want = PLANS[case]
    kw = {} if budget is None else {"vmem_budget": budget}
    plan = fa._plan(lq, lk, d, itemsize, interpret, bq, bk, **kw)
    got = {name: getattr(plan, name) for name in want}
    assert got == want
    # every block divides what it tiles
    assert plan.lqp % plan.bq == 0 and plan.lkp % plan.k_major == 0
    assert plan.k_major % plan.bk == 0 and plan.lkp % plan.dkv_bk == 0
    assert plan.lqp % plan.q_major == 0 and plan.q_major % plan.dkv_bq == 0
    assert plan.lqp >= lq and plan.lkp >= lk
    # what the kernels are reckoned to hold, with `_call`'s margin, is
    # inside the core's VMEM whatever the plan
    for need in (plan.fwd_vmem, plan.dq_vmem, plan.dkv_vmem):
        assert 0 < need + need // 4 < fa._VMEM


def _grants(shape, kv_shape=None, **kw):
    """{kernel name: the `vmem_limit_bytes` its pallas_call carries} of a
    bf16 causal attention's value and gradients as the chip would run them
    (traced, not lowered: the CPU has no Mosaic)."""
    import re
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(kv_shape or shape, jnp.bfloat16)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False,
                                       **kw).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, k, k))
    grants = re.findall(r"vmem_limit_bytes=(\w+)", text)
    names = re.findall(r"name=(flash_attention_\w+)", text)
    assert len(grants) == len(names)
    return {n: None if g == "None" else int(g)
            for n, g in zip(names, grants)}


# PR 30's plan at the GPT-2 cell's shape, field for field: the resident
# regime it already ran must not move
GPT2_PLAN = (1024, 1024, 64, 512, 512, 1024, 512, 512, 1024)


def test_flash_plan_and_grant_at_gpt2s_shape_are_the_parents():
    plan = fa._plan(1024, 1024, 64, 2, False)
    assert tuple(plan)[:9] == GPT2_PLAN
    assert plan._fields[:9] == ("lqp", "lkp", "dp", "bq", "bk", "k_major",
                                "dkv_bk", "dkv_bq", "q_major")
    # under Mosaic's default grant nothing is asked: the kernels' compiler
    # parameters are the parent's
    assert _grants((16, 12, 1024, 64)) == {"flash_attention_fwd": None,
                                           "flash_attention_bwd": None}
    assert _grants((32, 12, 128, 64)) == {"flash_attention_fwd": None,
                                          "flash_attention_bwd": None}


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_flash_grant_at_the_mellum2_cells_shape(window):
    """8192 x 128 over grouped heads: two kernels, each asking Mosaic for
    what the plan reckons and a quarter, more than the default and far less
    than the core has."""
    plan = fa._plan(8192, 8192, 128, 2, False)
    assert fa._merged(plan)
    # a window of two blocks of 512: the forward pairs its masked tiles
    cfg = fa._Cfg(1.0, True, 8192, 0, False, plan, window, 8)
    assert fa._paired(cfg) == (window is not None)
    grants = _grants((1, 32, 8192, 128), (1, 4, 8192, 128), window=window)
    assert grants == {
        "flash_attention_fwd": plan.fwd_vmem + plan.fwd_vmem // 4,
        "flash_attention_bwd": plan.dkv_vmem + plan.dkv_vmem // 4}
    assert fa._MOSAIC_DEFAULT < grants["flash_attention_fwd"] < 2 ** 25
    assert fa._MOSAIC_DEFAULT < grants["flash_attention_bwd"] < 2 ** 26


def test_flash_streamed_kernels_ask_for_their_major_blocks():
    """Past the budget three kernels, each granted its own need."""
    plan = fa._plan(33280, 33280, 128, 2, False)
    assert not fa._merged(plan)
    grants = _grants((1, 1, 33280, 128))
    assert set(grants) == {"flash_attention_fwd", "flash_attention_dq",
                           "flash_attention_dkv"}
    for name, need in (("fwd", plan.fwd_vmem), ("dq", plan.dq_vmem),
                       ("dkv", plan.dkv_vmem)):
        assert grants["flash_attention_" + name] == need + need // 4


def test_flash_causal_needs_keys_for_every_query():
    q = jnp.zeros((1, 1, 32, 8))
    with pytest.raises(ValueError, match="more queries than keys"):
        flash_attention(q, q[:, :, :16], q[:, :, :16], causal=True,
                        interpret=True)


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


