"""`window=` and grouped key/value heads of the flash-attention kernels
(interpreted) against the XLA branch of `ops/_raw.multihead_attention`,
whose band mask is the definition: output and dq / dk / dv, with K/V
resident and streamed, blocks smaller than, equal to and larger than the
window, lengths that are no multiple of the block, and `window=None`
bit-equal to the kernel without the argument."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.ops import select as _select

fa = importlib.import_module(
    "incubator_mxnet_tpu.ops.pallas.flash_attention")

# a budget that holds 64 resident positions: 1024 keys stream in major
# blocks of 512 (tests/test_pallas.py)
_TIGHT = 3 * 4 * 128 * 4 * 64

# (lq, lk, d, heads, kv_heads, window, block_q, block_k, budget)
CASES = {
    "window-below-block": (128, 128, 16, 2, 2, 8, 32, 32, None),
    "window-is-block": (128, 128, 16, 2, 2, 32, 32, 32, None),
    "window-over-blocks": (192, 192, 16, 2, 2, 70, 32, 16, None),
    "window-inner-wider-than-outer": (96, 96, 16, 2, 2, 40, 16, 48, None),
    "window-derived-blocks": (300, 300, 32, 2, 2, 100, None, None, None),
    "window-ragged-blocks-64": (300, 300, 16, 2, 2, 77, 64, 64, None),
    "window-one": (64, 64, 16, 1, 1, 1, 16, 16, None),
    "window-beyond-length": (64, 64, 16, 2, 2, 500, 16, 16, None),
    "window-offset-lq-below-lk": (48, 112, 16, 2, 2, 30, 16, 16, None),
    "window-decode-one-query": (1, 70, 8, 2, 2, 20, 16, 16, None),
    "window-streamed": (1024, 1024, 16, 1, 1, 200, 128, 128, _TIGHT),
    "window-streamed-below-major": (1024, 1024, 16, 1, 1, 600, 128, 64,
                                    _TIGHT),
    "window-streamed-ragged": (520, 1100, 16, 1, 1, 300, None, None, _TIGHT),
    "grouped-4-to-1": (64, 64, 16, 8, 2, None, 16, 16, None),
    "grouped-full": (80, 48, 16, 4, 1, None, 16, 16, None),
    "grouped-window": (128, 128, 16, 8, 2, 40, 32, 32, None),
    "grouped-window-streamed": (1024, 1024, 16, 4, 1, 200, 128, 128, _TIGHT),
    "grouped-ragged-derived": (300, 300, 32, 4, 2, 64, None, None, None),
    # the Mellum2 cell's regime since PR 31: a window AND grouped heads
    # through the ONE backward kernel (queries resident), over many blocks
    "grouped-window-merged-8-key-blocks": (512, 512, 16, 8, 2, 100, 64, 32,
                                           None),
    "grouped-window-of-two-blocks-merged": (384, 384, 16, 4, 1, 128, 64, 64,
                                            None),
    "grouped-window-offset-merged": (192, 320, 16, 4, 2, 70, 32, 32, None),
    # blocks that line up with the window (`_paired`): the forward folds a
    # row block's two masked tiles into one softmax pass; with an offset of
    # whole blocks, over grouped heads, a window of one and of four blocks
    "paired-offset-of-two-blocks": (64, 128, 16, 2, 2, 32, 16, 16, None),
    "paired-grouped-window-of-four-blocks": (256, 256, 16, 4, 1, 128, 32, 32,
                                             None),
    "paired-window-of-one-block-offset": (96, 128, 16, 2, 1, 32, 32, 32,
                                          None),
    # ... and what stays apart: a window that is no whole number of blocks,
    # keys that are padded, keys that stream
    "unpaired-padded-keys": (120, 120, 16, 2, 2, 64, 32, 32, None),
    "unpaired-streamed": (1024, 1024, 16, 1, 1, 256, 128, 128, _TIGHT),
}

PAIRED = {"window-is-block", "grouped-window-of-two-blocks-merged",
          "paired-offset-of-two-blocks",
          "paired-grouped-window-of-four-blocks",
          "paired-window-of-one-block-offset"}


def _xla(q, k, v, heads, kv_heads, causal, window):
    """The XLA branch (no kernel is open on the CPU) on (B, H, L, D)."""
    b, _, lq, d = q.shape

    def merge(x):
        return x.transpose(0, 2, 1, 3).reshape(b, x.shape[2], -1)
    out = _raw.multihead_attention(merge(q), merge(k), merge(v), heads,
                                   causal=causal, num_kv_heads=kv_heads,
                                   window=window)
    return out.reshape(b, lq, heads, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("case", CASES)
def test_window_and_grouped_heads_against_the_band_mask(case):
    lq, lk, d, heads, kv_heads, window, bq, bk, budget = CASES[case]
    causal = window is not None or lq <= lk and case != "grouped-full"
    rng = np.random.RandomState(5)
    q, w = (jnp.asarray(rng.randn(2, heads, lq, d).astype(np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, kv_heads, lk, d).astype(np.float32))
            for _ in range(2))
    kw = {} if budget is None else {"vmem_budget": budget}
    # backward is one kernel exactly where nothing is streamed
    plan = fa._plan(lq, lk, d, 4, True, bq, bk, **kw)
    assert fa._merged(plan) == (budget is None)
    cfg = fa._Cfg(1.0, causal, lk, lk - lq, True, plan,
                  window if window is not None and window < lk else None,
                  heads // kv_heads)
    assert fa._paired(cfg) == (case in PAIRED)

    def f_flash(q, k, v):
        out = fa._attention(q, k, v, causal, None, bq, bk, True,
                            window=window, **kw)
        return jnp.sum(out * w), out

    def f_ref(q, k, v):
        out = _xla(q, k, v, heads, kv_heads, causal, window)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(f_flash, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, ref), want = jax.value_and_grad(f_ref, (0, 1, 2),
                                        has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(grads, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_window_none_is_todays_kernel_at_gpt2s_shape():
    """No window, equal heads: the same kernels as before the arguments
    existed (the same jaxpr, so the same bits), at the head size, length
    and causality of the benchmark's GPT-2 cell."""
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.bfloat16)
               for _ in range(3))

    def both(**kw):
        def f(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, interpret=True, **kw).astype(
                    jnp.float32) ** 2)
        return jax.value_and_grad(f, (0, 1, 2))

    plain, none = both(), both(window=None)
    assert (str(jax.make_jaxpr(plain)(q, k, v))
            == str(jax.make_jaxpr(none)(q, k, v)))
    wide = both(window=4096)        # every key up to a row's own
    (a, ga), (b, gb) = plain(q, k, v), wide(q, k, v)
    assert float(a) == float(b)
    for x, y in zip(ga, gb):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))


def test_a_window_bounds_the_loops():
    """The window is a bound on the key loop, not a mask over it: of the
    16 key sub-blocks of 64 a row block of 64 could run at 1024 tokens, a
    window of 100 leaves at most three."""
    plan = fa._plan(1024, 1024, 16, 4, True, 64, 64)
    cfg = fa._Cfg(1.0, True, 1024, 0, True, plan, 100, 1)
    for qi in range(16):
        first, inside, full, end = (int(e) for e in fa._key_range(
            cfg, qi, 0, plan.bq, plan.bk, plan.k_major))
        assert end - first <= 3 and first <= inside <= full <= end
        assert end == qi + 1 and first == max(0, (qi * 64 - 99) // 64)


@pytest.mark.parametrize("bad", [dict(window=8, causal=False),
                                 dict(window=0, causal=True)])
def test_window_needs_causal_and_a_size(bad):
    q = jnp.zeros((1, 2, 32, 8))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, interpret=True, **bad)


def test_heads_must_divide():
    q = jnp.zeros((1, 6, 32, 8))
    with pytest.raises(ValueError, match="key/value"):
        fa.flash_attention(q, q[:, :4], q[:, :4], interpret=True)
    with pytest.raises(ValueError, match="key/value heads"):
        _raw.multihead_attention(jnp.zeros((1, 32, 48)),
                                 jnp.zeros((1, 32, 32)),
                                 jnp.zeros((1, 32, 32)), 6, num_kv_heads=4)


def test_window_and_grouped_heads_stay_in_the_kernel(monkeypatch):
    """ops/select.py: causal, a window and grouped heads keep the kernel;
    a mask leaves it, and the reason says which."""
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 32, 64).astype(np.float32))
    kv = jnp.asarray(rng.randn(1, 32, 16).astype(np.float32))
    with _select.capture() as log:
        got = _raw.multihead_attention(q, kv, kv, 8, causal=True,
                                       num_kv_heads=2, window=8)
    assert log == [{"kernel": "flash_attention", "selected": True,
                    "reason": "ok"}]
    monkeypatch.setenv("MXTPU_PALLAS", "0")
    want = _raw.multihead_attention(q, kv, kv, 8, causal=True,
                                    num_kv_heads=2, window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    with _select.capture() as log:
        _raw.multihead_attention(q, kv, kv, 8, num_kv_heads=2,
                                 mask=jnp.ones((32, 32), bool))
    assert not log[0]["selected"] and "mask" in log[0]["reason"]
