"""The flash kernels on a latent layer's parts (ops/pallas/flash_attention.py
`latent_flash_attention`): queries as (q_n, q_r), the keys as the key/value
product's (B, L, H (dn + dv)) and ONE part of dr that every head shares,
read where the products wrote them. Interpreted on the CPU, in float32,
against the XLA form of the same two-part score; then `LatentAttentionCell`
against the assembly it replaced (a head's keys [k_n ; k_r] joined, the
query's rotated channels turned by adjacent pairs), outputs and every
parameter's gradient, at the tolerances of tests/test_moe_lm.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, profiler
from incubator_mxnet_tpu.models import LatentAttentionCell
from incubator_mxnet_tpu.ops import _raw, select
from incubator_mxnet_tpu.ops.pallas import latent_flash_attention


def _two_part(q_n, q_r, kv, k_r, heads):
    """Causal softmax((q_n,h k_n,h^T + q_r,h k_r^T) / sqrt(dn + dr)) v_h by
    head, in XLA."""
    b, lq, _ = q_n.shape
    lk, dr = kv.shape[1], k_r.shape[2]
    dn = q_n.shape[2] // heads
    kv = kv.reshape(b, lk, heads, -1)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_n.reshape(b, lq, heads, dn),
                         kv[..., :dn])
              + jnp.einsum("bqhd,bkd->bhqk",
                           q_r.reshape(b, lq, heads, dr), k_r))
    scores = jnp.where(jnp.tril(jnp.ones((lq, lk), bool), lk - lq),
                       scores / np.sqrt(dn + dr), -1e30)
    weights = jax.nn.softmax(scores, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, kv[..., dn:]).reshape(
        b, lq, -1)


def _parts(batch, length, heads, dn, dr, dv, seed=0):
    rng = np.random.RandomState(seed)
    shapes = [(batch, length, heads * dn), (batch, length, heads * dr),
              (batch, length, heads * (dn + dv)), (batch, length, dr),
              (batch, length, heads * dv)]
    return [jnp.asarray(rng.randn(*s), jnp.float32) for s in shapes]


# (batch, length, heads, dn, dr, dv, blocks): JoyAI's and Kimi's widths in
# blocks of 16 and 32; a key part of 32; a length that is padded to the
# block (40 -> 48); two major blocks, so backward is `_dq` and `_dkv`
CASES = {
    "128+64/128": (2, 64, 2, 128, 64, 128, (16, 32)),
    "128+32/128": (1, 64, 2, 128, 32, 128, (32, 16)),
    "padded-sequence": (1, 40, 2, 128, 64, 128, (None, None)),
    "streamed": (1, 1024, 1, 128, 64, 128, (128, 128)),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_on_parts_are_the_xla_form(monkeypatch, case):
    """Forward, and the gradients of every part: dq_n, dq_r, the dk_n / dv
    array in kv's own layout and k_r's gradient summed over the heads."""
    import importlib
    fa = importlib.import_module(
        "incubator_mxnet_tpu.ops.pallas.flash_attention")
    batch, length, heads, dn, dr, dv, (block_q, block_k) = CASES[case]
    if case == "streamed":      # 512 resident positions: K, Q stream
        tight = 3 * 512 * 4 * 256 * 4
        latent = fa._latent
        monkeypatch.setattr(fa, "_latent", lambda *a, **kw: latent(
            *a, vmem_budget=tight, **kw))
        plan = fa._plan(length, length, dn + dr, 4, True, block_q, block_k,
                        tight)
        assert plan.k_major == plan.q_major == 512 and not fa._merged(plan)
    *parts, cotangent = _parts(batch, length, heads, dn, dr, dv)

    def kernels(*a):
        return latent_flash_attention(*a, heads, block_q=block_q,
                                      block_k=block_k, interpret=True)

    def both(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * cotangent),
                                  argnums=(0, 1, 2, 3))(*parts)
    got_out = kernels(*parts)
    want_out = _two_part(*parts, heads)
    assert got_out.shape == (batch, length, heads * dv)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                               rtol=1e-4, atol=1e-5)
    (_, got), (_, want) = both(kernels), both(
        lambda *a: _two_part(*a, heads))
    for name, mine, theirs in zip(("q_n", "q_r", "kv", "k_r"), got, want):
        assert mine.shape == theirs.shape, name
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


# -- the cell ---------------------------------------------------------------

def _assembled(cell, params, x):
    """The cell's forward as it was before the parts: a head's query [q_n ;
    q_r] with q_r's pairs turned in place (`rope(interleaved=True)`), a
    head's key [k_n ; k_r] with k_r broadcast over the heads, attention in
    XLA on the joined heads."""
    kv_rank, dn, dr, dv = cell._dims
    heads, eps = cell._num_heads, 1e-5
    b, length = x.shape[:2]
    if cell._q_rank is None:
        wq, wkva, gkv, wkvb, wo = params
        q = x @ wq.T
    else:
        wqa, gq, wqb, wkva, gkv, wkvb, wo = params
        q = _raw.rms_norm(x @ wqa.T, gq, eps) @ wqb.T
    down = x @ wkva.T
    latent = _raw.rms_norm(down[..., :kv_rank], gkv, eps)
    shared = down[..., kv_rank:]
    q = q.reshape(b, length, heads, dn + dr)
    if cell._rope is not None:
        inv_freq, factor = cell._rope
        q_rope = _raw.rope(q[..., dn:].reshape(b, length, heads * dr),
                           inv_freq, heads, factor, interleaved=True)
        q = jnp.concatenate(
            [q[..., :dn], q_rope.reshape(b, length, heads, dr)], -1)
        shared = _raw.rope(shared, inv_freq, 1, factor, interleaved=True)
    up = (latent @ wkvb.T).reshape(b, length, heads, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(
        shared[:, :, None], (b, length, heads, dr))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dn + dr)
    scores = jnp.where(jnp.tril(jnp.ones((length, length), bool)), scores,
                       -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     up[..., dn:])
    return out.reshape(b, length, heads * dv) @ wo.T


FORMS = {
    # JoyAI's: a query rank and its norm, positions turned by adjacent pairs
    "query-rank-interleaved": dict(q_rank=48, interleaved=True, rope={
        "rope_type": "default", "rope_theta": 3.2e7}),
    # Kimi's: no rank, nothing rotated
    "no-rank-no-rotation": dict(),
}


def _moved(before):
    return {k.split("/")[-1]: v - before.get(k, 0)
            for k, v in profiler.counters().items()
            if k.endswith(".latent_attention") and v != before.get(k, 0)}


@pytest.mark.parametrize("form", FORMS)
def test_the_cell_on_parts_is_the_assembly_it_replaced(monkeypatch, form):
    """2 heads of 128 + 64 beside values of 128 over a latent of 32, 40
    tokens: the kernels on the parts (selected once, counted) against the
    joined heads in XLA, the output and every parameter's gradient; the
    parameters keep their published shapes."""
    monkeypatch.setenv("MXTPU_PALLAS", "force")
    rng = np.random.RandomState(3)
    cell = LatentAttentionCell(64, 2, 32, 128, 64, 128, **FORMS[form])
    cell.initialize(init=mx.init.Normal(0.2))
    x = rng.randn(2, 40, 64).astype(np.float32)
    cotangent = rng.randn(2, 40, 64).astype(np.float32)
    params = list(cell.collect_params().values())
    width = 2 * (128 + 64)
    assert [p.shape for p in params] == (
        [(width, 64)] if "q_rank" not in FORMS[form]
        else [(48, 64), (48,), (width, 48)]) + [
            (32 + 64, 64), (32,), (2 * 256, 32), (64, 2 * 128)]
    before = dict(profiler.counters())
    with autograd.pause():
        cell(nd.array(x))
    assert _moved(before) == {"pallas.selected.latent_attention": 1}
    with autograd.record():
        out = cell(nd.array(x))
        loss = (out * nd.array(cotangent)).sum()
    loss.backward()
    arrays = [p.data().jax() for p in params]
    want, grads = jax.value_and_grad(
        lambda ps: jnp.sum(_assembled(cell, ps, jnp.asarray(x)) * cotangent))(
            arrays)
    np.testing.assert_allclose(
        out.asnumpy(), np.asarray(_assembled(cell, arrays, jnp.asarray(x))),
        rtol=1e-4, atol=1e-5)
    assert float(loss.asscalar()) == pytest.approx(float(want), rel=1e-4)
    # a gradient to 1e-4 of its largest entry (sums in another order), as
    # tests/test_moe_lm.py holds every parameter's
    for p, theirs in zip(params, grads):
        theirs = np.asarray(theirs)
        np.testing.assert_allclose(p.grad().asnumpy(), theirs, rtol=0,
                                   atol=1e-4 * float(np.max(np.abs(theirs))),
                                   err_msg=p.name)


@pytest.mark.parametrize("why", ["mesh", "nope64", "rope48", "off"])
def test_a_rejected_selection_assembles_the_heads(monkeypatch, why):
    """Under a mesh program, at widths the kernels do not read in place,
    and with Pallas off, the op joins each head's keys and queries and runs
    `multihead_attention`: the same numbers, and no kernel on the parts.
    Every rejection but the master switch's is counted, with its reason."""
    from incubator_mxnet_tpu.ops import pallas

    def refused(*a, **kw):
        raise AssertionError("the kernels on the parts ran")
    monkeypatch.setattr(pallas, "latent_flash_attention", refused)
    monkeypatch.setenv("MXTPU_PALLAS", "0" if why == "off" else "force")
    dn = 64 if why == "nope64" else 128
    dr = 48 if why == "rope48" else 64
    *parts, _ = _parts(1, 24, 2, dn, dr, 128, seed=5)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2 if why == "mesh"
                                                     else 1]), ("dp",))
    before = dict(profiler.counters())
    with select.capture() as log, select.partitioned(mesh):
        program = str(jax.make_jaxpr(
            lambda *a: _raw.latent_attention(*a, 2))(*parts))
        got = _raw.latent_attention(*parts, 2)
    decided = [d for d in log if d["kernel"] == "latent_attention"]
    if why == "off":
        assert not decided and not _moved(before)
    else:
        assert [d["selected"] for d in decided] == [False, False]
        assert _moved(before) == {"pallas.rejected.latent_attention": 2}
    assert ("pallas_call" in program) == (why in ("nope64", "rope48"))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_two_part(*parts, 2)),
                               rtol=1e-4, atol=1e-5)
