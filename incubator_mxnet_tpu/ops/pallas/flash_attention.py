"""Flash attention pallas kernels (TPU fast path for multihead attention).

Replaces the reference's interleaved_matmul_selfatt_* / cuDNN attention
(src/operator/contrib/transformer.cc) with a FlashAttention-2 style tiled
kernel: online softmax over K/V blocks, O(L) memory, scores never hit HBM.
Forward saves the per-row logsumexp; backward recomputes scores blockwise in
two kernels (dq; dk/dv).

Layout notes (TPU tiling wants the last two block dims ∈ {(8k, 128m), full}):
- q/k/v/o are (batch*heads, seq, head_dim) with head_dim padded to 128 lanes;
- lse/delta ride as (batch*heads, 1, seq) with full-seq blocks, written via
  dynamic slices (the (1, block_q) layout is not tileable);
- the online-softmax m/l scratch is (block_q, 128) lanes-broadcast.

Off-TPU the same kernels run with interpret=True (tests/conftest sets CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG = -1e30
_LANES = 128


def _ru(x, m):
    return (x + m - 1) // m * m


def _vspec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, block_q, block_k, kv_len, num_kv, offset):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = ((ki * block_k < (qi + 1) * block_q + offset) if causal
           else (ki >= 0))

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, row + offset >= col)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    if causal:
        last = jnp.clip(((qi + 1) * block_q - 1 + offset) // block_k,
                        0, num_kv - 1)
    else:
        last = num_kv - 1

    @pl.when(ki == last)
    def _():
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse = (m_scr[:, 0:1] + jnp.log(l)).reshape(1, block_q)
        lse_ref[0, 0:1, pl.ds(pl.multiple_of(qi * block_q, block_q),
                              block_q)] = lse


def _fwd(q, k, v, cfg):
    scale, causal, bq, bk, kv_len, offset, interpret = cfg
    bh, lq, d = q.shape
    lk = k.shape[1]
    num_q, num_kv = lq // bq, lk // bk
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, kv_len=kv_len,
                             num_kv=num_kv, offset=offset)
    return pl.pallas_call(
        kern,
        grid=(bh, num_q, num_kv),
        in_specs=[_vspec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  _vspec((1, bk, d), lambda b, i, j: (b, j, 0)),
                  _vspec((1, bk, d), lambda b, i, j: (b, j, 0))],
        out_specs=[_vspec((1, bq, d), lambda b, i, j: (b, i, 0)),
                   _vspec((1, 1, lq), lambda b, i, j: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _row(ref, start, size):
    """Read (1, size) slice of a (1, 1, L) block as (size, 1)."""
    return ref[0, 0:1, pl.ds(pl.multiple_of(start, size),
                             size)].reshape(size, 1)


def _masked_p(q, k, lse_col, scale, causal, qi, ki, block_q, block_k, kv_len,
              offset):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse_col)
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = col < kv_len
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(mask, row + offset >= col)
    return jnp.where(mask, p, 0.0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_scr, *,
               scale, causal, block_q, block_k, kv_len, num_kv, offset):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = ((ki * block_k < (qi + 1) * block_q + offset) if causal
           else (ki >= 0))

    @pl.when(run)
    def _():
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        lse = _row(lse_ref, qi * block_q, block_q)
        dl = _row(dl_ref, qi * block_q, block_q)
        p = _masked_p(q_ref[0], k, lse, scale, causal, qi, ki,
                      block_q, block_k, kv_len, offset)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    if causal:
        last = jnp.clip(((qi + 1) * block_q - 1 + offset) // block_k,
                        0, num_kv - 1)
    else:
        last = num_kv - 1

    @pl.when(ki == last)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, block_q, block_k, kv_len,
                num_q, offset):
    ki, qi = pl.program_id(1), pl.program_id(2)
    if causal:
        first = jnp.clip((ki * block_k - offset) // block_q, 0, num_q - 1)
    else:
        first = 0

    @pl.when(qi == first)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = ((ki * block_k < (qi + 1) * block_q + offset) if causal
           else (qi >= 0))

    @pl.when(run)
    def _():
        q, v, do = q_ref[0], v_ref[0], do_ref[0]
        lse = _row(lse_ref, qi * block_q, block_q)
        dl = _row(dl_ref, qi * block_q, block_q)
        p = _masked_p(q, k_ref[0], lse, scale, causal, qi, ki,
                      block_q, block_k, kv_len, offset)
        pt = p.astype(do.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(cfg, res, dout):
    scale, causal, bq, bk, kv_len, offset, interpret = cfg
    q, k, v, out, lse = res
    do, _ = dout
    bh, lq, d = q.shape
    lk = k.shape[1]
    num_q, num_kv = lq // bq, lk // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, lq)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, kv_len=kv_len, num_kv=num_kv,
                          offset=offset),
        grid=(bh, num_q, num_kv),
        in_specs=[_vspec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  _vspec((1, bk, d), lambda b, i, j: (b, j, 0)),
                  _vspec((1, bk, d), lambda b, i, j: (b, j, 0)),
                  _vspec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  _vspec((1, 1, lq), lambda b, i, j: (b, 0, 0)),
                  _vspec((1, 1, lq), lambda b, i, j: (b, 0, 0))],
        out_specs=_vspec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, kv_len=kv_len, num_q=num_q,
                          offset=offset),
        grid=(bh, num_kv, num_q),
        in_specs=[_vspec((1, bq, d), lambda b, j, i: (b, i, 0)),
                  _vspec((1, bk, d), lambda b, j, i: (b, j, 0)),
                  _vspec((1, bk, d), lambda b, j, i: (b, j, 0)),
                  _vspec((1, bq, d), lambda b, j, i: (b, i, 0)),
                  _vspec((1, 1, lq), lambda b, j, i: (b, 0, 0)),
                  _vspec((1, 1, lq), lambda b, j, i: (b, 0, 0))],
        out_specs=[_vspec((1, bk, d), lambda b, j, i: (b, j, 0)),
                   _vspec((1, bk, d), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    out, lse = _fwd(q, k, v, cfg)
    return out, lse


def _flash_fwd(q, k, v, cfg):
    out, lse = _fwd(q, k, v, cfg)
    return (out, lse), (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=None):
    """Tiled attention on (B, H, L, D) tensors; returns (B, H, Lq, D).

    Differentiable (custom VJP with blockwise recompute). Padding of L and D
    to block multiples is handled here; padded KV positions are masked inside
    the kernel, padded Q rows are sliced off (their grads vanish since the
    incoming cotangent there is zero).
    """
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)

    if interpret:
        block_q = min(block_q, _ru(lq, 16))
        block_k = min(block_k, _ru(lk, 16))
    else:
        # Mosaic needs the lse dynamic-slice lane index provably 128-aligned,
        # so q/k blocks are 128-multiples on hardware (lq/lk get padded up).
        block_q = _ru(min(block_q, _ru(lq, _LANES)), _LANES)
        block_k = _ru(min(block_k, _ru(lk, _LANES)), _LANES)
    lqp, lkp = _ru(lq, block_q), _ru(lk, block_k)
    dp = d if interpret else _ru(d, _LANES)

    def prep(x, lp):
        x = x.reshape(b * h, x.shape[2], d)
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, dp - d)))

    q3, k3, v3 = prep(q, lqp), prep(k, lkp), prep(v, lkp)
    if causal and lq > lk:
        raise ValueError("flash_attention: causal with more queries than keys "
                         "is undefined (use an explicit mask)")
    cfg = (scale, bool(causal), block_q, block_k, lk, lk - lq,
           bool(interpret))
    out, _ = _flash(q3, k3, v3, cfg)
    return out[:, :lq, :d].reshape(b, h, lq, d)
