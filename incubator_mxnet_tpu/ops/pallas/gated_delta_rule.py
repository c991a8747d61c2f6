"""The gated delta rule's chunked scan as Pallas kernels (the TPU fast path
of `ops/_raw.py` `gated_delta_rule`; the XLA form there, `_delta_group`, is
what these are held to).

What is computed is `_delta_group`'s chunked form, product for product: per
chunk of 64 tokens and head, with S the (dk, dv) float32 state the chunk
starts from and G the log-decays cumulated over the chunk,

    A = strictly_lower(beta * kk),  kk_ti = sum_c k_tc k_ic e^(G_tc - G_ic)
    P = lower(qk), the same sum over q_t
    T = (I + A)^-1    W = T (beta K e^G)    U = T (beta V) - W S
    O = (Q e^G) S + P U       S' = e^(G_last) S + (K e^(G_last - G))^T U

No decay is divided by: between sub-chunks of 16 rows, row t carries
e^(G_t - G_before) and column i e^(G_before - G_i); inside one the (16, 16,
dk) differences are exponentiated themselves, column by column. Operands of
the products that take q's dtype in `_delta_group` take it here, with
float32 sums; the inverse (the same finite series on the 16 x 16 diagonal
blocks, the same joins), and W and U that it multiplies, are float32 at
`highest`; the state is float32.

The backward is written by hand (a Pallas kernel has no transpose). From the
last chunk, with dS' carried:

    dU = P^T dO + (K e^(G_last - G)) dS'         dP = lower(dO U^T)
    dS = (Q e^G)^T dO + e^(G_last) dS' - W^T dU  dW = -dU S^T
    dT = dW (beta K e^G)^T + dU (beta V)^T       dA = -T^T dT T^T
      = -(T^T (dW | dU)) (W | U + W S)^T, one product
    d(beta K e^G) = T^T dW,  d(beta V) = T^T dU,  d(Q e^G) = dO S^T
    d(K e^(G_last - G)) = U dS'^T,  d(e^(G_last)) = rowsum(S * dS')

then the decays' elementwise factors, dA and dP back to q, k, v, beta and g
(a reversed cumulated sum within the chunk). The transpose of the inverse's
series and joins is that one product.

Kernels. One grid step is `block` tokens (8 chunks; a state is written
every `block` tokens and nothing else of a chunk leaves VMEM) of 8 heads;
the grid is (batch, head blocks, token blocks), the last sequential, with
the state (the backward's dS) in a VMEM scratch across it. What does not
wait for the state (decays, kk, qk, the inverse, W, T beta V) runs for a
step's chunks at once, so that the chunks' chains of small products
interleave (chunk by chunk the forward takes 7.70 ms for 4.24 at 8192 x 32 x
128: PERF.md, PR 33); the chunk-to-chunk part is three products a chunk and
head. The heads of a step run one after the other in a loop that is
compiled once (heads side by side in one straight line gained nothing: 1 /
2 / 4 heads 6.21 / 6.07 / 6.02 ms forward, 12.77 / 12.98 / 14.00 backward).
The state is held TRANSPOSED, (dv, dk): e^(G_last) then scales lanes, every
product with it is a plain or an NT one, and d(e^(G_last)) comes out a row.

Layout: q, k, g (B, L, H, dk), v (B, L, H, dv) as the mixer's stages hand
them over, no copy: in that array a head is a ROW of every (8, 128) tile, so
a block is (1, block, 8, d), and with the block as (block x 8, d) rows head j
is the rows j, j + 8, ...: one strided read (`_rows`). Mosaic has strided
reads for 32-bit types only, and bfloat16 packs two heads to a row, so a
bfloat16 block is widened whole into a float32 scratch first and a result
narrowed whole at the end (`_widened`; read as `ref[0, :, j]` the heads
cost 3.0 of a backward kernel's 12.1 ms at 8192 x 32 x 128). (Merged to (B,
L, H d), a head would be a lane block, but XLA holds (B, L, H, d) and (B, L,
H d) in different tiles: the merge was a copy of every operand and result,
67-134 MB each, and XLA kept the broadcast norms of q and k at 134 MB a
layer; the Kimi cell's step no longer fitted the chip: PERF.md, PR 33.)
beta (B, L, H) rides whole (a block holds every head; the head's column is
picked under a lane mask); its gradient leaves as rows, (B, H, 1, L). Mosaic
has no cumulated sum: G is a product with a triangle of ones, g in three
bfloat16 parts that add up to it exactly, dg its transpose.

`_plan` derives (block, heads a step, VMEM asked) from (length, heads, dk,
dv, dtype): ONE algorithm, no option. Off the chip the same kernels run
interpreted.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["delta_rule_fwd", "delta_rule_bwd", "padded_length"]

CHUNK = 64      # tokens whose products inside are matrix products
SUB = 16        # tokens of a chunk's diagonal blocks, decay by decay
GROUP = 8       # chunks a grid step; a state kept a step

_SUBS = CHUNK // SUB
_F32 = jnp.float32
_NN, _NT, _TN = (2, 1), (2, 2), (1, 1)    # batched: (n, ., .) operands
_MOSAIC_DEFAULT = 16 * 1024 * 1024
_VALUES_FWD, _VALUES_BWD = 32, 84         # `_plan`


def _mm(a, b, contract, exact=False):
    """Product of (n, ., .) operands, one a leading index, contracting
    `contract` = (axis of a, axis of b); float32 sums, `highest` where
    `exact`."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        precision=lax.Precision.HIGHEST if exact else None,
        preferred_element_type=_F32)


def _mm2(a, b, contract, exact=False):
    """The same for two matrices: contract (axis of a, axis of b)."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=lax.Precision.HIGHEST if exact else None,
        preferred_element_type=_F32)


def _grid(shape):
    """(row index, column index) of a matrix, as int32 arrays."""
    return (lax.broadcasted_iota(jnp.int32, shape, 0),
            lax.broadcasted_iota(jnp.int32, shape, 1))


def _rows_of(x, first, count=SUB):
    return x[:, first:first + count]


def _stacked_rows(blocks):
    return jnp.concatenate(blocks, axis=1)


def _unit_lower_inverse(a):
    """(I + a)^-1 of strictly lower triangular a (n, CHUNK, CHUNK):
    `_raw._unit_lower_inverse`'s series on the 16 x 16 diagonal blocks and
    its joins, product for product. The blocks on a diagonal ride SIDE BY
    SIDE, (n, size, CHUNK): times a block-diagonal matrix that is every
    block's own product at once, in `size` rows through the MXU where the
    whole matrices would take CHUNK (the `highest` products of the inverse
    were 47% of the forward kernel as whole matrices: PERF.md, PR 33)."""
    rows, cols = _grid((CHUNK, CHUNK))

    def same(size):
        return rows // size == cols // size

    def on_diagonal(x, size):
        """(n, size, CHUNK) blocks side by side -> block-diagonal."""
        return jnp.where(same(size),
                         jnp.concatenate([x] * (CHUNK // size), 1), 0.0)

    x_wide = jnp.where(same(SUB), -a, 0.0)
    x = sum(x_wide[:, first:first + SUB] for first in range(0, CHUNK, SUB))
    block_row, block_col = _grid((SUB, CHUNK))
    inverse = jnp.where(block_col % SUB == block_row, 1.0, 0.0) + x
    # (I + x)(I + x^2)(I + x^4)(I + x^8); x^16 = 0
    x = _mm(x, x_wide, _NN, True)
    for _ in range((SUB - 1).bit_length() - 2):
        both = _mm(jnp.concatenate([inverse, x], 1), on_diagonal(x, SUB),
                   _NN, True)
        inverse, x = inverse + both[:, :SUB], both[:, SUB:]
    inverse = inverse + _mm(inverse, on_diagonal(x, SUB), _NN, True)
    # neighbours joined: [[P, 0], [-R C P, R]] for [[P^-1, 0], [C, R^-1]]
    size = SUB
    while size < CHUNK:
        below = jnp.where(same(2 * size) & ~same(size), a, 0.0)
        corner = -_mm(_mm(inverse, below, _NN, True),
                      on_diagonal(inverse, size), _NN, True)
        first = (_grid((size, CHUNK))[1] // size) % 2 == 0
        inverse = jnp.concatenate([jnp.where(first, inverse, 0.0),
                                   jnp.where(first, corner, inverse)], 1)
        size *= 2
    return inverse


class _Parts(NamedTuple):
    """What a step's chunks hold before the state is known; (n, CHUNK, .)
    each, n the chunks of the step."""
    q: jax.Array          # the inputs, float32
    k: jax.Array
    v: jax.Array
    beta: jax.Array       # (n, CHUNK, 1)
    total: jax.Array      # G
    before: tuple         # G at the start of sub-chunks 1.., (n, 1, dk)
    inside: jax.Array     # G - G_before of the row's sub-chunk, <= 0
    rows: jax.Array       # e^inside
    reach: tuple          # e^(G_before_a - G_i), 0 from sub-chunk a on
    kk: jax.Array         # (n, CHUNK, CHUNK)
    inverse: jax.Array    # T
    carried: jax.Array    # e^G
    leave: jax.Array      # e^(G_last - G)
    kept: jax.Array       # e^(G_last), (n, 1, dk)
    wu: jax.Array         # T (beta K e^G | beta V), float32: W and U + W S
    w: jax.Array          # its W in q's dtype
    u_alone: jax.Array    # its U + W S
    q_in: jax.Array       # Q e^G, q's dtype
    p: jax.Array          # lower(qk), q's dtype
    k_out: jax.Array      # K e^(G_last - G), q's dtype


def _cumulated(g, reverse=False):
    """g (n, CHUNK, d) float32 summed along a chunk's tokens up to each
    (from each on, reversed): a product with a triangle of ones, g in three
    bfloat16 parts that add up to it exactly, so every product is exact
    and the sums are float32 (`highest` would take six passes for the
    same)."""
    rows, cols = _grid((CHUNK, CHUNK))
    ones = jnp.broadcast_to(
        jnp.where(rows <= cols if reverse else rows >= cols, 1.0, 0.0),
        (g.shape[0], CHUNK, CHUNK)).astype(jnp.bfloat16)
    total = None
    for _ in range(3):
        part = g.astype(jnp.bfloat16)
        g = g - part.astype(_F32)
        partial = _mm(ones, part, _NN)
        total = partial if total is None else total + partial
    return total


_HALF = SUB // 2    # a sub-chunk's rows in two float32 tiles of 8


def _halves(x):
    """x (n, CHUNK, d) -> the rows 0..7 and the rows 8..15 of every
    sub-chunk, (n, subs, 8, d) each."""
    x = x.reshape(x.shape[0], _SUBS, 2, _HALF, x.shape[-1])
    return x[:, :, 0], x[:, :, 1]


def _whole(top, bottom):
    """`_halves`'s inverse."""
    x = jnp.stack([top, bottom], 2)
    return x.reshape(x.shape[0], CHUNK, x.shape[-1])


def _column(i, inside, k):
    """Column i of every sub-chunk's diagonal block, from `_halves` of G -
    G_before and of k: (the half the column's own row lies in, e^(G_t - G_i)
    for the rows t of that half, 0 above row i, and for the rows of the
    lower half where that is another (else None), k_i (n, subs, 1, dk)).
    The upper half of a column of the lower half is all zeros, and is not
    made."""
    half, at = divmod(i, _HALF)
    own = inside[half][:, :, at:at + 1]
    row = lax.broadcasted_iota(jnp.int32, (_HALF, 1), 0)
    mine = jnp.exp(jnp.where(row >= at, inside[half] - own, -jnp.inf))
    below = jnp.exp(inside[1] - own) if half == 0 else None
    return half, mine, below, k[half][:, :, at:at + 1]


def _within(q, k, inside, kk, qk):
    """kk and qk with their diagonal blocks filled in: inside a sub-chunk
    e^(G_t - G_i) is made entry by entry, column i of every block at
    once, for the rows from i on."""
    q, k, inside = _halves(q), _halves(k), _halves(inside)
    kk, qk = list(_halves(kk)), list(_halves(qk))
    sub = lax.broadcasted_iota(jnp.int32, (_SUBS, _HALF, CHUNK), 0)
    col = lax.broadcasted_iota(jnp.int32, (_SUBS, _HALF, CHUNK), 2)
    for i in range(SUB):
        half, mine, below, k_i = _column(i, inside, k)
        place = col == sub * SUB + i
        for rows, decay in ((half, mine), (1, below)):
            if decay is None:
                continue
            column = k_i * decay
            kk[rows] = jnp.where(place, jnp.sum(k[rows] * column, -1,
                                                keepdims=True), kk[rows])
            qk[rows] = jnp.where(place, jnp.sum(q[rows] * column, -1,
                                                keepdims=True), qk[rows])
    return _whole(*kk), _whole(*qk)


def _within_back(q, k, inside, d_kk, d_qk):
    """`_within` taken back: (dq, dk, d inside) from the cotangents of kk
    and qk, of which only the diagonal blocks are read."""
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)

    def diagonal_blocks(x):
        """(n, CHUNK, CHUNK) -> (n, CHUNK, SUB): each row's own block."""
        out = x[..., :SUB]
        for a in range(1, _SUBS):
            out = jnp.where(row >= a * SUB, x[..., a * SUB:(a + 1) * SUB],
                            out)
        return out

    on_kk, on_qk = _halves(diagonal_blocks(d_kk)), _halves(diagonal_blocks(d_qk))
    q, k, inside = _halves(q), _halves(k), _halves(inside)
    zeros = jnp.zeros_like(k[0])
    d_k_row, d_q_row, d_k_col = ([zeros, zeros] for _ in range(3))
    at_row = lax.broadcasted_iota(jnp.int32, (_HALF, 1), 0)
    for i in range(SUB):
        half, mine, below, k_i = _column(i, inside, k)
        onto = 0.0
        for rows, decay in ((half, mine), (1, below)):
            if decay is None:
                continue
            column = k_i * decay
            from_kk = on_kk[rows][..., i:i + 1]
            from_qk = on_qk[rows][..., i:i + 1]
            d_k_row[rows] = d_k_row[rows] + from_kk * column
            d_q_row[rows] = d_q_row[rows] + from_qk * column
            onto = onto + jnp.sum(
                (from_kk * k[rows] + from_qk * q[rows]) * decay, 2,
                keepdims=True)
        d_k_col[half] = jnp.where(at_row == i % _HALF, onto, d_k_col[half])
    d_inside = [k[h] * d_k_row[h] + q[h] * d_q_row[h] - k[h] * d_k_col[h]
                for h in range(2)]
    return (_whole(*d_q_row),
            _whole(*(d_k_row[h] + d_k_col[h] for h in range(2))),
            _whole(*d_inside))


def _parts(dtype, q, k, v, g, beta):
    """q, k, g (n, CHUNK, dk), v (n, CHUNK, dv), beta (n, CHUNK, 1), q, k
    and v holding values of `dtype`, the products' own -> `_Parts`."""
    n, _, dk = k.shape
    q, k, v = (x.astype(_F32) for x in (q, k, v))
    rows, cols = _grid((CHUNK, CHUNK))
    row = rows[:, :1]                                   # (CHUNK, 1)
    total = _cumulated(g)
    before = tuple(total[:, a * SUB - 1:a * SUB] for a in range(1, _SUBS))
    started = jnp.zeros_like(total)
    for a, at in enumerate(before, 1):
        started = jnp.where(row >= a * SUB, at, started)
    inside = total - started
    row_decay = jnp.exp(inside)
    k_rows, q_rows = (k * row_decay).astype(dtype), (q * row_decay).astype(dtype)
    # between sub-chunks: rows of sub-chunk a against the columns before it
    reach = []
    kk_rows, qk_rows = ([jnp.zeros((n, SUB, CHUNK), _F32)] for _ in range(2))
    for a, at in enumerate(before, 1):
        reach.append(jnp.exp(jnp.where(row < a * SUB, at - total, -jnp.inf)))
        lhs = _stacked_rows([_rows_of(k_rows, a * SUB),
                             _rows_of(q_rows, a * SUB)])
        block = _mm(lhs, (k * reach[-1]).astype(dtype), _NT)
        kk_rows.append(block[:, :SUB])
        qk_rows.append(block[:, SUB:])
    kk, qk = _within(q, k, inside, _stacked_rows(kk_rows),
                     _stacked_rows(qk_rows))
    inverse = _unit_lower_inverse(jnp.where(rows > cols, beta * kk, 0.0))
    carried = jnp.exp(total)
    last = total[:, CHUNK - 1:]
    leave = jnp.exp(last - total)
    wu = _mm(inverse, jnp.concatenate([beta * (k * carried), beta * v], -1),
             _NN, True)
    return _Parts(
        q=q, k=k, v=v, beta=beta, total=total, before=before, inside=inside,
        rows=row_decay, reach=tuple(reach), kk=kk, inverse=inverse,
        carried=carried, leave=leave, kept=jnp.exp(last),
        wu=wu, w=wu[..., :dk].astype(dtype), u_alone=wu[..., dk:],
        q_in=(q * carried).astype(dtype), p=qk.astype(dtype),
        k_out=(k * leave).astype(dtype))


def _step(state_t, parts, c):
    """Chunk c from the state it starts with, held transposed (dv, dk)
    float32 -> (the state after it, o (CHUNK, dv) float32, U in q's
    dtype)."""
    dtype = parts.w.dtype
    held = state_t.astype(dtype)
    u = parts.u_alone[c] - _mm2(parts.w[c], held, (1, 1))
    u_low = u.astype(dtype)
    o = _mm2(parts.q_in[c], held, (1, 1)) + _mm2(parts.p[c], u_low, (1, 0))
    after = state_t * parts.kept[c] + _mm2(u_low, parts.k_out[c], (0, 0))
    return after, o, u_low


def head_forward(dtype, state_t, q, k, v, g, beta):
    """A step's chunks of one head: state_t (dv, dk) float32, q, k, g
    (tokens, dk), v (tokens, dv), beta (tokens, 1); q, k and v hold values
    of `dtype`, in which the products that `_delta_group` makes in q's
    dtype are made -> (the state after them, o (tokens, dv) float32)."""
    n = q.shape[0] // CHUNK
    parts = _parts(dtype, *(x.reshape(n, CHUNK, x.shape[-1])
                            for x in (q, k, v, g, beta)))
    out = []
    for c in range(n):
        state_t, o, _ = _step(state_t, parts, c)
        out.append(o)
    return state_t, jnp.concatenate(out, 0)


def _taken_back(parts, states, u_low, d_state_t, d_o):
    """The chunk-to-chunk part from the last chunk: d_o (n, CHUNK, dv) in
    q's dtype, d_state_t the cotangent of the state after the step ->
    (that of the state before it, then (n, CHUNK, .) each: dU, dW, d(Q e^G),
    dP, d(K e^(G_last - G)), and d(e^(G_last)) (n, 1, dk))."""
    dtype = parts.w.dtype
    rows, cols = _grid((CHUNK, CHUNK))
    n = len(states)
    d_u, d_w, d_q_in, d_p, d_k_out, d_kept = ([None] * n for _ in range(6))
    for c in reversed(range(n)):
        held, d_after = states[c].astype(dtype), d_state_t.astype(dtype)
        d_u[c] = (_mm2(parts.p[c], d_o[c], (0, 0))
                  + _mm2(parts.k_out[c], d_after, (1, 1)))
        d_u_low = d_u[c].astype(dtype)
        d_k_out[c] = _mm2(u_low[c], d_after, (1, 0))
        d_kept[c] = jnp.sum(states[c] * d_state_t, 0, keepdims=True)
        d_state_t = (parts.kept[c] * d_state_t
                     + _mm2(d_o[c], parts.q_in[c], (0, 0))
                     - _mm2(d_u_low, parts.w[c], (0, 0)))
        d_w[c] = -_mm2(d_u_low, held, (1, 0))
        d_q_in[c] = _mm2(d_o[c], held, (1, 0))
        d_p[c] = jnp.where(rows >= cols, _mm2(d_o[c], u_low[c], (1, 1)), 0.0)
    return (d_state_t,) + tuple(jnp.stack(x) for x in (
        d_u, d_w, d_q_in, d_p, d_k_out, d_kept))


def _parts_back(parts, d_u, d_w, d_q_in, d_qk, d_k_out, d_kept):
    """The cotangents of what `_parts` made, back to its inputs: (dq, dk,
    dv, dg (n, CHUNK, .), then the (n, CHUNK, .) arrays whose row sums are
    dbeta)."""
    n, _, dk = parts.k.shape
    dtype = parts.w.dtype
    q, k, v, beta, total = parts.q, parts.k, parts.v, parts.beta, parts.total
    rows, cols = _grid((CHUNK, CHUNK))
    row = rows[:, :1]
    strictly = rows > cols
    # the inverse and what it multiplied
    # dT = d(WU) (beta K e^G | beta V)^T and dA = -T^T dT T^T: with what T
    # multiplied taken back first, T^T d(WU), that is -(T^T d(WU)) (WU)^T
    k_carried = k * parts.carried
    d_scaled = _mm(parts.inverse, jnp.concatenate([d_w, d_u], -1), _TN, True)
    d_a = -_mm(d_scaled, parts.wu, _NT, True)
    d_bk, d_bv = d_scaled[..., :dk], d_scaled[..., dk:]
    d_kk = jnp.where(strictly, beta * d_a, 0.0)
    beta_from = (jnp.where(strictly, d_a * parts.kk, 0.0),
                 *((d_bk * k_carried + d_bv * v,) if dk == v.shape[-1] else
                   (d_bk * k_carried, d_bv * v)))
    d_v = beta * d_bv
    d_kc = beta * d_bk
    # the decays carried from the chunk's start and to its end
    d_k = d_kc * parts.carried + d_k_out * parts.leave
    d_q = d_q_in * parts.carried
    left = d_k_out * k * parts.leave
    d_total = (d_kc * k + d_q_in * q) * parts.carried - left
    d_last = (jnp.sum(left, 1, keepdims=True) + d_kept * parts.kept)
    # between sub-chunks
    k_rows = (k * parts.rows).astype(dtype)
    q_rows = (q * parts.rows).astype(dtype)
    d_k_rows, d_q_rows = ([jnp.zeros((n, SUB, dk), _F32)] for _ in range(2))
    d_before = []
    for a, reach in enumerate(parts.reach, 1):
        column = (k * reach).astype(dtype)
        lhs = _stacked_rows([_rows_of(k_rows, a * SUB),
                             _rows_of(q_rows, a * SUB)])
        d_block = _stacked_rows([_rows_of(d_kk, a * SUB),
                                 _rows_of(d_qk, a * SUB)]).astype(dtype)
        d_column = _mm(d_block, lhs, _TN)
        d_lhs = _mm(d_block, column, _NN)
        d_k_rows.append(d_lhs[:, :SUB])
        d_q_rows.append(d_lhs[:, SUB:])
        d_k = d_k + d_column * reach
        d_reach = d_column * k * reach
        d_total = d_total - d_reach
        d_before.append(jnp.sum(d_reach, 1, keepdims=True))
    d_k_rows, d_q_rows = _stacked_rows(d_k_rows), _stacked_rows(d_q_rows)
    d_k = d_k + d_k_rows * parts.rows
    d_q = d_q + d_q_rows * parts.rows
    d_inside = (d_k_rows * k + d_q_rows * q) * parts.rows
    # inside a sub-chunk, column by column
    d_q_in_sub, d_k_in_sub, d_inside_in_sub = _within_back(
        q, k, parts.inside, d_kk, d_qk)
    d_q, d_k = d_q + d_q_in_sub, d_k + d_k_in_sub
    d_inside = d_inside + d_inside_in_sub
    # inside = G - G_before; G_before and G_last are rows of G
    d_total = d_total + d_inside
    for a, from_reach in enumerate(d_before, 1):
        from_inside = jnp.sum(jnp.where(
            (row >= a * SUB) & (row < (a + 1) * SUB), d_inside, 0.0), 1,
            keepdims=True)
        d_total = d_total + jnp.where(row == a * SUB - 1,
                                      from_reach - from_inside, 0.0)
    d_total = d_total + jnp.where(row == CHUNK - 1, d_last, 0.0)
    return d_q, d_k, d_v, _cumulated(d_total, reverse=True), beta_from


def _row_sums(x):
    """x (rows, d) float32 summed along d, as a ROW (1, rows): ones times
    x^T, x in three bfloat16 parts that add up to it exactly."""
    ones = jnp.ones((8, x.shape[1]), jnp.bfloat16)
    total = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(_F32)
        partial = _mm2(ones, part, (1, 1))
        total = partial if total is None else total + partial
    return total[:1]


def head_backward(dtype, state_t, d_state_t, q, k, v, g, beta, d_o):
    """`head_forward` taken back: the state the step started from and the
    cotangent of the state after it (both (dv, dk) float32), the step's
    inputs, d_o (tokens, dv) -> (the cotangent of the state before, dq,
    dk, dv, dg (tokens, .) float32, dbeta as a ROW (1, tokens))."""
    tokens = q.shape[0]
    n = tokens // CHUNK
    parts = _parts(dtype, *(x.reshape(n, CHUNK, x.shape[-1])
                            for x in (q, k, v, g, beta)))
    states, u_low = [], []
    for c in range(n):
        states.append(state_t)
        state_t, _, u = _step(state_t, parts, c)
        u_low.append(u)
    d_state_t, *d_parts = _taken_back(
        parts, states, u_low, d_state_t,
        d_o.astype(dtype).reshape(n, CHUNK, -1))
    *grads, beta_from = _parts_back(parts, *d_parts)
    d_beta = sum(_row_sums(x.reshape(tokens, x.shape[-1])) for x in beta_from)
    return (d_state_t, *(x.reshape(tokens, x.shape[-1]) for x in grads),
            d_beta)


# -- the kernels -------------------------------------------------------------

class _Plan(NamedTuple):
    """Static, part of the jit key."""
    block: int        # tokens a grid step
    heads: int        # heads a grid step
    fwd_vmem: int     # bytes each kernel is reckoned to hold
    bwd_vmem: int
    interpret: bool


def padded_length(length):
    """L up to whole grid steps: whole chunks, and whole steps of `GROUP`
    chunks once there are that many."""
    chunks = -(-length // CHUNK)
    return -(-chunks // min(GROUP, chunks)) * min(GROUP, chunks) * CHUNK


def _plan(length, heads, dk, dv, itemsize, interpret):
    """`length` is a padded one. Heads a step: in the (B, L, H, d) arrays a
    head is a ROW of every (8, 128) tile, so a block takes the heads by
    whole tiles: 8, or all of them where H is no multiple of 8. A step
    holds its blocks twice (the pipeline's buffers), those that are not
    float32 once more widened (`_widened`) and, for the one head at work,
    the values of a phase: float32 arrays of (block, max(dk, dv)),
    `_VALUES_FWD` forward and `_VALUES_BWD` backward (Mosaic's own figures at
    512 x 128 lie under these: tests/test_tpu_compile.py compiles inside
    the bare reckoning)."""
    block = min(GROUP * CHUNK, length)
    wide = max(dk, dv)
    per_step = 8 if heads % 8 == 0 else heads

    def held(values, operands, float32):
        """`operands` arrays in q's dtype and `float32` ones in float32 a
        token and head; beta's block at 128 lanes, the states, dbeta's rows
        at 8 sublanes."""
        widened = 0 if itemsize == 4 else operands * 4
        return (per_step * block * wide * (
                    2 * (operands * itemsize + float32 * 4) + widened)
                + 2 * block * 128 * 4 + 3 * per_step * dv * dk * 4
                + 2 * per_step * 8 * block * 4 + values * block * wide * 4)

    return _Plan(block, per_step, held(_VALUES_FWD, 4, 1),
                 held(_VALUES_BWD, 7, 2), bool(interpret))


def _head_beta(beta_ref, head):
    """The column of `head` out of a (1, block, H) block: (block, 1)."""
    every = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, every.shape, 1)
    return jnp.sum(jnp.where(lane == head, every, 0.0), 1, keepdims=True)


def _rows(ref, j):
    """Head j of a (1, block, heads, d) float32 block. A head is a row of
    every (8, 128) tile, so with the block as (block x heads, d) rows it is
    the rows j, j + heads, ...: ONE strided read or write (Mosaic has them
    for 32-bit types only; indexed as `ref[0, :, j]` every row would be
    read into a tile of its own and the tiles shuffled together, 1.9 of a
    backward kernel's 12.1 ms). The interpreter has no reshaped writes and
    indexes."""
    _, block, heads, d = ref.shape
    return ref.reshape(block * heads, d), pl.ds(j, block, stride=heads)


def _read(interpret, ref, j):
    if interpret:
        return ref[0, :, j, :]
    rows, at = _rows(ref, j)
    return rows[at, :]


def _write(interpret, ref, j, value):
    if interpret:
        ref[0, :, j, :] = value
    else:
        rows, at = _rows(ref, j)
        rows[at, :] = value


def _widened(refs, wide):
    """The blocks the heads are read from: a block that is not float32
    (bfloat16 packs two heads to a row) is widened whole into its float32
    scratch first, tile by tile."""
    wide = list(wide)
    out = []
    for ref in refs:
        if ref.dtype == _F32:
            out.append(ref)
        else:
            out.append(wide.pop(0))
            out[-1][...] = ref[...].astype(_F32)
    return out, wide


def _fwd_kernel(dtype, interpret, q_ref, k_ref, v_ref, g_ref, beta_ref,
                o_ref, start_ref, state, *wide):
    heads = state.shape[0]
    read = functools.partial(_read, interpret)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[0, :, 0] = state[...]
    (q, k, v), wide = _widened((q_ref, k_ref, v_ref), wide)
    o = wide[0] if wide else o_ref
    first = pl.program_id(1) * heads

    def head(j, carry):
        after, out = head_forward(
            dtype, state[j], read(q, j), read(k, j), read(v, j),
            read(g_ref, j), _head_beta(beta_ref, first + j))
        state[j] = after
        _write(interpret, o, j, out)
        return carry
    lax.fori_loop(0, heads, head, 0)
    if wide:
        o_ref[...] = o[...].astype(o_ref.dtype)


def _bwd_kernel(dtype, interpret, q_ref, k_ref, v_ref, g_ref, beta_ref,
                do_ref, start_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                d_state, *wide):
    heads = d_state.shape[0]
    read = functools.partial(_read, interpret)

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    (q, k, v, d_o), wide = _widened((q_ref, k_ref, v_ref, do_ref), wide)
    narrow = (dq_ref, dk_ref, dv_ref)
    sinks = (*(wide or narrow), dg_ref)
    first = pl.program_id(1) * heads

    def head(j, carry):
        before, *grads, d_beta = head_backward(
            dtype, start_ref[0, j, 0], d_state[j], read(q, j), read(k, j),
            read(v, j), read(g_ref, j), _head_beta(beta_ref, first + j),
            read(d_o, j))
        d_state[j] = before
        for ref, grad in zip(sinks, grads):
            _write(interpret, ref, j, grad)
        dbeta_ref[0, j] = d_beta
        return carry
    lax.fori_loop(0, heads, head, 0)
    for ref, scratch in zip(narrow, wide):
        ref[...] = scratch[...].astype(ref.dtype)


def _grant(vmem):
    """What a kernel reckoned to hold `vmem` bytes asks of Mosaic: that and
    a quarter more; nothing where that is inside the default grant."""
    limit = vmem + vmem // 4
    return {"vmem_limit_bytes": limit} if limit > _MOSAIC_DEFAULT else {}


def _call(kernel, plan, name, vmem, **kw):
    params = {} if plan.interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_grant(vmem))}
    return pl.pallas_call(kernel, interpret=plan.interpret, name=name,
                          **params, **kw)


def _interpreted(interpret):
    if interpret is None:
        from . import is_tpu
        return not is_tpu()
    return bool(interpret)


def _specs(plan, heads, dk, dv, steps, backward):
    """Block specs of q, k, g; of v (and what is laid out as v); of beta;
    of the states, by grid index (batch, head block, token block); the
    backward walks the token blocks from the last."""
    def at(t):
        return steps - 1 - t if backward else t

    def tokens(width):
        return pl.BlockSpec((1, plan.block, plan.heads, width),
                            lambda b, h, t: (b, at(t), h, 0))
    every_head = pl.BlockSpec((1, plan.block, heads),
                              lambda b, h, t: (b, at(t), 0))
    state = pl.BlockSpec((1, plan.heads, 1, dv, dk),
                         lambda b, h, t: (b, h, at(t), 0, 0))
    return tokens(dk), tokens(dv), every_head, state


def _wide(plan, dtype, *widths):
    """The float32 scratch of the blocks of `widths` that are not float32
    themselves."""
    return [] if dtype == _F32 else [
        pltpu.VMEM((1, plan.block, plan.heads, width), _F32)
        for width in widths]


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_fwd(q, k, v, g, beta, interpret=None):
    """The gated delta rule from a zero state: q, k (B, L, H, dk) and v (B,
    L, H, dv) in one dtype, g (B, L, H, dk) and beta (B, L, H) float32, L
    `padded_length` of itself -> (o (B, L, H, dv) in q's dtype, the state
    each grid step started from (B, H, steps, dv, dk) float32, transposed
    as the kernels hold it)."""
    batch, length, heads, dk = q.shape
    dv = v.shape[3]
    plan = _plan(length, heads, dk, dv, q.dtype.itemsize,
                 _interpreted(interpret))
    steps = length // plan.block
    by_k, by_v, every_head, state = _specs(plan, heads, dk, dv, steps, False)
    return _call(
        functools.partial(_fwd_kernel, q.dtype, plan.interpret), plan,
        "gated_delta_rule_fwd", plan.fwd_vmem,
        grid=(batch, heads // plan.heads, steps),
        in_specs=[by_k, by_k, by_v, by_k, every_head],
        out_specs=[by_v, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct((batch, heads, steps, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((plan.heads, dv, dk), _F32),
                        *_wide(plan, q.dtype, dk, dk, dv, dv)],
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_bwd(q, k, v, g, beta, starts, d_o, interpret=None):
    """`delta_rule_fwd` taken back from d_o (B, L, H, dv): its inputs and
    the states it wrote are all it is given; a step's chunks are made
    again. -> (dq, dk, dv in q's dtype, dg float32, all laid out as their
    inputs; dbeta (B, L, H) float32)."""
    batch, length, heads, dk = q.shape
    dv = v.shape[3]
    plan = _plan(length, heads, dk, dv, q.dtype.itemsize,
                 _interpreted(interpret))
    steps = length // plan.block
    by_k, by_v, every_head, state = _specs(plan, heads, dk, dv, steps, True)
    row = pl.BlockSpec((1, plan.heads, 1, plan.block),
                       lambda b, h, t: (b, h, 0, steps - 1 - t))
    *grads, d_beta = _call(
        functools.partial(_bwd_kernel, q.dtype, plan.interpret), plan,
        "gated_delta_rule_bwd", plan.bwd_vmem,
        grid=(batch, heads // plan.heads, steps),
        in_specs=[by_k, by_k, by_v, by_k, every_head, by_v, state],
        out_specs=[by_k, by_k, by_v, by_k, row],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct((batch, heads, 1, length), _F32)],
        scratch_shapes=[pltpu.VMEM((plan.heads, dv, dk), _F32),
                        *_wide(plan, q.dtype, dk, dk, dv, dv, dk, dk, dv)],
        # dq, dk, dv, dg may take the place of q, k, v, g, which the caller
        # made again for this call alone: a grid step reads its blocks of
        # them before it writes the same blocks
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3},
    )(q, k, v, g, beta, d_o.astype(q.dtype), starts)
    return (*grads, jnp.moveaxis(d_beta[:, :, 0], 1, 2))
