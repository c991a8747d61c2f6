"""The expert layer's sum by token as one Pallas kernel (the TPU fast path
of `ops/_raw.py` `_sum_by_token` on the rungs of the row buffers; XLA's
scatter-add there is what this is held to).

    y[t] = sum over live rows r with token[r] == t of weight[r] * rows[r]

in float32, written once a row in the caller's dtype. The rows come in the
experts' order (`order[:capacity]`), the first `live` of them are the
groups' and the rest hold whatever the grouped products left, NaN
included: they are never read into a sum. A token with no live row reads
exactly 0. `weight` (float32, the forward's routing weights) multiplies a
row in float32; without it (the backward) the rows are added as they are.

The grid is (column blocks of D, row blocks), the row axis sequential. The
output's column block, (tokens, tn) in float32, is an accumulator resident
in VMEM for the whole row axis (8192 x 1152 x 4 = 36 MiB at the Mellum2
cell's size): the live rows' column block streams through, each row is
added at its token, and the block is written out once, narrowed, when the
last row block has been added. A row block past the live rows is never
fetched (its index map is clamped to the last live block) and adds nothing
(the loop over rows stops at `live`); in the last live slab a dead row's
add selects nothing. No sort: the token ids, and the weights, ride in SMEM
a row block at a time.

The accumulator is held as (tokens / 8, 8, tn): token t is sublane t % 8
of tile t // 8, so a row's add is a load, a select of one sublane and a
store of (8, tn) at a dynamic LEADING index, and a slab of 16 rows is read
once and broadcast a row at a time.

`plan` derives the column block from (tokens, D, the dtypes) under the
budget, the largest that divides D; ONE algorithm, no option. Off the chip
the same kernel runs interpreted.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _VMEM_BUDGET, _divisor, _grant, _ru

__all__ = ["sum_by_token", "plan"]

_LANES = 128
_SLAB = 16          # rows read at once: one packed bfloat16 tile of rows
_ROWS = 1024        # rows a block: XLA tiles a 1-D int32 array by 1024
_CHUNK = 256        # tokens zeroed or written out at once


class Plan(NamedTuple):
    tokens: int     # padded to the slab
    columns: int    # the column block, tn
    rows: int       # the row block
    vmem: int       # what the kernel holds


def plan(tokens, capacity, d, row_dtype, out_dtype):
    """The blocks of a sum of `capacity` rows of `d` (a multiple of 128)
    into `tokens` rows, or None where no column block of 128 or more keeps
    the accumulator, the output block and the rows' block (each double
    buffered but the accumulator) inside the budget."""
    padded = _ru(tokens, _SLAB)
    rows = min(_ROWS, _ru(capacity, _SLAB))
    row_bytes, out_bytes = (jnp.dtype(t).itemsize for t in (row_dtype,
                                                          out_dtype))
    for columns in range(d, 0, -_LANES):
        vmem = columns * (padded * (4 + 2 * out_bytes)
                          + 2 * rows * row_bytes)
        if d % columns == 0 and vmem <= _VMEM_BUDGET:
            return Plan(padded, columns, rows, vmem)
    return None


def _kernel(p, weighted, live_ref, token_ref, *refs):
    weight_ref = refs[0] if weighted else None
    rows_ref, out_ref, acc_ref = refs[-3:]
    j = pl.program_id(1)
    chunk = _divisor(p.tokens, _SLAB, _CHUNK)

    @pl.when(j == 0)
    def _():
        def zero(c, carry):
            acc_ref[pl.ds(c * (chunk // 8), chunk // 8)] = jnp.zeros(
                (chunk // 8, 8, p.columns), jnp.float32)
            return carry
        lax.fori_loop(0, p.tokens // chunk, zero, 0)

    count = jnp.clip(live_ref[0] - j * p.rows, 0, p.rows)
    sublane = lax.broadcasted_iota(jnp.int32, (8, p.columns), 0)

    def slab(s, carry):
        first = pl.multiple_of(s * _SLAB, _SLAB)
        rows = rows_ref[pl.ds(first, _SLAB), :].astype(jnp.float32)
        for i in range(_SLAB):
            r = first + i
            live = r < count
            # a dead row adds to sublane 8 of tile 0, which is no sublane
            token = jnp.where(live, token_ref[r], 0)
            at = jnp.where(live, token % 8, 8)
            row = jnp.broadcast_to(rows[i:i + 1], (8, p.columns))
            if weighted:
                row = row * weight_ref[r]
            tile = token // 8
            acc_ref[tile] = acc_ref[tile] + jnp.where(sublane == at, row, 0.0)
        return carry
    lax.fori_loop(0, (count + _SLAB - 1) // _SLAB, slab, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        def out(c, carry):
            block = acc_ref[pl.ds(c * (chunk // 8), chunk // 8)]
            out_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :] = (
                block.reshape(chunk, p.columns).astype(out_ref.dtype))
            return carry
        lax.fori_loop(0, p.tokens // chunk, out, 0)


def sum_by_token(rows, token, live, tokens, weight=None, *, out_dtype=None,
                 interpret=None):
    """rows (R, D) bfloat16 or float32, token (R,) int32 in [0, tokens), the
    first `live` (a traced int32) of them live; weight (R,) float32 or None
    -> (tokens, D) in `out_dtype` (rows' by default): token t's live rows,
    times their weight, summed in float32. D is a multiple of 128 and
    `plan` finds blocks for the shape (ops/select.py `sum_by_token`)."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    out_dtype = jnp.dtype(out_dtype or rows.dtype)
    capacity, d = rows.shape
    p = plan(tokens, capacity, d, rows.dtype, out_dtype)
    size = _ru(capacity, _SLAB)
    if size != capacity:
        rows = jnp.pad(rows, ((0, size - capacity), (0, 0)))
        token = jnp.pad(token, (0, size - capacity))
        if weight is not None:
            weight = jnp.pad(weight, (0, size - capacity))

    def live_block(j, live_ref):
        # past the live rows the block held is kept: nothing is fetched
        return jnp.minimum(j, jnp.maximum(live_ref[0] - 1, 0) // p.rows)

    def by_row(c, j, live_ref):
        return (live_block(j, live_ref),)
    smem = pl.BlockSpec((p.rows,), by_row, memory_space=pltpu.SMEM)
    in_specs = [smem] + ([smem] if weight is not None else []) + [
        pl.BlockSpec((p.rows, p.columns),
                     lambda c, j, live_ref: (live_block(j, live_ref), c))]
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), **_grant(p.vmem))}
    out = pl.pallas_call(
        functools.partial(_kernel, p, weight is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // p.columns, pl.cdiv(size, p.rows)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((p.tokens, p.columns),
                                   lambda c, j, live_ref: (0, c)),
            scratch_shapes=[pltpu.VMEM((p.tokens // 8, 8, p.columns),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((p.tokens, d), out_dtype),
        interpret=interpret, name="sum_by_token", **params,
    )(jnp.reshape(live, (1,)).astype(jnp.int32), token.astype(jnp.int32),
      *([] if weight is None else [weight.astype(jnp.float32)]), rows)
    return out[:tokens] if p.tokens != tokens else out
