"""Grouped matrix product for sparse experts: consecutive row groups of one
matrix, each against its own weight.

The kernels are the Mosaic grouped matmul that ships with jax
(`jax.experimental.pallas.ops.tpu.megablox`: `gmm` walks the row tiles the
groups cover and no other, `tgmm` gives a group's weight gradient). This
file gives them what the framework's step needs: tiles from the shape, and
a VJP of its own (the weight gradient through `tgmm`).

The kernels write the rows of the groups and NO other: the rest of the
result, and of the gradient with respect to the rows, is whatever the
buffer held. A dropless expert layer's buffer has a capacity, not a
count: the smallest rung of ops/_raw.py `row_capacities` that holds the
live rows (a quarter more than the even share when the routing is
balanced, tokens x top_k when it is not), so zeroing the rest here would
cost a pass over the buffer a product (on the chip, 8192 live rows of
65536: 0.78 ms for the three products, 0.91 ms for one such pass; PERF.md,
PR 28). The caller masks where it reads rows back (ops/_raw.py
`_rung_fwd`, `_rung_bwd`, `_held_rows`), fused into sums it makes anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from .flash_attention import _divisor

__all__ = ["grouped_matmul"]

_LANES = 128
_ROWS = 512         # row tile: the group boundaries cost a tile each
_MOST = 1152        # contraction / column tile: three (tile x tile) bf16
                    # operands, double buffered, and a float32 accumulator
                    # stay under the 16 MiB a kernel is granted


def _tiling(m, k, n):
    """Tiles that divide the shape: multiples of 128, the largest that fit."""
    return (_divisor(m, _LANES, _ROWS), _divisor(k, _LANES, _MOST),
            _divisor(n, _LANES, _MOST))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    out = gmm(lhs, rhs, group_sizes, lhs.dtype,
                        _tiling(*lhs.shape, rhs.shape[2]),
                        interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, res, grad):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    d_lhs = gmm(grad, rhs, group_sizes, lhs.dtype,
                          _tiling(m, n, k), transpose_rhs=True,
                          interpret=interpret)
    d_rhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                           _tiling(m, k, n), interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret=None):
    """lhs (M, K) in consecutive row groups of `group_sizes` (G,) int32, rhs
    (G, K, N) -> (M, N): rows of group g are lhs[rows] @ rhs[g]; rows beyond
    the groups are NOT written, in the result and in lhs's gradient alike.
    M, K and N are multiples of 128."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    with jax.named_scope("grouped_matmul"):
        return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), bool(interpret))
