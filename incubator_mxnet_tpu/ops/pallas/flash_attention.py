"""Flash attention pallas kernels (TPU fast path for multihead attention).

Replaces the reference's interleaved_matmul_selfatt_* / cuDNN attention
(src/operator/contrib/transformer.cc) with a FlashAttention-2 style tiled
kernel: online softmax over K/V blocks, O(L) memory, scores never hit HBM.
Forward saves the per-row logsumexp; backward recomputes scores blockwise,
in one kernel while a head's queries fit VMEM (`flash_attention_bwd`: up to
32768 positions in bf16) and in two beyond (dq; dk/dv). Operands keep their
dtype (bf16 on the chip); scores, softmax statistics and accumulators are
float32.

Tiling. A grid step costs ~0.4 us whatever it does, so a step does a
step's worth of work: one OUTER block of one sequence against an INNER
loop (`lax.fori_loop`) over sub-blocks of the other, whose operands stay
in VMEM.

- forward, dq: grid (batch*heads, lq / block_q, lk / k_major); the inner
  loop walks `block_k` keys at a time and, when causal, stops at the
  diagonal, so a block above it costs neither a grid step nor a DMA.
  m / l / acc are loop carries; they see scratch only between the steps
  of a streamed key axis.
- dk/dv: the mirror image. Grid (batch*heads, lk / block_k, lq / q_major);
  the inner loop walks query sub-blocks from the diagonal on. It computes
  the TRANSPOSED scores k q^T, so dv += p^T do and dk += ds^T q are plain
  products and lse / delta are used as the rows they are stored as. With
  the head's queries resident it adds each tile's ds k to a float32 dq
  held in VMEM across the key blocks: backward recomputes the scores and
  their exp once, and the dq kernel does not run.
- `k_major` (`q_major`) is the whole padded sequence while two operands of
  that length, double buffered, fit a third of `_VMEM_BUDGET` (96 of the
  128 MiB a v5e core has): the last grid axis then has ONE step, K and V
  (Q and dO) are fetched once a head (once a GROUP's key/value head: the
  block index does not change between its query heads), no carry is
  parked and backward is one kernel. That holds up to 32768 positions in
  bf16 and 16384 in float32, at d = 128 and at d = 64 alike (a row of 64
  takes the 128 lanes all the same; d = 256 halves both). From 32769
  (16385) on a sequence streams major blocks of at least 512 along that
  axis, with index maps clamped to the diagonal so that a skipped step
  re-uses the block it holds, and backward is `_dq` and `_dkv` apart.
  Which regime runs depends on the shape alone.
- what is asked of Mosaic: it grants a kernel 16 MiB of VMEM unless the
  call says otherwise, which at d = 128 in bf16 held 4096 resident
  positions. `_plan` reckons what each kernel holds (operands x pipeline
  buffers, the float32 dq and its output block, score tiles, accumulators,
  lse / delta rows at their sublane padding) and `_call` asks for that and
  a quarter (`vmem_limit_bytes`) where it passes the default; where it does
  not (1024 x 64: 10 MiB) nothing is asked and the kernels are built as
  before. tests/test_tpu_compile.py compiles each kernel inside the bare
  reckoning at 8192, 16384 (float32) and 32768.
- only the sub-blocks the diagonal (or the padding of the keys) crosses
  build a mask; the ones below it run a loop body without one.
- `window=w` (with `causal`): row r sees the w keys up to its own. The
  window is a BOUND, not a mask: the key loop of forward and dq starts at
  the first sub-block the window reaches, the query loop of dk/dv ends at
  the last one, and the streamed index maps are clamped on both sides, so
  a window layer costs window / length of a full one. Only the sub-blocks
  either edge crosses build a mask; where the blocks line up with the
  window (`_paired`) the forward folds the two masked tiles of a row block
  into one softmax pass. `window=None` builds today's kernels.
- grouped heads: K and V may hold fewer heads than Q (`group` query heads
  to one). Forward and dq read them through the index map (head b // group);
  dk/dv are written per QUERY head and summed over the group outside.
- keys in two parts (latent attention, `latent_flash_attention`): the
  score is q_n k_n^T + q_r k_r^T, two float32 products summed, where k_r
  is ONE part every head shares; the backward gives dq_n, dq_r, dk_n and dv,
  and k_r's gradient a head in float32, summed over the heads outside.
  The operands' shapes decide which form runs (`_In`), never a flag: one
  algorithm, the same loops, masks and plan.

`_plan` derives every block and every grant from (lq, lk, d, dtype) under
the budget; `block_q=` / `block_k=` / `vmem_budget=` override it for the
tests.

Layout (what Mosaic accepted, tests/test_tpu_compile.py):
- q/k/v/o are (batch*heads, seq, head_dim). A block's last dimension is the
  array's FULL last dimension, so head_dim is left as it is when it is a
  multiple of 128 or one of 64 / 32 / 16 / 8 (an even split of the 128
  lanes), and padded to 128 lanes otherwise (d = 80; keys of 192 in one
  part to 256);
- in two parts nothing is padded to the lanes or transposed: q_n, o and
  dO are (batch, seq, heads x width) as the products wrote them and head h
  is lane block h (`_by_head`); kv is the key/value product's (batch, seq,
  heads x (dn + dv)), a head's block split in VMEM at lane dn, and dk_n and
  dv go back into ONE array of that layout; k_r is (batch, seq, dr) under
  an index map that a batch's heads share, so it is fetched once (`_shared`);
  q_r and dq_r are (batch*heads, seq, dr), a head a row, since a block of
  64 lanes of a wider array is not one Mosaic takes. With the queries
  resident the backward makes delta itself from dO and o (`_own_delta`);
- the sequences are padded to the block in use only: a multiple of 128 on
  the chip (lane-dense score tiles, 128-aligned lane slices), of 16 when
  interpreted; a long sequence to the largest block that wastes under an
  eighth of it;
- lse/delta ride as (batch*heads, 1, seq) rows. The forward writes a
  (1, 1, block_q) block; dq turns its block into a column once a grid
  step; dk/dv slices the resident row at 128-aligned lane offsets.

Off-TPU the same kernels run with interpret=True (tests/conftest sets CPU).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "latent_flash_attention"]

_NEG = -1e30
_LANES = 128

# What one kernel may hold in VMEM. A v5e core has 128 MiB of it. Mosaic
# grants a kernel 16 MiB unless the call asks for more
# (`CompilerParams.vmem_limit_bytes`), and XLA keeps the rest, 112 MiB, as
# the pool its own fusions prefetch into (the 117,440,512 bytes of `color 1`
# in every program's buffer assignment). What a kernel is granted comes out
# of that pool for the length of the call only. So the plan may fill three
# quarters of the core, 96 MiB: the other quarter is XLA's own scoped 16 MiB
# for the fusions on either side of the call, and the margin `_call` adds
# to the reckoned need for what Mosaic lays out itself. Split in three:
# - the operands of the inner loop (K and V, or Q and dO), 2 arrays x 2
#   pipeline buffers x length x 128 lanes x itemsize: 32 MiB holds 32768
#   positions in bf16 at any head size up to 128;
# - what the merged backward keeps of the whole query sequence beside them:
#   the float32 dq it accumulates and dq's double-buffered output block,
#   (4 + 2 x itemsize) x length x 128 lanes, never more than the operands;
# - everything that does not grow with the length: the score tiles (~6 live
#   (outer x inner) arrays of 32 bits: s, p, dp, ds, the mask's iota, the
#   bf16 casts; 6 MiB at 512 x 512, the fastest of 13 tilings on the chip),
#   the outer block's operands and outputs (double buffered), the float32
#   accumulators, and the lse / delta rows at 8 sublanes a row.
_VMEM = 128 * 1024 * 1024
_VMEM_BUDGET = _VMEM * 3 // 4
_MOSAIC_DEFAULT = 16 * 1024 * 1024    # a kernel's grant when it asks nothing
_LIVE_TILES = 6
_OUTER, _INNER = 512, 512     # largest outer block and inner sub-block
_MIN_MAJOR = 512              # least a streamed major block may hold

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _ru(x, m):
    return (x + m - 1) // m * m


def _divisor(length, align, most):
    """Largest multiple of `align` that divides `length` and is <= `most`
    (`align` itself if there is none)."""
    n = length // align
    return align * max(c for c in range(1, n + 1)
                       if n % c == 0 and (c * align <= most or c == 1))


def _head_lanes(d):
    """The head size the kernels see for a head of d: d itself when it is a
    multiple of 128 or an even split of the 128 lanes, else padded (80 ->
    128, 192 -> 256)."""
    return d if d % _LANES == 0 or d in (8, 16, 32, 64) else _ru(d, _LANES)


class _Plan(NamedTuple):
    """Block sizes of the three kernels; static, part of the jit key."""
    lqp: int        # padded lengths
    lkp: int
    dp: int         # head size the kernels see
    bq: int         # forward / dq: outer query block, inner key sub-block,
    bk: int         # and the major key block held in VMEM
    k_major: int
    dkv_bk: int     # dk/dv: outer key block, inner query sub-block, major
    dkv_bq: int     # query block
    q_major: int
    fwd_vmem: int   # bytes each kernel holds in VMEM (`_call` asks Mosaic for
    dq_vmem: int    # them where they pass its default grant)
    dkv_vmem: int


def _plan(lq, lk, d, itemsize, interpret, block_q=None, block_k=None,
          vmem_budget=_VMEM_BUDGET):
    align = 16 if interpret else _LANES
    dp = _head_lanes(d)
    lanes = _ru(dp, _LANES)

    def blocks(length, override):
        if override is not None:
            blk = _ru(min(override, _ru(length, align)), align)
            return _ru(length, blk), blk, blk
        # one block if the sequence is short; else the largest block whose
        # padding costs at most an eighth (6000 -> 12 x 512, not 47 x 128)
        outer = _ru(length, align)
        if outer > _OUTER:
            outer = max((c for c in range(align, _OUTER + 1, align)
                         if _ru(length, c) - length <= length // 8),
                        default=align)
        return _ru(length, outer), outer, _divisor(outer, align, _INNER)

    def major(lp, sub):
        # positions of two double-buffered operands in a third of the budget
        fit = vmem_budget // 3 // (4 * lanes * itemsize)
        if lp <= fit:
            return lp
        return _divisor(lp, sub, max(fit, _MIN_MAJOR, sub))

    lqp, bq_outer, bq_inner = blocks(lq, block_q)
    lkp, bk_outer, bk_inner = blocks(lk, block_k)
    # the score tile of a step: ~4 live float32 arrays in a third of the
    # budget; shrink the inner sub-block until it fits (the tests' tight
    # budgets only: the chip's holds any tile up to `_OUTER` x `_INNER`)
    most = max(vmem_budget // 3 // 16, align * align)
    while bq_outer * bk_inner > most and bk_inner > align:
        bk_inner = _divisor(bk_outer, align, bk_inner - align)
    while bk_outer * bq_inner > most and bq_inner > align:
        bq_inner = _divisor(bq_outer, align, bq_inner - align)
    k_major, q_major = major(lkp, bk_inner), major(lqp, bq_inner)

    def held(rows, cols, size=4, buffers=1):
        # a (rows, cols) array in VMEM: 128 lanes a row, 32-bit sublane
        # tiles (8 rows of float32, 16 of bf16); 2 buffers where the
        # pipeline fetches the next block beside the one in use
        return buffers * _ru(rows, 32 // size) * _ru(cols, _LANES) * size

    def carry(steps, nbytes):
        # a loop carry, and the scratch it is parked in between the steps
        # of a streamed axis
        return nbytes * (1 if steps == 1 else 2)

    kv = 2 * held(k_major, dp, itemsize, 2)
    q_block = held(bq_outer, dp, itemsize, 2)
    tiles = _LIVE_TILES * held(bq_outer, bk_inner)
    k_steps, q_steps = lkp // k_major, lqp // q_major
    fwd = (2 * q_block + kv + held(1, bq_outer, buffers=2) + tiles
           + carry(k_steps, held(bq_outer, dp) + 2 * held(bq_outer, 1)))
    dq = (3 * q_block + kv + 2 * held(1, bq_outer, buffers=2) + tiles
          + carry(k_steps, held(bq_outer, dp)))
    # dk and dv leave in float32 when a group's are summed outside
    dkv = (2 * held(q_major, dp, itemsize, 2) + 2 * held(1, q_major, buffers=2)
           + 2 * held(bk_outer, dp, itemsize, 2) + 2 * held(bk_outer, dp, 4, 2)
           + _LIVE_TILES * held(bk_outer, bq_inner)
           + carry(q_steps, 2 * held(bk_outer, dp)))
    if q_steps == 1:        # `_merged`: dq in float32, and its output block
        dkv += held(lqp, dp) + held(lqp, dp, itemsize, 2)
    return _Plan(lqp, lkp, dp, bq_outer, bk_inner, k_major,
                 bk_outer, bq_inner, q_major, fwd, dq, dkv)


class _Cfg(NamedTuple):
    scale: float
    causal: bool
    kv_len: int      # keys that are not padding
    offset: int      # lk - lq: query row r sees key columns <= r + offset
    interpret: bool
    plan: _Plan
    window: int | None = None   # ... and > r + offset - window
    group: int = 1              # query heads to one key/value head
    heads: int = 0              # the latent layout's heads side by side
    #                             along the lanes; 0: one part (below)


class _In(NamedTuple):
    """The refs a kernel reads the attention's operands from, by the
    operands' layout. One part: q, k and v, a head a row of the first
    axis. Two (the latent layout): the queries as (q_n, q_r), the keys as
    (kv, k_r), where a head's block of kv holds its key part in the first
    `split` lanes and its values after them, and k_r is the one part every
    head shares; the score is q_n k_n^T + q_r k_r^T."""
    q: tuple
    k: tuple
    v: object
    split: int | None = None

    def queries(self, rows=None):
        if rows is None:
            return [ref[0] for ref in self.q]
        return [ref[0, rows, :] for ref in self.q]

    def keys(self, rows=None):
        if self.split is None:
            k, = self.k
            return [k[0] if rows is None else k[0, rows, :]]
        kv, k_r = self.k
        rows = slice(None) if rows is None else rows
        return [kv[0, rows, :self.split], k_r[0, rows, :]]

    def values(self, rows=None):
        if self.split is None:
            return self.v[0] if rows is None else self.v[0, rows, :]
        return self.v[0, slice(None) if rows is None else rows, self.split:]

    @property
    def dv(self):
        return self.v.shape[2] - (self.split or 0)

    def store_keys(self, outs, dk, dv, scale):
        """Write a key block's gradients: dk and dv, or the latent layout's
        dk_n and dv side by side in ONE block of kv's layout and k_r's
        gradient (this head's share, float32) beside it."""
        if self.split is None:
            dk_ref, dv_ref = outs
            dk_ref[0] = (dk[0] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
            return
        dkv_ref, dkr_ref = outs
        dkv_ref[0, :, :self.split] = (dk[0] * scale).astype(dkv_ref.dtype)
        dkv_ref[0, :, self.split:] = dv.astype(dkv_ref.dtype)
        dkr_ref[0] = (dk[1] * scale).astype(dkr_ref.dtype)


def _ins(cfg, refs):
    """(the operands' `_In`, the refs after them)."""
    if cfg.heads:
        (q_n, q_r, kv, k_r), rest = refs[:4], refs[4:]
        return _In((q_n, q_r), (kv, k_r), kv, q_n.shape[2]), rest
    (q, k, v), rest = refs[:3], refs[3:]
    return _In((q,), (k,), v), rest


def _dot_parts(xs, ys, dims):
    """sum_i xs[i] . ys[i] over `dims`, in float32: a score tile or its
    transpose from the parts of the queries and of the keys."""
    s = jax.lax.dot_general(xs[0], ys[0], dims,
                            preferred_element_type=jnp.float32)
    for x, y in zip(xs[1:], ys[1:]):
        s = s + jax.lax.dot_general(x, y, dims,
                                    preferred_element_type=jnp.float32)
    return s


def _by_head(cfg, index_map):
    """index_map for an operand of the latent layout (batch, seq, heads x
    width): grid row b is head b % heads of batch b // heads, one block of
    the lanes."""
    heads = cfg.heads

    def by_head(b, *axes):
        _, row, _ = index_map(b, *axes)
        return b // heads, row, b % heads
    return by_head


def _shared(cfg, index_map):
    """index_map for the part every head shares, (batch, seq, width): the
    same block for all of a batch's heads, so it is fetched once."""
    heads = cfg.heads

    def shared(b, *axes):
        _, row, _ = index_map(b, *axes)
        return b // heads, row, 0
    return shared


def _in_specs(cfg, q, k, v, q_rows, k_rows, q_map, kv_map):
    """BlockSpecs of the operands: queries of `q_rows` rows at q_map, keys
    and values of `k_rows` at kv_map, in either layout."""
    if not cfg.heads:
        d = q.shape[2]
        return [_vspec((1, q_rows, d), q_map),
                _vspec((1, k_rows, d), kv_map),
                _vspec((1, k_rows, v.shape[2]), kv_map)]
    (q_n, q_r), (kv, k_r) = q, k
    heads = cfg.heads
    return [_vspec((1, q_rows, q_n.shape[2] // heads), _by_head(cfg, q_map)),
            _vspec((1, q_rows, q_r.shape[2]), q_map),
            _vspec((1, k_rows, kv.shape[2] // heads), _by_head(cfg, kv_map)),
            _vspec((1, k_rows, k_r.shape[2]), _shared(cfg, kv_map))]


def _shapes(cfg, q, k, v):
    """(grid rows, lq, lk, the query parts' widths, the values' width)."""
    if not cfg.heads:
        return q.shape[0], q.shape[1], k.shape[1], (q.shape[2],), v.shape[2]
    (q_n, q_r), (kv, _) = q, k
    dn = q_n.shape[2] // cfg.heads
    return (q_r.shape[0], q_r.shape[1], kv.shape[1], (dn, q_r.shape[2]),
            kv.shape[2] // cfg.heads - dn)


def _vspec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _grant(vmem):
    """What a kernel reckoned to hold `vmem` bytes asks of Mosaic: that and
    a quarter more, for what Mosaic lays out itself (relayouts, spilled
    registers); nothing where that is inside the default grant, so that a
    short sequence's kernels are built as if no one had asked."""
    limit = vmem + vmem // 4
    return {"vmem_limit_bytes": limit} if limit > _MOSAIC_DEFAULT else {}


def _call(kernel, cfg, name, vmem, carried=(2,), **kw):
    """pallas_call of a three-axis grid; `carried` are the axes along
    which a step hands something to the next, `vmem` what the plan reckons
    the kernel holds."""
    params = {} if cfg.interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple("arbitrary" if axis in carried else
                                  "parallel" for axis in range(3)),
        **_grant(vmem))}
    return pl.pallas_call(kernel, interpret=cfg.interpret, name=name,
                          **params, **kw)


def _masker(cfg, shape, rows_axis):
    """mask(row0, col0) -> which entries of a score tile count, for the tile
    whose first query row is row0 and first key column col0; queries run
    along `rows_axis`. The iotas are built here, once a grid step, outside
    the loops: a masked tile then costs a compare and a select an entry
    (and nothing at all tests the padding where the keys have none)."""
    padded = cfg.kv_len != cfg.plan.lkp
    if cfg.causal:
        diff = (jax.lax.broadcasted_iota(jnp.int32, shape, rows_axis)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis))
    if padded:
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis)

    def mask(row0, col0):
        m = None
        if cfg.causal:      # row + offset >= col
            m = diff >= col0 - row0 - cfg.offset
        if cfg.window is not None:      # row + offset - col < window
            m = jnp.logical_and(
                m, diff < cfg.window + col0 - row0 - cfg.offset)
        if padded:
            inside = col < cfg.kv_len - col0
            m = inside if m is None else jnp.logical_and(m, inside)
        return m
    return mask


def _where(mask, x, other):
    return x if mask is None else jnp.where(mask, x, other)


def _loops(bounds, body, carry, masked_first):
    """Run body(masked)(i, carry) over consecutive ranges: `bounds` are
    their edges, and the ranges take turns with and without the mask."""
    masked = masked_first
    for lo, hi in zip(bounds, bounds[1:]):
        carry = jax.lax.fori_loop(lo, hi, body(masked), carry)
        masked = not masked
    return carry


def _carry(scratch, init, step, loop):
    """loop(carry) over the steps of the last grid axis. With ONE step
    (operands resident) the carry never leaves the loop; with more it is
    parked between steps in `scratch`, whose float32 (rows, 128) buffers
    hold a (rows, 1) statistic broadcast along the lanes (a one-lane
    scratch costs a relayout a step: +0.7 ms a layer on the chip)."""
    if not scratch:
        return loop(init)

    @pl.when(step == 0)
    def _():
        for ref, x in zip(scratch, init):
            ref[...] = jnp.broadcast_to(x, ref.shape)

    carry = loop(tuple(ref[:, :x.shape[1]] for ref, x in zip(scratch, init)))
    for ref, x in zip(scratch, carry):
        ref[...] = jnp.broadcast_to(x, ref.shape)
    return carry


def _scratch(steps, *shapes):
    """Scratch of `_carry`: none when the last grid axis has one step."""
    if steps == 1:
        return []
    return [pltpu.VMEM((rows, _ru(cols, _LANES) if cols == 1 else cols),
                       jnp.float32) for rows, cols in shapes]


def _at_last(steps, step, fn):
    if steps == 1:
        fn()
    else:
        pl.when(step == steps - 1)(fn)


def _key_range(cfg, qi, kj, bq, bk, k_major):
    """Sub-blocks of major key block kj that query block qi runs, as the
    edges of `_loops`: without a window (0, full, end), local indices
    [0, full) need no mask and [full, end) do; with one (first, inside, full,
    end), where [first, inside) are the sub-blocks the window's edge crosses
    and take the mask too."""
    subs = k_major // bk
    lo = kj * subs
    full = cfg.kv_len // bk                   # before the padding
    end = -(-cfg.kv_len // bk)
    if cfg.causal:
        # columns <= row + offset; the first row decides what is full
        full = jnp.minimum(full, (qi * bq + cfg.offset + 1) // bk)
        end = jnp.minimum(end, ((qi + 1) * bq + cfg.offset + bk - 1) // bk)
    end = jnp.clip(end - lo, 0, subs)
    if cfg.window is None:
        return 0, jnp.clip(full - lo, 0, end), end
    # columns > row + offset - window: the first row decides where the keys
    # start, the last row which sub-block is inside for every row
    reach = qi * bq + cfg.offset - cfg.window + 1
    first = jnp.clip(jnp.maximum(reach, 0) // bk - lo, 0, end)
    inside = jnp.clip((jnp.maximum(reach + bq - 1, 0) + bk - 1) // bk - lo,
                      first, end)
    return first, inside, jnp.clip(full - lo, inside, end), end


def _kv_map(cfg, num):
    """Index map of K and V for the forward and dq grids, clamped to the
    major key blocks query block i reads (down to the diagonal, up from
    where the window starts): a step beyond either re-uses the block it
    holds. Query head b reads key/value head b // group."""
    bq, k_major = cfg.plan.bq, cfg.plan.k_major

    def kv_map(b, i, j):
        if cfg.causal:
            last = ((i + 1) * bq - 1 + cfg.offset) // k_major
            j = jnp.minimum(j, jnp.clip(last, 0, num - 1))
        if cfg.window is not None:
            reach = i * bq + cfg.offset - cfg.window + 1
            j = jnp.maximum(j, jnp.clip(reach // k_major, 0, num - 1))
        return (b // cfg.group if cfg.group > 1 else b, j, 0)
    return kv_map


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _paired(cfg):
    """Whether a row block's window-edge tile and its diagonal tile are
    exact complements: with square tiles and window and offset whole
    numbers of them, entry (r, c) counts in the first iff c > r and in the
    second iff c <= r. The forward then selects the two score tiles into
    one and makes ONE max / exp / sum pass for both (two of the three
    tiles of a row block at window = 2 x block; 0.47 ms of 2.39 a layer at
    8192 x 128, window 1024, on the chip). K and V resident and unpadded
    only: both tiles are then at hand."""
    p_ = cfg.plan
    return (cfg.window is not None and p_.bq == p_.bk
            and cfg.window % p_.bk == 0 and cfg.offset % p_.bk == 0
            and cfg.kv_len == p_.lkp == p_.k_major)


def _fwd_kernel(*refs, cfg):
    ins, (o_ref, lse_ref, *scratch) = _ins(cfg, refs)
    p_ = cfg.plan
    bq, bk = p_.bq, p_.bk
    qi, kj = pl.program_id(1), pl.program_id(2)
    q = ins.queries()
    edges = _key_range(cfg, qi, kj, bq, bk, p_.k_major)
    mask = _masker(cfg, (bq, bk), 0)

    def tile(kb):
        """Scores and values of key sub-block kb, and its first key."""
        start = pl.multiple_of(kb * bk, bk)
        k = ins.keys(pl.ds(start, bk))
        v = ins.values(pl.ds(start, bk))
        s = _dot_parts(q, k, _NT) * cfg.scale
        return s, v, start

    def online(carry, s, parts):
        """One step of the online softmax over the score tile s;
        parts(p) gives the (probabilities, values) pairs whose products
        the step adds."""
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha
        for part, v in parts(p):
            acc = acc + jax.lax.dot(part.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    def body(masked):
        def step(kb, carry):
            s, v, start = tile(kb)
            if masked:
                s = _where(mask(qi * bq, kj * p_.k_major + start), s, _NEG)
            return online(carry, s, lambda p: [(p, v)])
        return step

    def paired(carry):
        """A row block whose two masked tiles are one: the sub-blocks
        between them first, then the window-edge tile and the diagonal
        tile through ONE max / exp / sum pass."""
        last = qi + cfg.offset // bk            # the diagonal's sub-block
        edge = last - cfg.window // bk          # the window edge's
        carry = jax.lax.fori_loop(jnp.maximum(edge + 1, 0), last,
                                  body(False), carry)

        def both(carry):
            lower = (jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                     <= jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            (s_d, v_d, _), (s_e, v_e, _) = tile(last), tile(edge)
            return online(carry, jnp.where(lower, s_d, s_e), lambda p: [
                (jnp.where(lower, p, 0.0), v_d),
                (jnp.where(lower, 0.0, p), v_e)])

        # the first row blocks have no edge yet: the diagonal's tile alone
        return jax.lax.cond(edge >= 0, both,
                            lambda carry: body(True)(last, carry), carry)

    init = (jnp.full((bq, 1), _NEG, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, ins.dv), jnp.float32))
    if _paired(cfg):
        m, l, acc = paired(init)
    else:
        m, l, acc = _carry(scratch, init, kj, lambda carry: _loops(
            edges, body, carry, cfg.window is not None))

    def store():
        l1 = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l1).astype(o_ref.dtype)
        # the column as a row: broadcast along the lanes, transposed on the
        # XLU (a reshape costs four times as much, 0.19 ms a layer)
        lse = jnp.broadcast_to(m + jnp.log(l1), (bq, _LANES))
        lse_ref[0] = lse.T[:1]

    _at_last(p_.lkp // p_.k_major, kj, store)


def _fwd(q, k, v, cfg):
    p_ = cfg.plan
    bh, lq, lk, _, dv = _shapes(cfg, q, k, v)   # dv: the output's head size
    bq, km = p_.bq, p_.k_major
    num_q, num_k = lq // bq, lk // km
    kv_map = _kv_map(cfg, num_k)
    q_map = lambda b, i, j: (b, i, 0)       # noqa: E731
    if cfg.heads:           # o as (batch, lq, heads x dv), what W_o reads
        o_spec = _vspec((1, bq, dv), _by_head(cfg, q_map))
        o_shape = (bh // cfg.heads, lq, cfg.heads * dv)
    else:
        o_spec, o_shape = _vspec((1, bq, dv), q_map), (bh, lq, dv)
    return _call(
        functools.partial(_fwd_kernel, cfg=cfg), cfg, "flash_attention_fwd",
        p_.fwd_vmem, grid=(bh, num_q, num_k),
        in_specs=_in_specs(cfg, q, k, v, bq, km, q_map, kv_map),
        out_specs=[o_spec,
                   _vspec((1, 1, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(o_shape, jax.tree.leaves(q)[0].dtype),
                   jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32)],
        scratch_shapes=_scratch(num_k, (bq, 1), (bq, 1), (bq, dv)),
    )(*jax.tree.leaves((q, k, v)))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, cfg):
    ins, (do_ref, lse_ref, dl_ref, *rest) = _ins(cfg, refs)
    dq_refs, scratch = rest[:len(ins.q)], rest[len(ins.q):]
    p_ = cfg.plan
    bq, bk = p_.bq, p_.bk
    qi, kj = pl.program_id(1), pl.program_id(2)
    q, do = ins.queries(), do_ref[0]
    lse = lse_ref[0].reshape(bq, 1)
    dl = dl_ref[0].reshape(bq, 1)
    edges = _key_range(cfg, qi, kj, bq, bk, p_.k_major)
    mask = _masker(cfg, (bq, bk), 0)

    def body(masked):
        def step(kb, carry):
            start = pl.multiple_of(kb * bk, bk)
            k = ins.keys(pl.ds(start, bk))
            v = ins.values(pl.ds(start, bk))
            s = _dot_parts(q, k, _NT) * cfg.scale
            p = jnp.exp(s - lse)
            if masked:
                p = _where(mask(qi * bq, kj * p_.k_major + start), p, 0.0)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - dl)).astype(k[0].dtype)  # x scale: on the sum
            return tuple(dq + jax.lax.dot(ds, part,
                                          preferred_element_type=jnp.float32)
                         for dq, part in zip(carry, k))
        return step

    dq = _carry(scratch, tuple(jnp.zeros(x.shape, jnp.float32) for x in q),
                kj, lambda carry: _loops(edges, body, carry,
                                         cfg.window is not None))

    def store():
        for ref, part in zip(dq_refs, dq):
            ref[0] = (part * cfg.scale).astype(ref.dtype)

    _at_last(p_.lkp // p_.k_major, kj, store)


def _merged(plan):
    """One backward kernel, not two: when the queries of a head are
    resident for dk/dv anyway."""
    return plan.q_major == plan.lqp


def _own_delta(cfg):
    """Whether the backward kernel makes delta = rowsum(dO o) itself, from
    the output where the forward wrote it: in the latent layout, with a
    head's dO and o resident. XLA's sum over each head's 128 lanes of
    (batch, lq, heads x dv) writes the float32 product twice (a layout
    change) where the kernel reads o once."""
    return bool(cfg.heads) and _merged(cfg.plan)


def _dkv_kernel(*refs, cfg):
    """dk and dv of one key block. With Q and dO resident (`_merged`) the
    same pass gives dq as well: every tile adds its ds k to the head's
    float32 dq, kept in VMEM across the key blocks, so the scores and
    their exp are recomputed once in backward, not twice."""
    ins, (do_ref, lse_ref, dl_ref, *rest) = _ins(cfg, refs)
    outs, scratch = rest[:2], rest[2:]
    p_ = cfg.plan
    bk, bq = p_.dkv_bk, p_.dkv_bq
    subs, num_q = p_.q_major // bq, p_.lqp // bq
    kj, qm = pl.program_id(1), pl.program_id(2)
    k, v = ins.keys(), ins.values()
    if _merged(p_):
        parts = len(ins.q)
        dq_refs, dq_scr, scratch = (scratch[:parts],
                                    scratch[parts:2 * parts], ())
        if _own_delta(cfg):     # dl_ref is o; delta's row is made below
            o_ref, dl_ref = dl_ref, rest[-1]

        @pl.when(kj == 0)
        def _():
            for ref in dq_scr:
                ref[...] = jnp.zeros_like(ref)
            if _own_delta(cfg):
                def row(qb, carry):
                    rows = pl.ds(pl.multiple_of(qb * bq, bq), bq)
                    dl = jnp.sum(do_ref[0, rows, :].astype(jnp.float32)
                                 * o_ref[0, rows, :].astype(jnp.float32),
                                 axis=1, keepdims=True)
                    # the column as a row, as the forward stores lse
                    dl_ref[0, :, rows] = jnp.broadcast_to(
                        dl, (bq, _LANES)).T[:1]
                    return carry
                jax.lax.fori_loop(0, num_q, row, 0)
    # query sub-blocks this key block meets: [first, last); the diagonal
    # crosses [first, full), the window's edge [inside, last), the padding
    # of the keys every one of them
    first, full, last = 0, 0, num_q
    if cfg.causal:
        first = jnp.clip((kj * bk - cfg.offset) // bq, 0, num_q)
        full = jnp.clip(((kj + 1) * bk - 1 - cfg.offset + bq - 1) // bq,
                        first, num_q)
    if cfg.window is not None:
        # rows < col + window - offset: the last column decides where the
        # queries end, the first which sub-block is inside for every row
        reach = cfg.window - cfg.offset + kj * bk
        last = jnp.clip((reach + bk - 2) // bq + 1, full, num_q)
    if cfg.kv_len % bk:
        full = jnp.where((kj + 1) * bk > cfg.kv_len, last, full)
    lo = qm * subs
    edges = [jnp.clip(first - lo, 0, subs)]
    edges.append(jnp.clip(full - lo, edges[0], subs))
    if cfg.window is None:
        edges.append(subs)
    else:
        edges.append(jnp.clip(reach // bq - lo, edges[1], subs))
        edges.append(jnp.clip(last - lo, edges[2], subs))
    mask = _masker(cfg, (bk, bq), 1)

    def body(masked):
        def step(qb, carry):
            *dk, dv = carry
            start = pl.multiple_of(qb * bq, bq)
            q = ins.queries(pl.ds(start, bq))
            do = do_ref[0, pl.ds(start, bq), :]
            lse = lse_ref[0, :, pl.ds(start, bq)]
            dl = dl_ref[0, :, pl.ds(start, bq)]
            # transposed tiles: keys along rows, queries along lanes
            s = _dot_parts(k, q, _NT) * cfg.scale
            p = jnp.exp(s - lse)
            if masked:
                p = _where(mask(qm * p_.q_major + start, kj * bk), p, 0.0)
            dv = dv + jax.lax.dot(p.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - dl)).astype(q[0].dtype)  # x scale: on the sum
            dk = [acc + jax.lax.dot(ds, part,
                                    preferred_element_type=jnp.float32)
                  for acc, part in zip(dk, q)]
            if _merged(p_):
                for ref, part in zip(dq_scr, k):
                    ref[pl.ds(start, bq), :] += jax.lax.dot_general(
                        ds, part, _TN, preferred_element_type=jnp.float32)
            return (*dk, dv)
        return step

    zeros = {}      # one array of zeros a shape

    def zero(shape):
        if shape not in zeros:
            zeros[shape] = jnp.zeros(shape, jnp.float32)
        return zeros[shape]

    *dk, dv = _carry(
        scratch, tuple(zero(x.shape) for x in k) + (zero(v.shape),), qm,
        lambda carry: _loops(edges, body, carry, True))
    _at_last(p_.lqp // p_.q_major, qm,
             lambda: ins.store_keys(outs, dk, dv, cfg.scale))
    if _merged(p_):
        @pl.when(kj == p_.lkp // bk - 1)
        def _():
            for ref, part in zip(dq_refs, dq_scr):
                ref[0] = (part[...] * cfg.scale).astype(ref.dtype)


def _bwd(cfg, res, dout):
    p_ = cfg.plan
    q, k, v, out, lse = res
    do, _ = dout
    bh, lq, lk, widths, dv_ = _shapes(cfg, q, k, v)
    heads = cfg.heads
    if _own_delta(cfg):
        delta = out             # the kernel makes delta's rows from it
    elif heads:     # do and out as (batch, lq, heads x dv)
        delta = jnp.sum((do.astype(jnp.float32) * out.astype(jnp.float32))
                        .reshape(bh // heads, lq, heads, dv_), axis=-1)
        delta = delta.transpose(0, 2, 1).reshape(bh, 1, lq)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).reshape(bh, 1, lq)

    bq, km = p_.bq, p_.k_major
    num_q, num_k = lq // bq, lk // km
    kv_map = _kv_map(cfg, num_k)
    merged = _merged(p_)

    def by_head(index_map):
        return _by_head(cfg, index_map) if heads else index_map

    if merged:
        dq = None
    else:
        q_map = lambda b, i, j: (b, i, 0)       # noqa: E731
        if heads:
            dq_specs = [_vspec((1, bq, w), m) for w, m in
                        zip(widths, (_by_head(cfg, q_map), q_map))]
            dq_shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in q]
        else:
            dq_specs = _vspec((1, bq, widths[0]), q_map)
            dq_shapes = jax.ShapeDtypeStruct(q.shape, q.dtype)
        dq = _call(
            functools.partial(_dq_kernel, cfg=cfg), cfg, "flash_attention_dq",
            p_.dq_vmem, grid=(bh, num_q, num_k),
            in_specs=_in_specs(cfg, q, k, v, bq, km, q_map, kv_map)
            + [_vspec((1, bq, dv_), by_head(q_map)),
               _vspec((1, 1, bq), lambda b, i, j: (b, 0, i)),
               _vspec((1, 1, bq), lambda b, i, j: (b, 0, i))],
            out_specs=dq_specs, out_shape=dq_shapes,
            scratch_shapes=_scratch(num_k, *((bq, w) for w in widths)),
        )(*jax.tree.leaves((q, k, v)), do, lse, delta)

    bk, qm = p_.dkv_bk, p_.q_major
    num_qm = lq // qm

    def met(j, i):
        # index map clamp: the major query blocks key block j meets, from
        # the diagonal to where the window ends
        if cfg.causal:
            i = jnp.maximum(i, jnp.clip((j * bk - cfg.offset) // qm,
                                        0, num_qm - 1))
        if cfg.window is not None:
            end = (j + 1) * bk - 2 + cfg.window - cfg.offset
            i = jnp.minimum(i, jnp.clip(end // qm, 0, num_qm - 1))
        return i

    def q_map(b, j, i):
        return (b, met(j, i), 0)

    def row_map(b, j, i):
        return (b, 0, met(j, i))

    def kv_head(b, j, i):
        return (b // cfg.group if cfg.group > 1 else b, j, 0)

    if heads:
        # dk_n and dv in ONE array of kv's layout, what kv_up's backward
        # reads; k_r's gradient a head in float32, summed below
        (kv, k_r), key_map = k, (lambda b, j, i: (b, j, 0))
        whole = [_vspec((1, lq, w), m) for w, m in zip(
            widths, (_by_head(cfg, lambda b, j, i: (b, 0, 0)),
                     lambda b, j, i: (b, 0, 0)))]
        dkv_specs = [_vspec((1, bk, kv.shape[2] // heads),
                            _by_head(cfg, key_map)),
                     _vspec((1, bk, widths[1]), key_map)]
        dkv_shapes = [jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                      jax.ShapeDtypeStruct((bh, lk, widths[1]), jnp.float32)]
        dq_shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in q]
    else:
        whole = [_vspec((1, lq, widths[0]), lambda b, j, i: (b, 0, 0))]
        dkv_specs = [_vspec((1, bk, widths[0]), lambda b, j, i: (b, j, 0)),
                     _vspec((1, bk, dv_), lambda b, j, i: (b, j, 0))]
        # a group's dk and dv are summed from float32, rounded once
        dkv_shapes = [jax.ShapeDtypeStruct(
            (bh, lk, x.shape[2]), x.dtype if cfg.group == 1 else jnp.float32)
            for x in (k, v)]
        dq_shapes = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    # dk, dv; or in two parts kv's gradient and k_r's a head in float32
    dk, dv, *dq_merged = _call(
        functools.partial(_dkv_kernel, cfg=cfg), cfg,
        "flash_attention_bwd" if merged else "flash_attention_dkv",
        p_.dkv_vmem, carried=(1, 2) if merged else (2,),
        grid=(bh, lk // bk, num_qm),
        in_specs=_in_specs(cfg, q, k, v, qm, bk, q_map, kv_head)
        + [_vspec((1, qm, dv_), by_head(q_map)),
           _vspec((1, 1, qm), row_map),
           _vspec((1, qm, dv_), by_head(q_map)) if _own_delta(cfg)
           else _vspec((1, 1, qm), row_map)],
        out_specs=dkv_specs + whole * merged,
        out_shape=dkv_shapes + dq_shapes * merged,
        scratch_shapes=([pltpu.VMEM((lq, w), jnp.float32) for w in widths]
                        + [pltpu.VMEM((1, 1, lq), jnp.float32)]  # delta
                        * _own_delta(cfg)
                        if merged else _scratch(
                            num_qm, *((bk, w) for w in widths), (bk, dv_))),
    )(*jax.tree.leaves((q, k, v)), do, lse, delta)
    if merged:
        dq = tuple(dq_merged) if heads else dq_merged[0]
    if heads:       # (dq_n, dq_r), (dkv, k_r's gradient summed over heads)
        return tuple(dq), (dk, dv.reshape(-1, heads, lk, widths[1]).sum(
            1).astype(k_r.dtype)), None
    if cfg.group > 1:       # one dk, dv a query head: sum each group's
        dk, dv = (x.reshape(-1, cfg.group, lk, x.shape[2]).sum(1).astype(
            k.dtype) for x in (dk, dv))
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

def _attention(q, k, v, causal, scale, block_q, block_k, interpret,
               vmem_budget=_VMEM_BUDGET, window=None):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    if causal and lq > lk:
        raise ValueError("flash_attention: causal with more queries than keys "
                         "is undefined (use an explicit mask)")
    if window is not None and not (causal and window > 0):
        raise ValueError("flash_attention: window= counts the keys up to a "
                         "row's own, so it needs causal=True and window > 0")
    if h % k.shape[1] or k.shape[:3] != v.shape[:3] or k.shape[3] != d:
        raise ValueError(f"flash_attention: {h} query heads of {d} over "
                         f"key/value shapes {k.shape} / {v.shape}")
    # the plan reckons every operand at the query/key head size, the larger
    # of the two wherever they differ (192 beside values of 128)
    plan = _plan(lq, lk, max(d, v.shape[3]), q.dtype.itemsize, interpret,
                 block_q, block_k, vmem_budget)
    dv = v.shape[3]

    def prep(x, lp):
        width = x.shape[3]
        lanes = plan.dp if width == d else _head_lanes(width)
        x = x.reshape(-1, x.shape[2], width)
        if lp == x.shape[1] and lanes == width:
            return x
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, lanes - width)))

    if window is not None and window >= lk:
        window = None           # every key up to a row's own: plain causal
    cfg = _Cfg(float(scale) if scale is not None else 1.0 / (d ** 0.5),
               bool(causal), lk, lk - lq, bool(interpret), plan,
               window, h // k.shape[1])
    out, _ = _flash(prep(q, plan.lqp), prep(k, plan.lkp), prep(v, plan.lkp),
                    cfg)
    if out.shape[1:] != (lq, dv):
        out = out[:, :lq, :dv]
    return out.reshape(b, h, lq, dv)


def flash_attention(q, k, v, *, causal=False, window=None, scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Tiled attention on (B, H, L, D) tensors; returns (B, H, Lq, D).

    `window=w` (causal only): a row sees the w keys up to its own. K and V
    may hold fewer heads than Q, (B, H / group, Lk, D): query head h reads
    key/value head h // group. V's head size may differ from Q's and K's
    (keys of 192 beside values of 128); the result has V's. A latent
    layer's parts go to `latent_flash_attention` instead.

    Differentiable (custom VJP with blockwise recompute). The block sizes
    come from the shape (`_plan`); `block_q` / `block_k` override them for
    the tests. Padding, where the blocks need any, is handled here; padded
    KV positions are masked inside the kernel, padded Q rows are sliced off
    (their grads vanish since the incoming cotangent there is zero).
    """
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    return _attention(q, k, v, causal, scale, block_q, block_k, interpret,
                      window=window)


def _latent(q_n, q_r, kv, k_r, heads, block_q, block_k, interpret,
            vmem_budget=_VMEM_BUDGET):
    b, lq, _ = q_n.shape
    lk, dr = kv.shape[1], k_r.shape[2]
    dn = q_n.shape[2] // heads
    dv = kv.shape[2] // heads - dn
    if (q_n.shape[2] % heads or kv.shape[2] % heads or dv <= 0
            or q_r.shape != (b, lq, heads * dr) or lq > lk
            or k_r.shape[:2] != kv.shape[:2] or kv.shape[0] != b):
        raise ValueError(
            f"latent_flash_attention: {heads} causal heads over q_n "
            f"{q_n.shape}, q_r {q_r.shape}, kv {kv.shape}, k_r {k_r.shape}")
    # what the kernels hold of a head is what one part of dn + dr would
    # take in lanes (256 at 128 + 64): the one-part plan reckons it
    plan = _plan(lq, lk, max(dn + dr, dv), q_n.dtype.itemsize, interpret,
                 block_q, block_k, vmem_budget)
    cfg = _Cfg((dn + dr) ** -0.5, True, lk, lk - lq, bool(interpret), plan,
               heads=heads)
    # a head of q_r a row: its block is the array's whole last dimension
    q_r = q_r.reshape(b, lq, heads, dr).transpose(0, 2, 1, 3).reshape(
        b * heads, lq, dr)

    def prep(x, lp):        # the sequence padded to the blocks, if need be
        if lp == x.shape[1]:
            return x
        return jnp.pad(x, ((0, 0), (0, lp - x.shape[1]), (0, 0)))

    out, _ = _flash((prep(q_n, plan.lqp), prep(q_r, plan.lqp)),
                    (prep(kv, plan.lkp), prep(k_r, plan.lkp)), None, cfg)
    return out if out.shape[1] == lq else out[:, :lq]


def latent_flash_attention(q_n, q_r, kv, k_r, num_heads, *, block_q=None,
                           block_k=None, interpret=None):
    """Causal attention of a latent layer (MLA) on its parts as the
    products wrote them, by the same kernels: q_n (B, Lq, H dn) and q_r
    (B, Lq, H dr), a head's queries without and with positions; kv (B, Lk,
    H (dn + dv)), head h's key part k_n,h and its values side by side; k_r
    (B, Lk, dr), the part of the keys every head shares. Head h's score is
    (q_n,h k_n,h^T + q_r,h k_r^T) / sqrt(dn + dr); returns (B, Lq, H dv),
    what the output projection reads.

    Nothing is assembled, padded to 128 lanes or transposed on the way in
    but q_r (a head a row, whole); the gradients come back in the operands'
    own layouts (dk_n and dv as one array of kv's), k_r's as the sum over
    the heads of their float32 shares. `block_q`, `block_k` and `interpret`
    as for `flash_attention`."""
    if interpret is None:
        from . import is_tpu
        interpret = not is_tpu()
    return _latent(q_n, q_r, kv, k_r, num_heads, block_q, block_k, interpret)
