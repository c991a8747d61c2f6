"""Kernel-selection layer: ONE place that decides, per call site, whether a
hand-written Pallas kernel (ops/pallas/) replaces the plain XLA
formulation of a hot op.

Every framework path that can hit a Pallas kernel — eager ops, the
`hybridize()` CachedOp trace, and the FusedTrainStep/TrainLoop whole-loop
trace — routes its decision through these predicates, so the
qualification rules (platform, dtype, shape alignment) live in one table
instead of being re-derived inline at each call site, and every decision
is observable:

* counters ``pallas.selected.<kernel>`` / ``pallas.rejected.<kernel>``
  (domain ``ops``) count decisions — once per TRACE (the CachedOp build
  traces a signature twice: the eval_shape structure probe, then the
  first jit dispatch), in eager mode once per call — directional
  indicators, not exact compile counts;
* :class:`capture` collects the decisions made while tracing a
  hybridized block, and `HybridBlock._build_cache` attaches them to the
  compile's flight-recorder record, so "which kernels did my model
  actually get" is answerable from a flight dump.

Escape hatches (checked by ``pallas.enabled()``):

* ``MXTPU_PALLAS=0``  — master off switch: plain XLA everywhere;
* ``MXTPU_PALLAS=force`` / ``MXTPU_FORCE_PALLAS=1`` — select kernels
  off-TPU too (interpret mode; what the CPU parity tests use);
* ``MXTPU_NO_PALLAS=1`` — legacy spelling of the off switch.

Selection table (docs/trainloop.md renders this):

================ =========================================================
kernel           qualifies when
================ =========================================================
flash_attention  pallas enabled; no explicit mask; no attention-weight
                 dropout in training mode (the kernel keeps scores in
                 VMEM and applies no dropout). Causal, a window,
                 grouped key/value heads and values whose heads have a
                 size of their own stay in the kernel, and shape
                 never disqualifies: the kernel derives its blocks from
                 (lq, lk, d), keeps K/V resident while a head fits VMEM
                 and streams them beyond, and leaves a head size of 64
                 unpadded
latent_attention pallas enabled (the op takes no mask); parts of the
                 keys and values whose widths the kernels read where the
                 products wrote them: nope_dim and v_dim multiples of
                 128 (a head is whole 128-lane blocks of (B, L, H x w)),
                 rope_dim a divisor of 128 (q_r a head a row, its whole
                 last dimension). Else the keys are assembled a head at
                 a time and `multihead_attention` runs (its own row
                 decides there)
gated_delta_rule pallas enabled; heads of dk and dv both multiples of
                 128 (a head is a row of whole 128-lane tiles of the (B,
                 L, H, d) arrays, read with no copy); q's dtype bfloat16
                 or float32; else `_delta_group` under `lax.scan`
grouped_matmul   pallas enabled; rows, contraction and columns all
                 multiples of 128 (the Mosaic grouped matmul's tiles);
                 else `jax.lax.ragged_dot`
sum_by_token     pallas enabled; the rows' width a multiple of 128; rows
                 bfloat16 or float32; a column block of the tokens'
                 float32 sums fits VMEM (ops/pallas/token_sum.py `plan`);
                 else XLA's scatter-add
layer_norm       pallas enabled; normalized axis is the LAST axis;
                 1-D gamma; on real TPU the width is 128-lane aligned
scale_shift_act  pallas enabled; channels-last input (the BatchNorm+ReLU
                 epilogue: one HBM pass for normalize+affine+act); on
                 real TPU channel count 128-lane aligned
conv_bn_relu     pallas enabled; inference-style BN (moving stats);
                 NHWC; 1x1/stride-1/no-pad conv runs as one fused
                 matmul+epilogue kernel, any other geometry keeps the
                 XLA conv and fuses only the epilogue
================ =========================================================

Every row also needs a program on ONE device: inside a program that
GSPMD partitions over a mesh (:class:`partitioned`) no kernel qualifies.
"""
from __future__ import annotations

import threading

import numpy as np

from .. import profiler as _prof

__all__ = ["flash_attention", "latent_attention", "gated_delta_rule",
           "grouped_matmul", "sum_by_token", "layer_norm", "scale_shift_act", "conv_bn_relu",
           "capture", "quiet", "partitioned", "meshed", "selection_table"]

_tls = threading.local()


class capture:
    """Collect the selection decisions made on this thread inside the
    scope (used by HybridBlock._build_cache to attach the traced block's
    kernel choices to its compile record). Nestable; each scope sees only
    its own decisions."""

    def __enter__(self):
        self._prev = getattr(_tls, "log", None)
        _tls.log = []
        return _tls.log

    def __exit__(self, *exc):
        _tls.log = self._prev
        return False


class quiet:
    """Suppress the selection counters on this thread inside the scope.
    perfscope's cost capture re-lowers an already-traced program purely
    to read XLA's cost analysis; without this, every analyzed compile
    would double-count pallas.selected.*/rejected.*."""

    def __enter__(self):
        self._prev = getattr(_tls, "quiet", False)
        _tls.quiet = True
        return self

    def __exit__(self, *exc):
        _tls.quiet = self._prev
        return False


class partitioned:
    """Mark what this thread traces inside the scope as ONE program that
    GSPMD partitions over `mesh` (FusedTrainStep(mesh=...), FrozenModel(
    mesh=...)). jax cannot partition a Mosaic kernel by itself — the
    lowering raises "Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map" — so over more than one device
    every kernel is rejected (counted, with this reason) and the XLA
    formulation, which GSPMD can split, is traced instead. `mesh=None`
    and a one-device mesh change nothing."""

    def __init__(self, mesh):
        self._size = int(mesh.size) if mesh is not None else 1

    def __enter__(self):
        self._prev = getattr(_tls, "mesh_size", 1)
        _tls.mesh_size = self._size
        return self

    def __exit__(self, *exc):
        _tls.mesh_size = self._prev
        return False


def meshed() -> bool:
    """Is this thread tracing ONE program that GSPMD partitions over more
    than one device (see :class:`partitioned`)?"""
    return getattr(_tls, "mesh_size", 1) > 1


def _decide(kernel: str, ok: bool, reason: str) -> bool:
    if not getattr(_tls, "quiet", False):
        _prof.counter(
            ("pallas.selected." if ok else "pallas.rejected.") + kernel,
            "ops").increment()
    log = getattr(_tls, "log", None)
    if log is not None:
        log.append({"kernel": kernel, "selected": bool(ok),
                    "reason": reason})
    return ok


def _open(kernel: str) -> bool:
    """May a kernel be selected here at all? The master switch, then the
    program being traced (see :class:`partitioned`)."""
    from . import pallas as _pallas
    if not _pallas.enabled():
        return False
    if meshed():
        return _decide(kernel, False, "multi-device GSPMD program")
    return True


def _on_tpu():
    from . import pallas as _pallas
    return _pallas.is_tpu()


def flash_attention(mask, dropout_active: bool) -> bool:
    """Qualify the pallas flash-attention kernel for a multihead-attention
    call (O(L) memory, scores stay in VMEM). An explicit mask, attention
    dropout and a mesh program leave it; causal, a window and grouped
    key/value heads do not."""
    if not _open("flash_attention"):
        return False
    if mask is not None:
        return _decide("flash_attention", False,
                       "explicit mask (causal and window stay in the kernel)")
    if dropout_active:
        return _decide("flash_attention", False,
                       "attention-weight dropout in training")
    return _decide("flash_attention", True, "ok")


def latent_attention(nope_dim, rope_dim, v_dim) -> bool:
    """Qualify the flash kernels on a latent layer's parts (ops/pallas/
    flash_attention.py `latent_flash_attention`): the queries as (q_n,
    q_r), the keys as the key/value product's (B, L, H x (nope_dim +
    v_dim)) and the one part of `rope_dim` every head shares, read where
    the products wrote them. Decided once a layer; the op takes no mask."""
    if not _open("latent_attention"):
        return False
    if nope_dim % 128 or v_dim % 128:
        return _decide("latent_attention", False,
                       f"nope_dim {nope_dim} or v_dim {v_dim} not % 128")
    if 128 % rope_dim:
        return _decide("latent_attention", False,
                       f"rope_dim {rope_dim} does not divide 128")
    return _decide("latent_attention", True, "ok")


def grouped_matmul(lhs, rhs) -> bool:
    """Qualify the Mosaic grouped matmul (jax's megablox kernels behind
    ops/pallas/grouped_matmul.py) for the sparse experts' products: lhs
    (M, K) in row groups against rhs (G, K, N)."""
    if not _open("grouped_matmul"):
        return False
    m, k, n = lhs.shape[0], lhs.shape[1], rhs.shape[2]
    if m % 128 or k % 128 or n % 128:
        return _decide("grouped_matmul", False,
                       f"({m}, {k}, {n}) not all multiples of 128")
    return _decide("grouped_matmul", True, "ok")


def sum_by_token(rows, tokens) -> bool:
    """Qualify the Pallas sum by token (ops/pallas/token_sum.py: a column
    block of the `tokens` float32 sums resident in VMEM while the live rows
    stream through) for the sparse experts' rows (capacity, D), the
    combine in the forward and the rows' gradient in the backward."""
    if not _open("sum_by_token"):
        return False
    d, dtype = rows.shape[1], np.dtype(rows.dtype).name
    if d % 128:
        return _decide("sum_by_token", False, f"width {d} not % 128")
    if dtype not in ("bfloat16", "float32"):
        return _decide("sum_by_token", False, f"dtype {dtype}")
    from .pallas import token_sum
    if token_sum.plan(tokens, rows.shape[0], d, rows.dtype,
                      rows.dtype) is None:
        return _decide("sum_by_token", False,
                       f"{tokens} x 128 sums do not fit VMEM")
    return _decide("sum_by_token", True, "ok")


def gated_delta_rule(dk, dv, dtype) -> bool:
    """Qualify the Pallas kernels of the gated delta rule's chunked scan
    (ops/pallas/gated_delta_rule.py: a chunk's tiles and the state stay in
    VMEM, forward and backward) for heads of dk (keys) and dv (values) in
    `dtype`: a head is a row of whole 128-lane tiles of the (B, L, H, d)
    arrays."""
    if not _open("gated_delta_rule"):
        return False
    if dk % 128 or dv % 128:
        return _decide("gated_delta_rule", False,
                       f"heads of ({dk}, {dv}) not multiples of 128")
    if np.dtype(dtype).name not in ("bfloat16", "float32"):
        return _decide("gated_delta_rule", False, f"dtype {dtype}")
    return _decide("gated_delta_rule", True, "ok")


def layer_norm(x, gamma, axis) -> bool:
    """Qualify the fused pallas layernorm (one HBM pass, f32 stats)."""
    if not _open("layer_norm"):
        return False
    if axis not in (-1, x.ndim - 1) or gamma.ndim != 1:
        return _decide("layer_norm", False, "non-last-axis")
    if _on_tpu() and x.shape[-1] % 128:
        return _decide("layer_norm", False,
                       f"width {x.shape[-1]} not 128-lane aligned")
    return _decide("layer_norm", True, "ok")


# activations the fused epilogue kernel implements; anything else keeps
# the XLA chain (which supports the full _ACTIVATIONS table)
_EPILOGUE_ACTS = (None, "relu", "relu6")


def scale_shift_act(x, channel_axis, act=None) -> bool:
    """Qualify the fused scale+shift+activation epilogue (the
    BatchNorm[+ReLU] tail as one HBM pass) — channels-last layouts only;
    the per-channel scale/shift broadcast along the last axis maps onto
    lanes."""
    if not _open("scale_shift_act"):
        return False
    if act not in _EPILOGUE_ACTS:
        return _decide("scale_shift_act", False, f"act {act!r}")
    if channel_axis % x.ndim != x.ndim - 1:
        return _decide("scale_shift_act", False, "channels not last")
    if _on_tpu() and x.shape[-1] % 128:
        return _decide("scale_shift_act", False,
                       f"channels {x.shape[-1]} not 128-lane aligned")
    return _decide("scale_shift_act", True, "ok")


def conv_bn_relu(x, weight, stride, pad, dilate, num_group,
                 layout, training: bool, act="relu") -> bool:
    """Qualify the fused conv+BN+relu path (inference hot path: the conv
    epilogue applies the folded BN scale/shift + relu in one pass; 1x1
    convs run entirely as a fused pallas matmul)."""
    if not _open("conv_bn_relu"):
        return False
    if act not in _EPILOGUE_ACTS:
        return _decide("conv_bn_relu", False, f"act {act!r}")
    if training:
        # training-mode BN normalizes with CURRENT batch stats of the conv
        # output — a second pass by construction; the scale_shift_act
        # epilogue covers that case separately
        return _decide("conv_bn_relu", False, "training-mode batch stats")
    if layout != "NHWC":
        return _decide("conv_bn_relu", False, f"layout {layout}")
    if num_group != 1:
        return _decide("conv_bn_relu", False, "grouped conv")
    if dilate is not None and any(d != 1 for d in dilate):
        return _decide("conv_bn_relu", False, "dilated conv")
    if _on_tpu() and (x.shape[-1] % 128 or weight.shape[-1] % 128):
        return _decide("conv_bn_relu", False,
                       "channels not 128-lane aligned")
    return _decide("conv_bn_relu", True, "ok")


def selection_table():
    """The qualification rules as data (docs/tests): kernel -> rule."""
    return {
        "flash_attention": ("no explicit mask, no attention-weight "
                            "dropout; causal, window and grouped heads "
                            "stay"),
        "latent_attention": ("nope_dim and v_dim % 128 == 0; "
                             "128 % rope_dim == 0"),
        "gated_delta_rule": ("heads of dk and dv % 128 == 0; bfloat16 or "
                             "float32"),
        "grouped_matmul": "rows, contraction and columns % 128 == 0",
        "sum_by_token": ("width % 128 == 0; bfloat16 or float32; a column "
                         "block of the sums fits VMEM"),
        "layer_norm": "last-axis, 1-D gamma; TPU: width % 128 == 0",
        "scale_shift_act": "channels-last; TPU: channels % 128 == 0",
        "conv_bn_relu": ("inference BN, NHWC, ungrouped/undilated; "
                         "TPU: in/out channels % 128 == 0; 1x1/s1 fully "
                         "fused, other geometries fuse the epilogue"),
    }
