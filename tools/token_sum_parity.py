#!/usr/bin/env python3
"""The expert layer's sum by token (`ops/_raw.py` `_sum_by_token`) as XLA's
scatter-add and as the Pallas kernel (ops/pallas/token_sum.py), on one
layer at each expert cell's shapes and load, on whatever device jax has (the
chip, through the chip tool).

    python3 tools/token_sum_parity.py [--cells mellum2 zaya1 kimi mellum2_top]
                                      [--tokens N] [--reps N] [--seed N]

A cell's layer: 8192 tokens of its width, its top-k of its experts with 8
held, routed uniformly at random (the cells' routing is pinned at its
initial balance), so the live rows are near the held experts' even share and
the row buffer is the first rung of `row_capacities`; `mellum2_top` is the
Mellum2 layer with the held experts' scores raised until 3.5 times the even
share is live (28406 rows at seed 0), on the top rung of 65536 rows. The
rows past the live ones are NaN, as the grouped products may leave them.

Prints one JSON line a cell: for the forward (bfloat16 rows times float32
weights) and the backward (bfloat16 rows, no weight) of each form, the
largest error of its float32 sums over the largest entry of a float64 numpy
sum, whether every entry is finite and every token with no live row exactly
0, and the DEVICE time of a call as the step makes it (a bfloat16 result),
by kernel, from a profiler trace of `--reps` calls. Off the chip the kernel
runs interpreted and nothing is timed; `--tokens` shrinks every layer for
such a rehearsal.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"), os.path.join(ROOT, "tools")]

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu.ops import _raw

# (width, top_k, experts, raise of the held experts' scores); 8 held
CELLS = {"mellum2": (2304, 8, 64, 0.0), "zaya1": (2048, 1, 16, 0.0),
         "kimi": (2304, 8, 256, 0.0), "mellum2_top": (2304, 8, 64, 0.35)}
HELD = 8


def layer(tokens, d, top_k, experts, lift, rng):
    """(rows bf16 with NaN past the live ones, picked, weight, live count,
    capacity) of one layer routed as `sparse_experts` routes it."""
    scores = rng.rand(tokens, experts)
    scores[:, :HELD] += lift
    chosen = np.argsort(-scores, axis=1)[:, :top_k].reshape(-1)
    held = chosen < HELD
    order = np.argsort(np.where(held, chosen, HELD), kind="stable")
    live = int(held.sum())
    ladder = _raw.row_capacities(tokens * top_k, HELD, experts)
    capacity = ladder[_raw.row_capacity(live, ladder)]
    rows = rng.randn(capacity, d).astype(np.float32)
    rows[live:] = np.nan
    weight = rng.rand(capacity).astype(np.float32)
    return (jnp.asarray(rows, jnp.bfloat16), order[:capacity].astype(np.int32),
            weight, live, capacity)


def reference(rows, picked, top_k, tokens, live, weight):
    """float64 sums by token of the live rows (times their weight)."""
    rows = np.asarray(rows.astype(jnp.float32), np.float64)[:live]
    if weight is not None:
        rows = rows * weight[:live, None]
    out = np.zeros((tokens, rows.shape[1]))
    np.add.at(out, picked[:live] // top_k, rows)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", default=list(CELLS))
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    for cell in args.cells:
        d, top_k, experts, lift = CELLS[cell]
        rng = np.random.RandomState(args.seed % 2**32)
        rows, picked, weight, live, capacity = layer(
            args.tokens, d, top_k, experts, lift, rng)
        line = {"cell": cell, "tokens": args.tokens, "d": d, "top_k": top_k,
                "capacity": capacity, "live": live,
                "device": device.device_kind, "platform": device.platform}
        owned = set(picked[:live] // top_k)
        empty = np.array([t not in owned for t in range(args.tokens)])
        operands = (rows, jnp.asarray(picked), jnp.int32(live))
        for direction, w in (("fwd", weight), ("bwd", None)):
            want = reference(rows, picked, top_k, args.tokens, live, w)
            extra = () if w is None else (jnp.asarray(w),)
            for form, kernel in (("xla", False), ("kernel", True)):
                def summed(rows, picked, live, *w, dtype=jnp.float32):
                    return _raw._sum_by_token(
                        rows, picked, top_k, args.tokens, live,
                        w[0] if w else None, kernel, dtype).astype(dtype)
                got = np.asarray(jax.jit(summed)(*operands, *extra))
                entry = {
                    "over_ref_max": float(np.max(np.abs(got - want))
                                          / np.max(np.abs(want))),
                    "finite": bool(np.all(np.isfinite(got))),
                    "empty_tokens_zero": bool(np.all(got[empty] == 0.0)),
                    "empty_tokens": int(empty.sum())}
                if on_chip:
                    from attention_parity import device_ms
                    step_form = jax.jit(lambda *a: summed(
                        *a, dtype=jnp.bfloat16))
                    entry["device_ms"] = device_ms(
                        step_form, (*operands, *extra), args.reps,
                        kernels=r"sum_by_token")
                line[f"{direction}_{form}"] = entry
        if on_chip:
            line["fwd_bwd_ms"] = {
                form: line[f"fwd_{form}"]["device_ms"]["total"]
                + line[f"bwd_{form}"]["device_ms"]["total"]
                for form in ("xla", "kernel")}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
