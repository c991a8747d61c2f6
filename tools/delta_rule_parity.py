#!/usr/bin/env python3
"""The gated delta rule's Pallas kernels against the XLA form
(`ops/_raw.py` `_delta_group` under its scans) on the same inputs, at the
Kimi-Linear cell's shape, on whatever device jax has (the chip, through the
chip tool).

    python3 tools/delta_rule_parity.py [--shape B L H D] [--dtype bfloat16]
                                       [--reps N] [--seed N]

Prints one JSON line: for the output and the five gradients the largest
absolute difference over the XLA form's largest entry (both in `--dtype`;
tests/test_kimi_linear.py holds both to the token-by-token recurrence in
float32), and the DEVICE time of a forward call and of a forward-and-backward
call of each, by kernel, from a profiler trace of `--reps` calls, with the
time a chunk and head in us. Off the chip the kernels run interpreted and
nothing is timed.
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


def device_ms(fn, args, reps):
    """`tools/attention_parity.py`'s reduction, by this op's kernels. A
    `while` of the XLA form is an event that spans its body's, so the XLA
    form's "total" counts those twice."""
    from attention_parity import device_ms as by_kernel
    return by_kernel(fn, args, reps, kernels=r"gated_delta_rule_\w+?")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=4, default=[1, 8192, 32, 128])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-xla-times", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    b, length, h, d = args.shape
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(args.seed % 2**32)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(b, length, h, d)) * d ** -0.5
    k = unit(rng.randn(b, length, h, d))
    v = rng.randn(b, length, h, d)
    # the cell's decays: exp(A_log) in 1..16 times softplus of about dt
    g = -rng.uniform(1, 16, (1, 1, h, 1)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(0.1), (b, length, h, d)))
    beta = 1 / (1 + np.exp(-rng.randn(b, length, h)))
    w = rng.randn(b, length, h, d)
    q, k, v, w = (jnp.asarray(x, dtype) for x in (q, k, v, w))
    g, beta = (jnp.asarray(x, jnp.float32) for x in (g, beta))

    def built(mode):
        os.environ["MXTPU_PALLAS"] = mode

        def fwd(q, k, v, g, beta):
            return _raw.gated_delta_rule(q, k, v, g, beta)[0]

        def loss(q, k, v, g, beta):
            out = fwd(q, k, v, g, beta)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))
        fwd = jax.jit(fwd)
        (_, out), grads = both(q, k, v, g, beta)     # traced under `mode`
        jax.block_until_ready(fwd(q, k, v, g, beta))
        return fwd, both, (out,) + grads

    operands = (q, k, v, g, beta)
    kernel_fwd, kernel_both, got = built("1" if on_chip else "force")
    xla_fwd, xla_both, want = built("0")
    line = {"shape": args.shape, "dtype": args.dtype,
            "device": device.device_kind, "platform": device.platform}
    for what, mine, theirs in zip(("out", "dq", "dk", "dv", "dg", "dbeta"),
                                  got, want):
        mine, theirs = (np.asarray(x, np.float32) for x in (mine, theirs))
        line[what] = {
            "over_xla_max": float(np.max(np.abs(mine - theirs))
                                  / np.max(np.abs(theirs))),
            "finite": bool(np.all(np.isfinite(mine)))}
    if on_chip:
        chunk_heads = b * h * -(-length // 64)
        for name, fwd, both in (("kernels", kernel_fwd, kernel_both),
                                ("xla", xla_fwd, xla_both)):
            if name == "xla" and args.skip_xla_times:
                continue
            f = device_ms(fwd, operands, args.reps)
            fb = device_ms(both, operands, args.reps)
            line[name] = {"fwd_device_ms": f, "fwd_bwd_device_ms": fb}
        mine = line["kernels"]
        fwd_ms = mine["fwd_device_ms"].get("gated_delta_rule_fwd", 0.0)
        bwd_ms = mine["fwd_bwd_device_ms"].get("gated_delta_rule_bwd", 0.0)
        line["us_a_chunk_head"] = {"fwd": fwd_ms * 1e3 / chunk_heads,
                                   "bwd": bwd_ms * 1e3 / chunk_heads}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
