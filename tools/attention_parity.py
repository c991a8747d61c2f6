#!/usr/bin/env python3
"""The flash-attention kernels against the XLA formulation in float32, at
the benchmark's own shape, on whatever device jax has (the chip, through
the chip tool; the benchmark's `correct` cannot see a wrong gradient that
still descends).

    python3 tools/attention_parity.py [--shape B H L D] [--full]
                                      [--kv-heads N] [--window W] [--block N]
                                      [--kernel path/to/flash_attention.py]

`--kv-heads` gives K and V fewer heads than Q (grouped heads), `--window`
the keys a row sees up to its own; the Mellum2 cell's layers are `--shape 1
32 8192 128 --kv-heads 4` with `--window 1024` and without. `--block`
overrides the blocks the kernels derive from the shape (`block_q` =
`block_k` = N), to time a tiling the plan does not choose.

Prints one JSON line a kernel file (this tree's first): for the output and
the three gradients the largest absolute error and that error over the
reference's largest entry, and the DEVICE time of a forward call and of a
forward-and-backward call, by kernel, from a profiler trace of `--reps`
calls (a host clock around one call reads ~1 ms of dispatch on top: PR 26
mistook it for kernel time). Off the chip nothing is traced and the times
are left out. `--kernel` adds another file's `flash_attention`, a parent's
say, held to the same reference on the same inputs.
"""
import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu.ops import _raw
from lib import xplane


def reference(q, k, v, w, causal, window=None):
    """ops/_raw.py's XLA branch (an explicit all-ones mask keeps the call
    off the kernel), float32 operands and float32 products; one key/value
    head with its query heads at a time, so that the (L, L) scores of a
    long sequence fit."""
    b, h, lq, d = q.shape
    group = h // k.shape[1]

    def merge(x):
        return x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            b, x.shape[2], -1)

    def loss(q, k, v, w):
        out = _raw.multihead_attention(
            merge(q), merge(k), merge(v), group, causal=causal,
            num_kv_heads=1, window=window,
            mask=jnp.ones((lq, k.shape[2]), bool))
        out = out.reshape(b, lq, group, d).transpose(0, 2, 1, 3)
        return jnp.sum(out * w.astype(jnp.float32)), out

    one = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
    parts = []
    with jax.default_matmul_precision("highest"):
        for j in range(k.shape[1]):
            heads = slice(j * group, (j + 1) * group)
            (_, out), grads = one(q[:, heads], k[:, j:j + 1], v[:, j:j + 1],
                                  w[:, heads])
            parts.append([np.asarray(x, np.float32) for x in (out,) + grads])
    return [np.concatenate(xs, axis=1) for xs in zip(*parts)]


def load(path):
    spec = importlib.util.spec_from_file_location("kernel_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.flash_attention


def device_ms(fn, args, reps, kernels=r"flash_attention_\w+?"):
    """{operation: ms a call} on the device, from a trace of `reps` calls;
    custom-calls by their kernel's name (a pattern of names, `kernels`),
    the rest under "xla"."""
    jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        planes = xplane.load(xplane.newest(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    table = {}
    for plane, lines in planes.items():
        if not plane.startswith(xplane.DEVICE_PLANE):
            continue
        for name, start, end in lines.get(xplane.OPS_LINE, []):
            kernel = re.match(r"%(?:\w*?jvp_)?(" + kernels + r")_*[.\d]* =",
                              name) if "custom-call" in name else None
            key = kernel.group(1) if kernel else "xla"
            table[key] = table.get(key, 0.0) + (end - start) / reps / 1e6
    table["total"] = sum(table.values())
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=4, default=[16, 12, 1024, 64])
    ap.add_argument("--full", action="store_true", help="not causal")
    ap.add_argument("--kv-heads", type=int, help="key/value heads (grouped)")
    ap.add_argument("--window", type=int, help="keys a row sees (causal)")
    ap.add_argument("--block", type=int, help="block_q = block_k override")
    ap.add_argument("--kernel", action="append", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    causal = not args.full
    device = jax.devices()[0]
    interpret = device.platform != "tpu"

    rng = np.random.RandomState(args.seed % 2**32)
    b, h, length, d = args.shape
    kv_shape = (b, args.kv_heads or h, length, d)
    q, w = (jnp.asarray(rng.randn(*args.shape), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(*kv_shape), jnp.bfloat16) for _ in range(2))
    ref = reference(q, k, v, w, causal, args.window)
    extra = {} if args.window is None else {"window": args.window}
    if args.block:
        extra.update(block_q=args.block, block_k=args.block)

    from incubator_mxnet_tpu.ops.pallas import flash_attention
    kernels = [("this tree", flash_attention)]
    kernels += [(path, load(path)) for path in args.kernel]
    for name, kernel in kernels:
        def fwd(q, k, v):
            return kernel(q, k, v, causal=causal, interpret=interpret,
                          **extra)

        def loss(q, k, v):
            out = fwd(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))
        (_, out), grads = both(q, k, v)
        line = {"kernel": name, "shape": args.shape, "causal": causal,
                "kv_heads": kv_shape[1], "window": args.window,
                "block": args.block,
                "device": device.device_kind, "platform": device.platform}
        for what, got, want in zip(("out", "dq", "dk", "dv"),
                                   (out,) + grads, ref):
            err = float(np.max(np.abs(np.asarray(got, np.float32) - want)))
            line[what] = {"max_abs": err,
                          "over_ref_max": err / float(np.max(np.abs(want)))}
        if not interpret:
            line["fwd_device_ms"] = device_ms(jax.jit(fwd), (q, k, v),
                                              args.reps)
            line["fwd_bwd_device_ms"] = device_ms(both, (q, k, v), args.reps)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
