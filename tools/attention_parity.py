#!/usr/bin/env python3
"""The flash-attention kernels against the XLA formulation in float32, at
the benchmark's own shape, on whatever device jax has (the chip, through
the chip tool; the benchmark's `correct` cannot see a wrong gradient that
still descends).

    python3 tools/attention_parity.py [--shape B H L D] [--full]
                                      [--kernel path/to/flash_attention.py]

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


def reference(q, k, v, w, causal):
    """ops/_raw.py's XLA branch (an explicit all-ones mask keeps the call
    off the kernel), float32 operands and float32 products."""
    b, h, lq, d = q.shape

    def merge(x):
        return x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            b, x.shape[2], h * d)

    def loss(q, k, v):
        out = _raw.multihead_attention(
            merge(q), merge(k), merge(v), h, causal=causal,
            mask=jnp.ones((lq, k.shape[2]), bool))
        out = out.reshape(b, lq, h, d).transpose(0, 2, 1, 3)
        return jnp.sum(out * w.astype(jnp.float32)), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out,) + grads


def load(path):
    spec = importlib.util.spec_from_file_location("kernel_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.flash_attention


def device_ms(fn, args, reps):
    """{operation: ms a call} on the device, from a trace of `reps` calls;
    custom-calls by their kernel's name, the rest under "xla"."""
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
            kernel = re.match(r"%(?:\w*?jvp_)?(flash_attention_\w+?)_*[.\d]* =",
                              name) if "custom-call" in name else None
            key = kernel.group(1) if kernel else "xla"
            table[key] = table.get(key, 0.0) + (end - start) / reps / 1e6
    table["total"] = sum(table.values())
    return table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=4, default=[16, 12, 1024, 64])
    ap.add_argument("--full", action="store_true", help="not causal")
    ap.add_argument("--kernel", action="append", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    causal = not args.full
    device = jax.devices()[0]
    interpret = device.platform != "tpu"

    rng = np.random.RandomState(args.seed % 2**32)
    q, k, v, w = (jnp.asarray(rng.randn(*args.shape), jnp.bfloat16)
                  for _ in range(4))
    ref = [np.asarray(x, np.float32) for x in reference(q, k, v, w, causal)]

    from incubator_mxnet_tpu.ops.pallas import flash_attention
    kernels = [("this tree", flash_attention)]
    kernels += [(path, load(path)) for path in args.kernel]
    for name, kernel in kernels:
        def fwd(q, k, v):
            return kernel(q, k, v, causal=causal, interpret=interpret)

        def loss(q, k, v):
            out = fwd(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))
        (_, out), grads = both(q, k, v)
        line = {"kernel": name, "shape": args.shape, "causal": causal,
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
