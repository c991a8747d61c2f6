#!/usr/bin/env python
"""The quickest proof that the Gluon train and serve paths still start on
the chip.

    python chip_smoke.py              one TPU chip: device, eager, kernels,
                                      train-resnet50, train-lm, serve
    python chip_smoke.py --multichip  four chips: the LM step on a dp4 and
                                      a dp2 x mp2 mesh against one device,
                                      and nothing else
    python chip_smoke.py --tiny       the same control flow at toy sizes,
                                      for the CPU rehearsal: it carries on
                                      through every phase on a platform
                                      other than tpu, and always ends
                                      not-ok

One process, the package's public API, random weights from a fixed seed.
Every phase prints one JSON line (compile seconds, persistent-cache hits
and misses, step or request milliseconds ended by a device barrier, peak
device bytes); the first phase that fails ends the run with a non-zero
exit code. The last line of a run that passed on a TPU is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Comparisons between two different XLA programs use the tolerances below,
never ``==``: the compiler owns the order of a reduction.
"""
import argparse
import contextlib
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd, serving
from incubator_mxnet_tpu.models import TransformerLM, get_model
from incubator_mxnet_tpu.models.transformer_lm import lm_loss
from incubator_mxnet_tpu.ops import _raw
from incubator_mxnet_tpu.parallel import FusedTrainStep, make_mesh
from incubator_mxnet_tpu.runtime import cache_guard, native_available

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# max|got - ref| / max|ref| between two programs computing the same thing
TOL_F32 = 1e-4      # float32 programs where matmuls are true float32 (CPU)
TOL_BF16 = 3e-2     # bfloat16 operands: 8 mantissa bits, f32 accumulation.
#                     Also float32 programs on the TPU, whose matmuls and
#                     convolutions take bfloat16 operands by default
TOL_LOSS = 2e-2     # first-step loss, sharded vs one device, bf16 model

# full sizes: the published widths of the two models; --tiny shrinks
# batch, resolution, sequence, vocabulary and depth, never the code path
FULL = {
    "resnet": {"batch": 128, "hw": 224, "classes": 1000},
    "lm": {"batch": 16, "seq": 512, "vocab": 50257, "layers": 12,
           "units": 768},
    "attn": {"b": 16, "h": 12, "l": 512, "d": 64},
    "ssa": [(128, 56, 56, 256), (128, 7, 7, 2048)],
    "cbr": {"x": (8, 56, 56, 256), "cout": 128},
    "delta": {"b": 1, "l": 1024, "h": 4, "d": 128},
    "serve": {"buckets": (1, 2, 4, 8), "waves": (1, 3, 8)},
}
TINY = {
    "resnet": {"batch": 8, "hw": 64, "classes": 10},
    "lm": {"batch": 4, "seq": 128, "vocab": 512, "layers": 2,
           "units": 128},
    "attn": {"b": 2, "h": 2, "l": 128, "d": 64},
    "ssa": [(2, 8, 8, 128)],
    "cbr": {"x": (2, 8, 8, 128), "cout": 128},
    "delta": {"b": 1, "l": 100, "h": 1, "d": 128},
    "serve": {"buckets": (1, 2, 4), "waves": (1, 3)},
}
STEPS = 5
# the rows of ops/select.py's table that `_kernel_cases` goes through
SELECTED_BY_THE_CASES = ("flash_attention", "layer_norm", "scale_shift_act",
                         "conv_bn_relu", "gated_delta_rule")


# ---------------------------------------------------------------------------
# what every phase line carries
# ---------------------------------------------------------------------------

def _compiles():
    """The package's lifetime compile counters (profiler/build.py): backend
    compile requests with their seconds, and the persistent cache's hits
    and writes (a miss is counted when the fresh executable is written)."""
    counted = mx.profiler.counters()
    return tuple(counted.get("compile/" + name, 0) for name in
                 ("requests", "seconds", "cache_hits", "cache_misses"))


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()      # None on XLA:CPU
    return stats.get("peak_bytes_in_use") if stats else None


@contextlib.contextmanager
def phase(name):
    """Run one phase; print its line; a failure ends the process."""
    rec = {"phase": name}
    before = _compiles()
    t0 = time.perf_counter()
    try:
        yield rec
    except BaseException:
        traceback.print_exc()
        rec["ok"] = False
        raise SystemExit(_finish(ok=False, failed=name, rec=rec))
    after = _compiles()
    rec.update(ok=True, seconds=round(time.perf_counter() - t0, 3),
               compiles=after[0] - before[0],
               compile_s=round(after[1] - before[1], 3),
               cache_hits=after[2] - before[2],
               cache_misses=after[3] - before[3],
               peak_bytes=_peak_bytes())
    print(json.dumps(rec), flush=True)


def _finish(ok, failed=None, rec=None):
    """The last line, and the exit code."""
    if rec is not None:
        print(json.dumps(rec), flush=True)
    dev = jax.devices()[0]
    last = {"ok": bool(ok), "device": {"platform": dev.platform,
                                      "kind": dev.device_kind,
                                      "count": len(jax.devices())}}
    if failed:
        last["failed"] = failed
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


def _timed_ms(fn, n):
    """n calls, each ended by a device barrier; milliseconds each."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(round((time.perf_counter() - t0) * 1e3, 3))
    return out


def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != {ref.shape}")
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite values")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))


def _check_close(what, got, ref, tol):
    err = _rel_err(got, ref)
    if err > tol:
        raise AssertionError(f"{what}: relative error {err:.3e} > {tol}")
    return err


@contextlib.contextmanager
def _xla_path():
    """Trace with the Pallas master switch off: the XLA formulation in
    ops/_raw.py, the reference each kernel is compared with."""
    old = os.environ.get("MXTPU_PALLAS")
    os.environ["MXTPU_PALLAS"] = "0"
    try:
        yield
    finally:
        if old is None:
            del os.environ["MXTPU_PALLAS"]
        else:
            os.environ["MXTPU_PALLAS"] = old


def _pallas_counters(since=None):
    """The kernel-selection counters (ops/select.py), or how far they
    moved since an earlier reading."""
    now = {k.split("/", 1)[1]: v for k, v in mx.profiler.counters().items()
           if "/pallas." in k}
    if since is None:
        return now
    return {k: v - since.get(k, 0) for k, v in now.items()
            if v != since.get(k, 0)}


# ---------------------------------------------------------------------------
# phases on one chip
# ---------------------------------------------------------------------------

def phase_device(tiny):
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not tiny:
        # no chip, no result: nothing on stdout
        sys.exit(f"chip_smoke: needs a TPU; jax found platform "
                 f"{dev.platform!r} ({dev.device_kind}). --tiny rehearses "
                 f"the control flow here and still ends not-ok.")
    with phase("device") as rec:
        rec.update(platform=dev.platform, kind=dev.device_kind,
                   count=len(jax.devices()), jax=jax.__version__)
        rec["native_runtime"] = native_available()
        if not rec["native_runtime"]:
            raise RuntimeError("the native runtime did not build (g++?)")
        rec["compile_cache_dir"] = cache_guard.use_compile_cache(HERE)
        rec["cache_canary_ok"] = cache_guard.check()
        if not rec["cache_canary_ok"]:
            raise RuntimeError("the compile-cache canary tripped: an "
                               "executable read back from the cache "
                               "computed wrong values")
    return dev.platform == "tpu"


def phase_eager(ctx, tol):
    """README.md's imperative flow, then hybridize() against eager."""
    with phase("eager") as rec:
        mx.random.seed(SEED)
        rng = np.random.RandomState(SEED)
        x = nd.array(rng.randn(64, 32).astype(np.float32), ctx=ctx)
        y = nd.array(rng.randint(0, 10, 64), ctx=ctx)
        if x.context != ctx:
            raise AssertionError(f"array landed on {x.context}, not {ctx}")
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(10))
        net.initialize(ctx=ctx)
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 0.01})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        losses = []
        for _ in range(STEPS):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(64)
            losses.append(float(loss.mean().asscalar()))
        if not (np.isfinite(losses).all() and losses[-1] < losses[0]):
            raise AssertionError(f"eager loss did not fall: {losses}")
        eager = net(x).asnumpy()
        net.hybridize()
        rec["hybridize_rel_err"] = _check_close(
            "hybridize vs eager", net(x).asnumpy(), eager, tol)
        rec["ms"] = _timed_ms(lambda: net(x).jax(), 3)
        rec["loss"] = [round(v, 5) for v in losses]


def _kernel_cases(cfg):
    """(name, fn(*args), args, differentiate wrt) per kernel, through the
    ops/_raw.py entry the models call — so the selection layer decides,
    exactly as it does inside a model."""
    rng = np.random.RandomState(SEED)

    def rand(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale,
                           dtype)

    a = cfg["attn"]
    heads, width = a["h"], a["h"] * a["d"]
    qkv = [rand((a["b"], a["l"], width), scale=0.5) for _ in range(3)]
    cases = []
    for causal in (False, True):
        cases.append((
            "flash_attention" + ("_causal" if causal else ""),
            lambda q, k, v, causal=causal: _raw.multihead_attention(
                q, k, v, heads, causal=causal),
            qkv, (0, 1, 2)))
    rows = a["b"] * a["l"]
    cases.append((
        "layer_norm", lambda x, g, b: _raw.layer_norm(x, g, b),
        [rand((rows, width)), rand((width,), scale=0.1) + 1.0,
         rand((width,), scale=0.1)], (0, 1, 2)))
    for shape in cfg["ssa"]:
        c = shape[-1]
        stats = [rand((c,), jnp.float32, 0.1) + 1.0,
                 rand((c,), jnp.float32, 0.1),
                 rand((c,), jnp.float32, 0.1),
                 jnp.abs(rand((c,), jnp.float32)) + 0.5]
        cases.append((
            "scale_shift_act_" + "x".join(map(str, shape)),
            lambda x, g, b, m, v: _raw.batch_norm(
                x, g, b, m, v, axis=-1, training=True, act="relu")[0],
            [rand(shape)] + stats, (0, 1, 2)))
    xs, cout = cfg["cbr"]["x"], cfg["cbr"]["cout"]
    cin = xs[-1]
    stats = [rand((cout,), jnp.float32, 0.1) + 1.0,
             rand((cout,), jnp.float32, 0.1),
             rand((cout,), jnp.float32, 0.1),
             jnp.abs(rand((cout,), jnp.float32)) + 0.5]
    for kname, ksz, pad in (("1x1", 1, 0), ("3x3", 3, 1)):
        w = rand((ksz, ksz, cin, cout), scale=(ksz * ksz * cin) ** -0.5)
        cases.append((
            f"conv_bn_relu_{kname}",
            lambda x, w, g, b, m, v, pad=pad: _raw.conv_bn_relu(
                x, w, g, b, m, v, pad=(pad, pad), layout="NHWC",
                training=False),
            [rand(xs), w] + stats, (0, 1)))
    # the gated delta rule's scan (heads of 128, two grid steps of 8 chunks)
    # against `_delta_group`'s XLA form: unit keys, decays of the Kimi
    # mixer's size, a writing strength in (0, 1)
    s = cfg["delta"]
    by_head = (s["b"], s["l"], s["h"], s["d"])

    def unit(x):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
    cases.append((
        "gated_delta_rule",
        lambda q, k, v, g, beta: _raw.gated_delta_rule(q, k, v, g, beta)[0],
        [(unit(rand(by_head)) * s["d"] ** -0.5).astype(jnp.bfloat16),
         unit(rand(by_head)).astype(jnp.bfloat16), rand(by_head),
         -jnp.exp(rand(by_head, jnp.float32) - 3.0),
         jax.nn.sigmoid(rand(by_head[:3], jnp.float32))], (0, 1, 2, 3, 4)))
    return cases


def phase_kernels(cfg, on_tpu):
    """Every Pallas kernel the two models select, forward and backward,
    against the XLA formulation of the same op."""
    with phase("kernels") as rec:
        rec["tolerance"] = TOL_BF16
        rec["kernels"] = {}
        before = _pallas_counters()
        for name, fn, args, wrt in _kernel_cases(cfg):
            def build(args, fn=fn, wrt=wrt):
                # a fresh function each time: jax keys its trace cache on
                # the function, and the switch is read while tracing
                def fwd_bwd(*a):
                    def scalar(*d):
                        full = list(a)
                        for i, v in zip(wrt, d):
                            full[i] = v
                        out = fn(*full)
                        return jnp.sum(out.astype(jnp.float32) ** 2), out
                    (_, out), grads = jax.value_and_grad(
                        scalar, argnums=tuple(range(len(wrt))),
                        has_aux=True)(*[a[i] for i in wrt])
                    return out, grads
                return jax.jit(fwd_bwd).lower(*args).compile()

            # the reference takes the same values in float32, so what is
            # measured is the kernel's rounding and not the reference's
            wide = [a.astype(jnp.float32) for a in args]
            kern = build(args)
            with _xla_path():
                ref = build(wide)
            has_call = "tpu_custom_call" in kern.as_text()
            if on_tpu and not has_call:
                raise AssertionError(f"{name}: no tpu_custom_call in the "
                                     "compiled program — the kernel was "
                                     "not selected")
            if "tpu_custom_call" in ref.as_text():
                raise AssertionError(f"{name}: the XLA reference holds a "
                                     "Pallas kernel")
            (out, grads), (rout, rgrads) = kern(*args), ref(*wide)
            errs = [_check_close(f"{name} forward", out, rout, TOL_BF16)]
            errs += [_check_close(f"{name} grad {i}", g, r, TOL_BF16)
                     for i, (g, r) in enumerate(zip(grads, rgrads))]
            rec["kernels"][name] = {
                "compiled": has_call, "max_rel_err": round(max(errs), 6),
                "ms": _timed_ms(lambda: kern(*args), 2)[-1]}
        rejected = {k: v for k, v in _pallas_counters().items()
                    if k.startswith("pallas.rejected.")}
        if rejected:
            raise AssertionError(f"selection rejected a kernel: {rejected}")
        moved = rec["pallas"] = _pallas_counters(before)
        for kernel in SELECTED_BY_THE_CASES:
            if on_tpu and not moved.get(f"pallas.selected.{kernel}"):
                raise AssertionError(f"{kernel} was not selected: {moved}")


def _train(rec, step, x, y):
    """Warm up (compile + one more step), then STEPS timed steps with no
    compile inside; loss finite and lower than where it started."""
    t0 = time.perf_counter()
    first = float(step(x, y).asscalar())
    rec["first_step_s"] = round(time.perf_counter() - t0, 3)
    step(x, y).wait_to_read()
    before = _compiles()[0]
    losses, ms = [], []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        loss = step(x, y)
        loss.wait_to_read()
        ms.append(round((time.perf_counter() - t0) * 1e3, 3))
        losses.append(float(loss.asscalar()))
    rec["compiles_in_window"] = _compiles()[0] - before
    rec["ms"] = ms
    rec["loss"] = [round(first, 4)] + [round(v, 4) for v in losses]
    if rec["compiles_in_window"]:
        raise AssertionError(f"{rec['compiles_in_window']} compiles inside "
                             "the timed steps")
    if not (np.isfinite(rec["loss"]).all() and losses[-1] < first):
        raise AssertionError(f"loss did not fall: {rec['loss']}")


def _resnet(cfg):
    mx.random.seed(SEED)
    net = get_model("resnet50_v1", classes=cfg["classes"], layout="NHWC")
    net.initialize(init=mx.init.Xavier())
    return net


def phase_train_resnet50(cfg):
    with phase("train-resnet50") as rec:
        c = cfg["resnet"]
        rng = np.random.RandomState(SEED)
        net = _resnet(c)
        net.cast("bfloat16")
        x = nd.array(rng.randn(c["batch"], c["hw"], c["hw"], 3)
                     .astype(np.float32)).astype("bfloat16")
        y = nd.array(rng.randint(0, c["classes"], c["batch"]))
        # a small rate: under BatchNorm the loss does not depend on the
        # scale of a conv weight, so at Xavier's small norms the gradient
        # norm is ~1e4 and the first steps of a larger rate jump about
        opt = mx.optimizer.create("sgd", learning_rate=3e-5, momentum=0.9,
                                  wd=1e-4, multi_precision=True)
        step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt)
        rec.update(batch=c["batch"], image=c["hw"], dtype="bfloat16")
        before = _pallas_counters()
        _train(rec, step, x, y)
        # the zoo's ResNet is BatchNorm then Activation, two layers: it
        # asks the selection layer for no kernel, so nothing moves here
        rec["pallas"] = _pallas_counters(before)


def _lm(c):
    mx.random.seed(SEED)
    net = TransformerLM(c["vocab"], num_layers=c["layers"],
                        units=c["units"], hidden_size=4 * c["units"],
                        num_heads=c["units"] // 64, max_length=c["seq"],
                        dropout=0.0)
    net.initialize(init=mx.init.Normal(0.02))
    net.cast("bfloat16")
    return net


def _lm_step(c, **mesh_kwargs):
    net = _lm(c)
    opt = mx.optimizer.create("adam", learning_rate=1e-3,
                              multi_precision=True)
    step = FusedTrainStep(net, lambda out, y: lm_loss(out, y).mean(), opt,
                          **mesh_kwargs)
    tokens = nd.array(np.random.RandomState(SEED).randint(
        0, c["vocab"], (c["batch"], c["seq"])))
    return net, step, tokens


def phase_train_lm(cfg, on_tpu):
    with phase("train-lm") as rec:
        c = cfg["lm"]
        before = _pallas_counters()
        _, step, tokens = _lm_step(c)
        rec.update(batch=c["batch"], seq=c["seq"], layers=c["layers"],
                   units=c["units"], dtype="bfloat16")
        _train(rec, step, tokens, tokens)
        moved = rec["pallas"] = _pallas_counters(before)
        for kernel in ("flash_attention", "layer_norm"):
            if on_tpu and not moved.get(f"pallas.selected.{kernel}"):
                raise AssertionError(f"{kernel} was not selected: {moved}")
            if moved.get(f"pallas.rejected.{kernel}"):
                raise AssertionError(f"{kernel} was rejected: {moved}")
        text = step.lower(tokens, tokens).compile().as_text()
        rec["tpu_custom_calls"] = text.count("tpu_custom_call")
        if on_tpu and not rec["tpu_custom_calls"]:
            raise AssertionError("no tpu_custom_call in the LM step's HLO")


def _post(url, doc):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def phase_serve(cfg, tol):
    """freeze -> ModelServer on a local port -> concurrent POST /predict in
    waves of different widths -> answers against the hybridized forward."""
    with phase("serve") as rec:
        c, s = cfg["resnet"], cfg["serve"]
        net = _resnet(c)
        shape = (c["hw"], c["hw"], 3)
        n = max(s["waves"])
        samples = np.random.RandomState(SEED).randn(n, *shape).astype(
            np.float32)
        frozen = net.freeze(shape, batch_buckets=s["buckets"])
        net.hybridize()
        want = net(nd.array(samples)).asnumpy()
        # a wide coalescing window: a request body is ~1 MB of JSON and
        # the clients share this process's interpreter lock
        server = serving.ModelServer(frozen, port=0, max_delay_ms=500.0,
                                     default_timeout_ms=60000.0)
        host, port = server.start()
        url = f"http://{host}:{port}"
        try:
            sizes, ms, worst = [], [], 0.0
            for width in s["waves"]:
                replies = [None] * width
                errors = []

                def client(i):
                    try:
                        replies[i] = _post(url + "/predict",
                                           {"data": samples[i].tolist()})
                    except Exception as e:  # noqa: BLE001 — read below
                        errors.append(e)

                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(width)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(180)
                if errors or any(r is None for r in replies):
                    raise AssertionError(f"wave of {width}: {errors}")
                for i, r in enumerate(replies):
                    worst = max(worst, _check_close(
                        f"/predict sample {i}", r["output"], want[i], tol))
                    sizes.append(r["batch_size"])
                    ms.append(r["latency_ms"])
            with urllib.request.urlopen(url + "/stats", timeout=30) as r:
                stats = json.loads(r.read())
        finally:
            server.stop()
        rec.update(buckets=list(s["buckets"]), requests=len(sizes),
                   batch_sizes=sizes, ms=ms, max_rel_err=round(worst, 6),
                   batch_fill=round(stats["batch_fill"], 3),
                   batches=stats.get("serving.batches"))
        if max(sizes) < 2 or stats["batch_fill"] <= 1.0:
            raise AssertionError("no batch ever held more than one request: "
                                 f"sizes {sizes}, /stats fill "
                                 f"{stats['batch_fill']}")


# ---------------------------------------------------------------------------
# --multichip: the sharded LM step against one device
# ---------------------------------------------------------------------------

def _device_bytes(compiled):
    """Per-device bytes of one compiled step: what it holds on entry
    (parameters, optimizer state, batch) and its whole footprint."""
    ma = compiled.memory_analysis()
    return {"arguments": int(ma.argument_size_in_bytes),
            "total": int(ma.argument_size_in_bytes
                         + ma.output_size_in_bytes + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes)}


def _lm_run(rec, c, name, **mesh_kwargs):
    """Train the LM a few steps under one layout; the compiled step's
    per-device bytes ride along."""
    net, step, tokens = _lm_step(c, **mesh_kwargs)
    out = rec[name] = {}
    _train(out, step, tokens, tokens)
    compiled = step.lower(tokens, tokens).compile()
    out["device_bytes"] = _device_bytes(compiled)
    return net, compiled, out


def _lm_sharded(rec, c, name, ref, **mesh_kwargs):
    """The LM under a four-device layout: who holds what, what the
    compiler inserted, and the losses against the one-device run."""
    net, compiled, out = _lm_run(rec, c, name, **mesh_kwargs)
    params = [p.data().jax() for p in net.collect_params().values()]
    holders = set().union(*(p.sharding.device_set for p in params))
    batch_holders = compiled.input_shardings[0][-2].device_set
    out.update(
        param_devices=len(holders), batch_devices=len(batch_holders),
        params_split=sum(not p.sharding.is_fully_replicated for p in params),
        all_reduces=compiled.as_text().count("all-reduce("),
        loss_rel_err=round(max(abs(a - b) / abs(b) for a, b in
                               zip(out["loss"], ref["loss"])), 6))
    if len(holders) != 4 or len(batch_holders) != 4:
        raise AssertionError(f"{name}: shards on {len(holders)} param / "
                             f"{len(batch_holders)} batch devices, not 4")
    if not out["all_reduces"]:
        raise AssertionError(f"{name}: no all-reduce in the compiled step")
    if out["loss_rel_err"] > TOL_LOSS:
        raise AssertionError(f"{name}: losses {out['loss']} vs one device "
                             f"{ref['loss']}")
    return out


def phase_multichip(cfg):
    with phase("multichip-lm") as rec:
        devices = jax.devices()
        if len(devices) < 4:
            raise RuntimeError(f"--multichip needs 4 devices, jax found "
                               f"{len(devices)}")
        # depth cut, every width kept: a second on four chips is charged
        # four times, and mesh, shardings and collectives do not change
        # with depth
        c = dict(cfg["lm"], layers=min(cfg["lm"]["layers"], 4))
        rec.update(batch=c["batch"], seq=c["seq"], layers=c["layers"],
                   units=c["units"])
        _, _, one = _lm_run(rec, c, "one_device")
        dp4 = _lm_sharded(rec, c, "dp4", one,
                          mesh=make_mesh({"dp": 4}, devices[:4]),
                          sharding="dp")
        auto = _lm_sharded(rec, c, "dp2xmp2", one,
                           mesh=make_mesh({"dp": 2, "mp": 2}, devices[:4]),
                           sharding="auto")
        if not auto["params_split"]:
            raise AssertionError("dp2xmp2: sharding='auto' split no "
                                 "parameter over the model axis")
        held, repl = (auto["device_bytes"]["arguments"],
                      dp4["device_bytes"]["arguments"])
        if held >= repl:
            raise AssertionError(
                f"dp2xmp2 holds {held} bytes of parameters and state per "
                f"device, not below the replicated dp4's {repl}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes for the CPU rehearsal; never ok off-TPU")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the sharded LM steps")
    args = ap.parse_args(argv)
    cfg = TINY if args.tiny else FULL
    np.random.seed(SEED)
    on_tpu = phase_device(args.tiny)
    if not on_tpu:
        # the CPU rehearsal still walks the kernels, interpreted
        os.environ["MXTPU_PALLAS"] = "force"
    if args.multichip:
        phase_multichip(cfg)
    else:
        tol = TOL_BF16 if on_tpu else TOL_F32
        phase_eager(mx.tpu(0) if on_tpu else mx.cpu(0), tol)
        phase_kernels(cfg, on_tpu)
        phase_train_resnet50(cfg)
        phase_train_lm(cfg, on_tpu)
        phase_serve(cfg, tol)
    # a pass at toy sizes proves nothing about the chip either
    return _finish(ok=on_tpu and not args.tiny)


if __name__ == "__main__":
    sys.exit(main())
