"""FrozenModel — ahead-of-time-compiled inference executables.

The serving counterpart of `HybridBlock.hybridize()`: where hybridize
compiles lazily on first call per signature (fine for training, fatal for
tail latency), FrozenModel **freezes** a trained block and precompiles —
at construction time, before traffic arrives — one XLA executable per
batch-size bucket:

* **freeze** — parameters are snapshotted (and optionally `device_put`
  onto an explicit Context) at construction; later training updates to
  the source block do not leak into serving, and no autograd state is
  ever touched (the trace runs with recording off, training=False, so
  BatchNorm uses running stats and dropout is identity);
* **AOT compile** — the forward is traced ONCE (`jax.eval_shape`, no
  device work) to learn the output tree, then `jit.lower(...).compile()`
  builds a concrete executable per bucket — compile cost is paid at
  deploy time, with an explicit warmup execution per bucket so first
  requests never see allocator/runtime lazy-init either;
* **donation** — the padded input batch buffer is donated to the
  executable on backends that support it (TPU/GPU), so steady-state
  serving does not hold two copies of every in-flight batch; params are
  passed (not donated) and live on-device for the model's lifetime.

The reference lineage is `mxnet-model-server`'s frozen
symbol+params checkpoint; `FrozenModel.from_exported` loads exactly that
artifact (`prefix-symbol.json` + `prefix-0000.params`, via SymbolBlock).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import autograd
from .. import perfscope as _ps
from .. import profiler as _prof
from ..diagnostics import flight as _flight
from ..gluon.block import HybridBlock, _flatten_out, _unflatten_out
from ..gluon.parameter import DeferredInitializationError, _ParamTraceScope
from ..ndarray import NDArray
from ..ndarray import random as ndrandom
from ..ops import select as _select
from .errors import InvalidInputError, ReshardingGateError

__all__ = ["FrozenModel", "default_buckets"]


def default_buckets(max_batch: int | None = None):
    """Power-of-two bucket ladder, overridable via MXTPU_SERVING_BUCKETS
    (comma-separated batch sizes)."""
    from ..settings import env_str
    env = env_str("MXTPU_SERVING_BUCKETS")
    if env:
        sizes = sorted({int(s) for s in env.split(",") if s.strip()})
    else:
        sizes, b = [], 1
        cap = int(max_batch or 32)
        while b < cap:
            sizes.append(b)
            b *= 2
        sizes.append(cap)
        sizes = sorted(set(sizes))
    if not sizes or sizes[0] < 1:
        raise ValueError(f"invalid serving buckets {sizes!r}")
    return tuple(sizes)


class FrozenModel:
    """An immutable, serving-ready snapshot of a Gluon block.

    Parameters
    ----------
    block : HybridBlock (SymbolBlock included)
        Trained model; params must be initialized (or initializable from
        `input_shape` via one deferred-shape inference pass).
    input_shape : tuple
        PER-SAMPLE input shape (no batch dimension).
    dtype : str
        Input dtype requests must match.
    batch_buckets : sequence of int, optional
        Batch sizes to precompile; default `default_buckets()`.
    ctx : Context, optional
        Freeze params onto this device (default: wherever they live).
    warmup : bool
        Execute each compiled bucket once at construction (default True).
    donate : bool, optional
        Donate the input buffer to the executable. Default: only on
        backends that support donation (not CPU, where XLA would warn
        and ignore it).
    compute_dtype : str, optional
        Execute the forward in this dtype ("bfloat16"/"bf16") while the
        request/response surface stays `dtype`: params are cast once at
        freeze, the input is cast on entry, floating outputs are cast
        back on exit. None/"float32" leaves the path untouched.
    mesh : Mesh, optional
        Shard the frozen params across this device mesh via the
        resolution layer (`parallel.sharding.resolve_param` — logical
        axis rules, counted replicated fallback) and compile every
        bucket as a GSPMD program over it.
    mesh_mode : str
        Commscope layout-signature mode for the resharding detector
        ("auto" default; "dp"/"mp"/"fsdp" narrow the expected kinds).
    reshard_gate : bool
        With a mesh, refuse to deploy (raise
        :class:`ReshardingGateError`) when any compiled bucket's
        optimized HLO contains resharding collectives — an accidental
        all-gather per request is a p99 catastrophe, caught at freeze
        time. Default True; False serves degraded with the verdict
        still flagged in /healthz + /stats.
    compile_cache : optional
        A `fleet.CompileCache`-shaped object (``load(lowered)`` /
        ``store(lowered, compiled)``): buckets found in the cache are
        deserialized instead of compiled, so replica N+1 of a fleet
        skips the XLA compiles replica 0 already paid for.
    """

    def __init__(self, block, input_shape, dtype="float32",
                 batch_buckets=None, ctx=None, warmup=True, donate=None,
                 compute_dtype=None, mesh=None, mesh_mode="auto",
                 reshard_gate=True, compile_cache=None):
        if not isinstance(block, HybridBlock):
            raise TypeError("FrozenModel requires a HybridBlock (or "
                            f"SymbolBlock), got {type(block).__name__}")
        self._block = block
        self._input_shape = tuple(int(d) for d in input_shape)
        self._dtype = np.dtype(dtype)
        self._ctx = ctx
        self._mesh = mesh
        self._mesh_mode = mesh_mode
        self._compile_cache = compile_cache
        self._compute = None
        if compute_dtype is not None and str(compute_dtype) != "float32":
            if str(compute_dtype) not in ("bfloat16", "bf16"):
                raise ValueError(
                    f"compute_dtype must be 'float32' or 'bfloat16', "
                    f"got {compute_dtype!r}")
            self._compute = jax.numpy.bfloat16
        self.buckets = tuple(sorted(batch_buckets)) if batch_buckets \
            else default_buckets()

        params = self._frozen_params(block)
        self._param_ids = [id(p) for p in params]
        self._param_raws = tuple(p.data()._data if ctx is None
                                 else jax.device_put(p.data()._data,
                                                     ctx.device)
                                 for p in params)
        if self._compute is not None:
            # cast once at freeze: floating params live in the compute
            # dtype for the model's lifetime (integer tables untouched)
            self._param_raws = tuple(
                r.astype(self._compute)
                if jax.numpy.issubdtype(r.dtype, jax.numpy.floating)
                else r for r in self._param_raws)
        self._x_sharding = None
        self._key = jax.random.PRNGKey(0)  # inference: dropout is identity
        if mesh is not None:
            # the resolution layer decides each param's placement
            # (logical axis rules; counted replicated fallback); the
            # request batch and the trace key ride replicated
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.sharding import resolve_param
            self._param_raws = tuple(
                jax.device_put(r, resolve_param(p, mesh))
                for p, r in zip(params, self._param_raws))
            self._x_sharding = NamedSharding(mesh, PartitionSpec())
            self._key = jax.device_put(self._key, self._x_sharding)
        if donate is None:
            donate = jax.default_backend() not in ("cpu",)
        self.donate = bool(donate)

        self._out_tree = None
        raw_fn = self._make_raw_fn()
        self._jit = jax.jit(raw_fn,
                            donate_argnums=(2,) if self.donate else ())
        self._exec = {}
        for b in self.buckets:
            self._compile_bucket(b, warmup)
        _prof.set_gauge("serving.compiled_buckets", len(self._exec),
                        "serving")
        if mesh is not None and reshard_gate:
            self._check_reshard_gate()

    # -- freezing ---------------------------------------------------------
    def _frozen_params(self, block):
        params = list(block.collect_params().values())
        try:
            for p in params:
                p.data()
        except DeferredInitializationError:
            # one shape-inference forward on a zero sample completes
            # deferred init (same move as HybridBlock._call_cached)
            from .. import ndarray as nd_mod
            with autograd.pause(False):
                block(nd_mod.zeros((1,) + self._input_shape,
                                   dtype=self._dtype.name))
            params = list(block.collect_params().values())
            for p in params:
                p.data()
        return params

    # -- tracing / compilation -------------------------------------------
    def _make_raw_fn(self):
        block = self._block
        param_ids = self._param_ids
        compute = self._compute
        out_dtype = self._dtype
        info = {}

        def raw_fn(key_raw, p_raws, x_raw):
            if compute is not None:
                # the compute-dtype boundary: requests stay `dtype` on
                # the wire, the forward runs in bf16, floating outputs
                # come back in `dtype` (int outputs — argmax heads —
                # pass through)
                x_raw = x_raw.astype(compute)
            sub = dict(zip(param_ids, p_raws))
            # recording=False, training=False: pure inference semantics —
            # BN running stats are read, never written; dropout passes
            # through; nothing lands on any autograd tape
            with _ParamTraceScope(sub), autograd._Scope(False, False), \
                    ndrandom._TraceKeyScope(key_raw), \
                    _select.partitioned(self._mesh):
                out = block.forward(NDArray(x_raw))
                leaves, tree = _flatten_out(out)
            info["tree"] = tree
            outs = tuple(x._data for x in leaves)
            if compute is not None:
                outs = tuple(
                    o.astype(out_dtype)
                    if jax.numpy.issubdtype(o.dtype, jax.numpy.floating)
                    else o for o in outs)
            return outs

        self._raw_info = info
        return raw_fn

    def _compile_bucket(self, b, warmup):
        shape = (b,) + self._input_shape
        if self._x_sharding is not None:
            x_spec = jax.ShapeDtypeStruct(shape, self._dtype,
                                          sharding=self._x_sharding)
        else:
            x_spec = jax.ShapeDtypeStruct(shape, self._dtype)
        if _flight._REC is not None:
            _flight.record("compile", f"serving.freeze:b{b}",
                           {"shape": list(shape), "dtype": str(self._dtype)})
        with _prof.Scope(f"serving.compile:b{b}", "serving", sync=False):
            # lower always (it is cheap tracing, and it learns the
            # output tree); the expensive compile consults the shared
            # AOT cache first — a hit deserializes replica 0's
            # executable instead of recompiling it
            lowered = self._jit.lower(self._key, self._param_raws, x_spec)
            compiled = (self._compile_cache.load(lowered)
                        if self._compile_cache is not None else None)
            if compiled is None:
                compiled = lowered.compile()
                if self._compile_cache is not None:
                    self._compile_cache.store(lowered, compiled)
            self._exec[b] = compiled
        if self._out_tree is None:
            self._out_tree = self._raw_info["tree"]
        commscoped = False
        if _ps._PS is not None:
            # the bucket is already lowered — the roofline verdict is a
            # free host-side read here (no extra trace). The compiled
            # executable rides along so commscope's collective
            # extraction reads the optimized HLO without compiling again
            _ps.analyze_lowered(
                lowered, name=self.program_name(b),
                dtype=self._dtype, kind="serving_bucket",
                extra={"bucket": b}, compiled=self._exec[b],
                mesh=self._mesh, mode=self._mesh_mode)
            try:
                from .. import commscope as _cs
                commscoped = _cs._CS is not None
            except Exception:  # noqa: BLE001
                commscoped = False
        if self._mesh is not None and not commscoped:
            # the resharding gate must see a verdict even with the
            # observability stack unarmed: hand the compiled HLO to
            # commscope's extractor directly (total, never raises)
            try:
                from .. import commscope as _cs
                _cs.capture(self.program_name(b), compiled=self._exec[b],
                            mesh=self._mesh, mode=self._mesh_mode,
                            kind="serving_bucket", extra={"bucket": b})
            except Exception:  # noqa: BLE001 — verdicts, not serving
                pass
        _prof.counter("serving.compiles", "serving").increment()
        if warmup:
            x0 = np.zeros(shape, self._dtype)
            outs = self.run_raw(x0)
            jax.block_until_ready(outs)
            _prof.counter("serving.warmup_runs", "serving").increment()

    def _check_reshard_gate(self):
        """Refuse a sharded deploy whose compiled buckets contain
        resharding collectives (commscope's verdict over the optimized
        HLO) — the accidental all-gather is caught at freeze time, not
        in production p99."""
        verdicts = self.comm_verdicts()
        flagged = sorted(b for b, v in verdicts.items()
                         if v.get("resharding_collectives"))
        if flagged:
            detail = {b: verdicts[b]["resharding_collectives"]
                      for b in flagged}
            raise ReshardingGateError(
                f"sharded serve path for {self._block.name!r} contains "
                f"resharding collectives in buckets {detail} — fix the "
                f"param layout (see docs/commscope.md) or pass "
                f"reshard_gate=False to serve degraded")

    # -- execution --------------------------------------------------------
    @property
    def input_shape(self):
        return self._input_shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def program_name(self, b: int) -> str:
        """The perfscope/commscope program-table name of one bucket's
        AOT executable — the ONE join key servescope, /healthz and
        /stats use to attach roofline + resharding verdicts."""
        return f"serving:{self._block.name}:b{b}"

    def comm_verdicts(self) -> dict:
        """Per-bucket commscope resharding verdict for the compiled
        executables: ``{bucket: {resharding_collectives, hlo_available,
        collective_count, collective_bytes}}``. An accidental
        all-gather on the serve path is a per-request p99 catastrophe
        (docs/commscope.md), so the deep /healthz and /stats surface
        this verdict. Empty when commscope never captured the buckets
        (unarmed, or compiled before arming). Never raises."""
        out = {}
        try:
            from .. import commscope as _cs
            progs = {p.get("name"): p for p in _cs.programs()}
        except Exception:  # noqa: BLE001
            return out
        for b in self.buckets:
            rec = progs.get(self.program_name(b))
            if not isinstance(rec, dict):
                continue
            totals = rec.get("totals") or {}
            out[str(b)] = {
                "resharding_collectives":
                    rec.get("resharding_collectives", 0),
                "hlo_available": rec.get("hlo_available", True),
                "collective_count": totals.get("count"),
                "collective_bytes": totals.get("bytes"),
            }
        return out

    def roofline_verdicts(self) -> dict:
        """Per-bucket perfscope roofline verdict for the compiled
        executables (``{bucket: verdict}``); empty when perfscope never
        captured them. Never raises."""
        out = {}
        try:
            from .. import perfscope as _ps_mod
            progs = {p.get("name"): p for p in _ps_mod.programs()}
        except Exception:  # noqa: BLE001
            return out
        for b in self.buckets:
            rec = progs.get(self.program_name(b))
            if isinstance(rec, dict):
                out[str(b)] = rec.get("verdict")
        return out

    def bucket_for(self, n: int) -> int:
        """Smallest compiled bucket that fits n samples."""
        for b in self.buckets:
            if b >= n:
                return b
        raise InvalidInputError(
            f"batch of {n} exceeds the largest compiled bucket "
            f"({self.buckets[-1]}); recompile with larger batch_buckets")

    def validate(self, x: np.ndarray):
        """Shape/dtype admission check for ONE sample (no batch dim)."""
        if tuple(x.shape) != self._input_shape:
            raise InvalidInputError(
                f"sample shape {tuple(x.shape)} != expected "
                f"{self._input_shape}")
        if np.dtype(x.dtype) != self._dtype:
            raise InvalidInputError(
                f"sample dtype {x.dtype} != expected {self._dtype.name}")

    def run_raw(self, x) -> tuple:
        """Execute the bucket exactly matching `x.shape[0]`. Returns the
        flat tuple of raw output arrays (still batched/padded)."""
        n = int(x.shape[0])
        ex = self._exec.get(n)
        if ex is None:
            raise InvalidInputError(
                f"no compiled bucket for batch {n}; buckets={self.buckets}")
        xj = jax.numpy.asarray(x)
        if self._x_sharding is not None:
            xj = jax.device_put(xj, self._x_sharding)
        return ex(self._key, self._param_raws, xj)

    def predict_batch(self, x: np.ndarray, timings: dict | None = None) \
            -> list:
        """Serve a host batch of n <= max_batch samples: pad up to the
        bucket, execute, slice back to n. Returns the per-output list of
        np arrays (length n each). Rows are independent in inference
        graphs, so padding rows never changes real rows' values.

        ``timings``: when a dict is passed (servescope's sampled path)
        it is filled with the per-phase wall split ``{"pad_ms",
        "exec_ms", "unpad_ms"}`` — pad copy, executable wall (transfer
        + device, closed by an explicit ``block_until_ready`` so the
        boundary is real on async backends), and the unpad slice/host
        conversion. With ``timings=None`` the path is unchanged (the
        conversion itself is the sync)."""
        n = int(x.shape[0])
        b = self.bucket_for(n)
        if timings is None:
            if b != n:
                pad = np.zeros((b - n,) + self._input_shape, self._dtype)
                x = np.concatenate([np.ascontiguousarray(x), pad], axis=0)
            outs = self.run_raw(x)
            return [np.asarray(o)[:n] for o in outs]
        t0 = time.perf_counter()
        if b != n:
            pad = np.zeros((b - n,) + self._input_shape, self._dtype)
            x = np.concatenate([np.ascontiguousarray(x), pad], axis=0)
        t1 = time.perf_counter()
        outs = self.run_raw(x)
        jax.block_until_ready(outs)
        t2 = time.perf_counter()
        res = [np.asarray(o)[:n] for o in outs]
        t3 = time.perf_counter()
        timings["pad_ms"] = (t1 - t0) * 1e3
        timings["exec_ms"] = (t2 - t1) * 1e3
        timings["unpad_ms"] = (t3 - t2) * 1e3
        return res

    def __call__(self, x):
        """NDArray-level convenience matching `block(x)`: accepts an
        NDArray or np array WITH batch dim, returns NDArray(s) in the
        block's output structure."""
        x_np = x.asnumpy() if isinstance(x, NDArray) else np.asarray(x)
        outs = self.predict_batch(x_np.astype(self._dtype, copy=False))
        leaves = [NDArray(jax.numpy.asarray(o)) for o in outs]
        return _unflatten_out(self._out_tree, leaves)

    # -- quantization -----------------------------------------------------
    def quantize(self, mode="int8", calib_data=None, calib_mode=None,
                 exclude=(), **freeze_kwargs):
        """A NEW serving-ready FrozenModel in reduced precision; this
        model keeps serving float32 unchanged from its frozen snapshot.

        * ``mode="bf16"`` — same block, ``compute_dtype="bfloat16"``:
          params cast once at freeze, activations computed in bf16,
          floating outputs cast back; the request/response dtype is
          untouched. No calibration needed.
        * ``mode="int8"`` — `contrib.quantization.quantize_net` swaps
          every Dense/Conv2D for its int8 twin (symmetric, per-output-
          channel weight scales; with ``calib_data`` + ``calib_mode``
          the activation scales are baked static first). NOTE: the
          conversion mutates the underlying block in place (the contrib
          contract); this FrozenModel's already-compiled executables
          and its frozen param snapshot are unaffected, but the source
          block object the caller holds is converted.

        ``freeze_kwargs`` override the new freeze (``mesh=``,
        ``compile_cache=``, ``batch_buckets=``, ...); buckets and ctx
        default to this model's.
        """
        kw = {"batch_buckets": self.buckets, "ctx": self._ctx}
        kw.update(freeze_kwargs)
        if mode in ("bf16", "bfloat16"):
            kw.setdefault("compute_dtype", "bfloat16")
            return FrozenModel(self._block, self._input_shape,
                               dtype=self._dtype.name, **kw)
        if mode == "int8":
            from ..contrib.quantization import quantize_net
            qnet = quantize_net(self._block, calib_data=calib_data,
                                exclude=exclude, calib_mode=calib_mode)
            return FrozenModel(qnet, self._input_shape,
                               dtype=self._dtype.name, **kw)
        raise ValueError(
            f"quantize mode must be 'int8' or 'bf16', got {mode!r}")

    # -- checkpoints ------------------------------------------------------
    @staticmethod
    def from_exported(prefix, input_shape, epoch=0, input_name="data",
                      ctx=None, **kwargs):
        """Load a `HybridBlock.export()` checkpoint
        (`prefix-symbol.json` + `prefix-{epoch:04d}.params`) straight
        into a serving-ready FrozenModel — the mxnet-model-server flow."""
        from ..gluon.block import SymbolBlock
        block = SymbolBlock.imports(f"{prefix}-symbol.json", [input_name],
                                    f"{prefix}-{epoch:04d}.params", ctx=ctx)
        return FrozenModel(block, input_shape, ctx=ctx, **kwargs)

    def __repr__(self):
        bits = [f"FrozenModel(input={self._input_shape}",
                f"dtype={self._dtype.name}", f"buckets={self.buckets}",
                f"donate={self.donate}"]
        if self._compute is not None:
            bits.append("compute=bfloat16")
        if self._mesh is not None:
            bits.append(f"mesh={dict(self._mesh.shape)}")
        return ", ".join(bits) + ")"
