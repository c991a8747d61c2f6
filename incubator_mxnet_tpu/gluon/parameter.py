"""Parameter & ParameterDict (parity: python/mxnet/gluon/parameter.py).

A Parameter owns an NDArray plus grad bookkeeping. Two extras make the
TPU-native design work:

* trace substitution — while a HybridBlock is being traced under jax.jit,
  `param.data()` returns the traced value injected as a jit argument (so one
  compiled executable serves every step without retracing as weights change);
* aux-state sink — non-learnable state (BatchNorm running stats) updated
  during a traced forward is captured as extra jit outputs and written back
  after the call, keeping the jitted function pure.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from .. import autograd, initializer as _initializer
from .. import profiler as _prof
from ..context import Context, current_context
from ..ndarray import NDArray
from .. import ndarray as nd


class DeferredInitializationError(RuntimeError):
    pass


class _TraceState(threading.local):
    def __init__(self):
        self.active = False
        self.sub = {}          # id(Parameter) -> raw traced array
        self.aux_updates = {}  # id(Parameter) -> raw traced array
        self.params_seen = {}  # id(Parameter) -> Parameter (ordering)


_trace = _TraceState()


class _ParamTraceScope:
    """Context manager installing the substitution map during tracing."""

    def __init__(self, sub):
        self._sub = sub

    def __enter__(self):
        _trace.active = True
        _trace.sub = self._sub
        _trace.aux_updates = {}
        return _trace

    def __exit__(self, *exc):
        _trace.active = False
        _trace.sub = {}
        _trace.params_seen = {}  # drop refs: avoid pinning dead models' aux
        return False


class Parameter:
    """A weight/bias/aux tensor of a Block.

    grad_req: 'write' | 'add' | 'null' ('null' → aux state, no gradient).
    Shapes may contain 0 (unknown) for deferred initialization; they are
    completed from the first forward's input shapes.
    """

    def __init__(self, name, shape=None, dtype="float32", init=None,
                 grad_req="write", lr_mult=1.0, wd_mult=1.0,
                 allow_deferred_init=True, differentiable=True):
        self.name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.init = init
        self.grad_req = grad_req if differentiable else "null"
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.allow_deferred_init = allow_deferred_init
        self._data: NDArray | None = None
        self._deferred = None  # (init, ctx) awaiting shape completion
        self._sharding = None  # parallel/: optional PartitionSpec annotation

    # -- shape ------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new):
        if self._shape is not None:
            assert len(self._shape) == len(new) and all(
                s in (0, n) for s, n in zip(self._shape, new)), (
                f"Inferred shape {new} incompatible with declared {self._shape} "
                f"for parameter {self.name}")
        self._shape = tuple(int(s) for s in new)

    @property
    def shape_is_known(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    # -- init -------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None, force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0] if ctx else None  # single-device storage; mesh sharding
        ctx = ctx or current_context()     # is handled by parallel/, not replicas
        eff = init or self.init or default_init or _initializer.create("uniform")
        if isinstance(eff, str):
            eff = _initializer.create(eff)
        if isinstance(eff, _initializer.Mixed):
            eff = eff.init_for(self.name)
        if not self.shape_is_known:
            if not self.allow_deferred_init:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has unknown shape {self._shape}")
            self._deferred = (eff, ctx)
            return
        self._finish_init(eff, ctx)

    def _finish_init(self, init_obj, ctx):
        from ..ndarray import random as ndrandom
        key = ndrandom._key()
        raw = init_obj(key, self._shape, self.dtype)
        _prof.build.made(raw)
        self._data = NDArray(raw, ctx=ctx)
        if self.grad_req != "null":
            self._data.attach_grad(self.grad_req)
        self._deferred = None

    def finish_deferred_init(self):
        if self._deferred is not None:
            if not self.shape_is_known:
                raise DeferredInitializationError(
                    f"Parameter {self.name}: shape still unknown {self._shape}")
            init_obj, ctx = self._deferred
            with _prof.build.span("mxtpu.setup.init", "init_s",
                                  outermost=True):
                self._finish_init(init_obj, ctx)

    # -- access -----------------------------------------------------------
    def data(self, ctx=None) -> NDArray:
        if _trace.active:
            raw = _trace.sub.get(id(self))
            if raw is not None:
                return NDArray(raw)
        if self._data is None:
            if self._deferred is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} deferred; call net once or set shape")
            raise RuntimeError(
                f"Parameter {self.name} is not initialized; call .initialize()")
        return self._data

    def set_data(self, data):
        if not isinstance(data, NDArray):
            data = nd.array(data)
        if self._data is None:
            self.shape = data.shape
            self._data = data.astype(self.dtype)
            if self.grad_req != "null":
                self._data.attach_grad(self.grad_req)
        else:
            self._data._data = data._data.astype(self._data._data.dtype)

    def grad(self, ctx=None) -> NDArray:
        d = self.data()
        if d._grad is None:
            raise RuntimeError(f"Parameter {self.name} has no gradient "
                               f"(grad_req={self.grad_req})")
        return d._grad

    def zero_grad(self):
        if self._data is not None and self._data._grad is not None:
            self._data._grad._data = nd.zeros(self._data.shape,
                                              dtype=self._data._data.dtype)._data

    def register_grad_hook(self, fn):
        """``fn(self)`` fires the moment this parameter's gradient is
        finalized inside ``autograd.backward`` — i.e. mid-backward, as
        soon as no remaining node can contribute to it. The readiness
        signal for overlapped gradient communication (reference: BytePS /
        ByteScheduler per-tensor ready callbacks in ps-lite's push/pull
        pipeline). ``fn=None`` clears. Requires an initialized parameter
        (call after ``initialize()``/first forward for deferred shapes)."""
        if self._data is None:
            raise RuntimeError(
                f"Parameter {self.name} is not initialized; grad hooks "
                "attach to the parameter's storage")
        self._data._grad_hook = None if fn is None else (lambda _leaf: fn(self))

    def list_ctx(self):
        return [self._data.context] if self._data is not None else []

    def update_aux(self, raw):
        """Write new aux-state value; inside a trace this is captured as an
        extra output instead of mutating (keeps the jitted fn pure)."""
        if _trace.active:
            _trace.aux_updates[id(self)] = raw
            _trace.params_seen[id(self)] = self
        else:
            self._data._data = raw

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            had_grad = self._data._grad is not None
            self._data = self._data.astype(dtype)
            if had_grad:
                self._data.attach_grad(self.grad_req)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-learnable constant parameter (parity: gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        super().__init__(name, shape=value.shape, dtype=str(np.dtype(value._data.dtype))
                         if value._data.dtype != np.dtype("V2") else "bfloat16",
                         init="zeros", grad_req="null")
        self._value = value

    def initialize(self, init=None, ctx=None, default_init=None, force_reinit=False):
        self._data = NDArray(self._value._data, ctx=ctx if not isinstance(ctx, list) else ctx[0])


class ParameterDict:
    """Ordered name→Parameter mapping (parity: gluon.ParameterDict)."""

    def __init__(self, prefix=""):
        self.prefix = prefix
        self._params = OrderedDict()

    def get(self, name, **kwargs) -> Parameter:
        full = self.prefix + name
        if full in self._params:
            return self._params[full]
        p = Parameter(full, **kwargs)
        self._params[full] = p
        return p

    def get_constant(self, name, value=None):
        full = self.prefix + name
        if full not in self._params:
            self._params[full] = Constant(full, value)
        return self._params[full]

    def update(self, other):
        items = other.items() if hasattr(other, "items") else other
        for k, v in items:
            self._params[k] = v

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, k):
        return self._params[k]

    def __contains__(self, k):
        return k in self._params

    def __len__(self):
        return len(self._params)

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        with _prof.build.span("mxtpu.setup.init", "init_s", outermost=True):
            for p in self.values():
                p.initialize(init=None, ctx=ctx, default_init=init,
                             force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.values():
            if p._data is not None:
                p._data = p._data.as_in_context(ctx)
                if p.grad_req != "null":
                    p._data.attach_grad(p.grad_req)

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, fname, strip_prefix=""):
        arrays = {}
        for name, p in self.items():
            if p._data is None:
                continue
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arrays[key] = p._data
        nd.save(fname, arrays)

    def load(self, fname, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arrays = nd.load(fname)
        arrays = {restore_prefix + k: v for k, v in arrays.items()}
        for name, p in self.items():
            if name in arrays:
                p.set_data(arrays[name] if ctx is None else arrays[name].as_in_context(ctx))
            elif not allow_missing:
                raise KeyError(f"Parameter {name} missing from {fname}")
        if not ignore_extra:
            extra = set(arrays) - set(self._params)
            if extra:
                raise KeyError(f"File {fname} has extra parameters {sorted(extra)}")

    def __repr__(self):
        inner = "\n".join(f"  {p}" for p in self.values())
        return f"ParameterDict(\n{inner}\n)"
