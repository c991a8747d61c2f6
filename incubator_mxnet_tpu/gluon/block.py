"""Block / HybridBlock (parity: python/mxnet/gluon/block.py).

Block = imperative module tree. HybridBlock adds `hybridize()`: the forward
is traced ONCE per (input-signature, training-mode) into a single `jax.jit`
executable — the TPU-native CachedOp. Parameters enter the compiled function
as arguments (no retrace on update); BatchNorm-style aux state comes back as
extra outputs and is written back after the call; dropout keys are threaded
in so compiled randomness differs per step. Under the eager tape, one cached
call records as ONE node whose vjp re-enters XLA — so loss.backward() on a
hybridized net runs forward+backward as compiled XLA computations, matching
the reference's CachedOp forward/backward graph pair.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict

import jax

from .. import autograd
from .. import profiler as _prof
from ..diagnostics import memory as _dmem
from ..diagnostics import flight as _flight
from .. import perfscope as _perfscope
from ..base import NameManager, camel_to_snake
from ..ndarray import NDArray, _apply
from ..ndarray import random as ndrandom
from .parameter import (DeferredInitializationError, Parameter, ParameterDict,
                        _ParamTraceScope, _trace)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]

_NO_SCOPE = contextlib.nullcontext()


class _NameScope:
    """Parity shim for `with self.name_scope():` — naming is automatic here."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class Block:
    def __init__(self, prefix=None, params=None):
        hint = camel_to_snake(type(self).__name__) + "_"
        self._prefix = NameManager.current().get(prefix, hint)
        self._params = ParameterDict(self._prefix)
        if params is not None:
            self._params.update(params.items() if isinstance(params, ParameterDict)
                                else params)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- registration -----------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block
        return block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    # -- properties -------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return _NameScope()

    # -- parameter collection --------------------------------------------
    def collect_params(self, select=None) -> ParameterDict:
        out = ParameterDict(self._prefix)
        out.update({p.name: p for p in self._params.values()})
        out.update({p.name: p for p in self._reg_params.values()})
        for child in self._children.values():
            out.update(child.collect_params().items())
        if select is not None:
            import re
            pat = re.compile(select)
            filtered = ParameterDict(self._prefix)
            filtered.update({k: v for k, v in out.items() if pat.search(k)})
            return filtered
        return out

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        with _prof.build.span("mxtpu.setup.init", "init_s", outermost=True):
            self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        # own params only; the child recursion covers descendants exactly once
        for p in self._params.values():
            p.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)
        for child in self._children.values():
            child.cast(dtype)
        self._dtype = dtype

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- sharding annotations (mxtpu.sharding, docs/sharding.md) ----------
    def shard(self, spec="__unset__", recursive=True, **by_name):
        """Attach GSPMD sharding annotations to this block's parameters.

        `spec` is a `jax.sharding.PartitionSpec` whose entries may be
        mesh axis names (``'dp'``, ``'mp'``) or LOGICAL names
        (``'model'``, ``'batch'``, …) resolved through the active
        `sharding.axis_rules` at build time. It applies to every
        parameter in the subtree whose rank matches ``len(spec)`` —
        `net.shard(P('model', None))` puts all 2-D kernels on the model
        axis and leaves 1-D biases/norms alone. Keyword form targets
        parameters by registered attribute name on each block:
        `dense.shard(weight=P('model', None), bias=P())`.
        `block.shard(None)` CLEARS the subtree's annotations.

        Annotations are layout hints consumed by the sharded executor
        (Trainer/TrainLoop/FusedTrainStep with a mesh); a dim that does
        not divide its mesh axis falls back to replicated. Returns
        ``self`` for chaining."""
        from jax.sharding import PartitionSpec

        matched = set()

        def visit(blk):
            for name, p in blk._reg_params.items():
                if name in by_name:
                    matched.add(name)
                    p._sharding = by_name[name]
                elif spec is None:
                    p._sharding = None
                elif spec != "__unset__" and p._shape is not None \
                        and len(p._shape) == len(tuple(spec)):
                    p._sharding = spec
            if recursive:
                for child in blk._children.values():
                    visit(child)

        if spec != "__unset__" and spec is not None \
                and not isinstance(spec, PartitionSpec):
            raise TypeError(f"spec must be a PartitionSpec or None, "
                            f"got {type(spec).__name__}")
        for v in by_name.values():
            if v is not None and not isinstance(v, PartitionSpec):
                raise TypeError("by-name sharding values must be "
                                "PartitionSpec or None")
        visit(self)
        unmatched = set(by_name) - matched
        if unmatched:
            # a typo'd keyword must not leave the model silently
            # replicated while the user believes it is sharded
            raise ValueError(
                f"shard() keywords {sorted(unmatched)} match no "
                f"registered parameter in this subtree (this block "
                f"registers: {sorted(self._reg_params)})")
        return self

    # -- persistence ------------------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        """Structural names ('features.0.weight'), independent of the
        global auto-name counters (parity: reference block.py
        _collect_params_with_prefix — what makes save/load work across
        processes and across separately-constructed identical nets)."""
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        from ..ndarray import save as nd_save
        params = self._collect_params_with_prefix()
        arrays = {}
        seen = {}
        for name, p in params.items():
            if p._data is None:
                continue
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = name
            arrays[name] = p.data()
        nd_save(filename, arrays)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        from ..ndarray import load as nd_load
        arrays = nd_load(filename)
        params = self._collect_params_with_prefix()
        if arrays and not any(k in params for k in arrays):
            # legacy/name-based file (or symbol checkpoint): fall back to
            # the full-name ParameterDict path
            self.collect_params().load(filename, ctx=ctx,
                                       allow_missing=allow_missing,
                                       ignore_extra=ignore_extra)
            return
        for name, p in params.items():
            if name in arrays:
                v = arrays[name]
                p.set_data(v if ctx is None else v.as_in_context(ctx))
            elif not allow_missing:
                raise KeyError(f"Parameter {name} missing from {filename}")
        if not ignore_extra:
            extra = set(arrays) - set(params)
            if extra:
                raise KeyError(
                    f"File {filename} has extra parameters {sorted(extra)}")

    # -- execution --------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        # while a program is traced (a fused step, a hybridized or frozen
        # forward) the block's name goes into every operation's `op_name`
        # (docs/profiler.md, "Names in a device trace"). Not in eager
        # mode: an eager op is compiled once, under whichever block
        # called it first, so a name there would be another block's.
        with jax.named_scope(self.name) if _trace.active else _NO_SCOPE:
            if _dmem._ACTIVE:
                # attribute arrays created during this forward to this
                # block (innermost scope wins) for memory_summary()'s
                # by-block view
                _dmem.push_block(self.name)
                try:
                    out = self._invoke(*args, **kwargs)
                finally:
                    _dmem.pop_block()
            else:
                out = self._invoke(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def _invoke(self, *args, **kwargs):
        try:
            return self.forward(*args, **kwargs)
        except DeferredInitializationError:
            self._deferred_infer(*args, **kwargs)
            return self.forward(*args, **kwargs)

    def _deferred_infer(self, *args, **kwargs):
        """Complete deferred shapes: per-layer infer_shape if provided."""
        self.infer_shape(*args, **kwargs)
        for p in self.collect_params().values():
            p.finish_deferred_init()

    def infer_shape(self, *args, **kwargs):
        """Layers with deferred params override this; containers recurse by
        just re-running forward (children infer on their own calls)."""
        raise DeferredInitializationError(
            f"{type(self).__name__} has uninitialized parameters and no "
            f"infer_shape; initialize with explicit shapes")

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        lines = [f"{type(self).__name__}("]
        for name, child in self._children.items():
            lines.append(f"  ({name}): {type(child).__name__}")
        lines.append(")")
        return "\n".join(lines)

    def __repr__(self):
        mods = "\n".join(f"  ({k}): {type(v).__name__}" for k, v in self._children.items())
        return f"{type(self).__name__}(\n{mods}\n)" if mods else f"{type(self).__name__}()"


class _CacheEntry:
    """One compiled signature: jitted forward + (lazily) jitted pullback —
    the forward/backward executable pair of the reference's CachedOp."""

    __slots__ = ("raw_fn", "jitted", "_vjp_jit", "n_real", "n_aux",
                 "aux_params", "out_treedef")

    def __init__(self, raw_fn, jitted, n_real, n_aux, aux_params, out_treedef):
        self.raw_fn = raw_fn      # (key, *raws) -> flat outputs, UNJITTED
        self.jitted = jitted      # jax.jit(raw_fn)
        self._vjp_jit = None
        self.n_real = n_real
        self.n_aux = n_aux
        self.aux_params = aux_params
        self.out_treedef = out_treedef

    def vjp_jit(self):
        # jax 0.9 cannot linearize some primitives (reduce_window) through an
        # inner pjit, so the pullback is built from the UNJITTED fn and jitted
        # as a whole: one compiled backward executable per signature.
        if self._vjp_jit is None:
            raw_fn = self.raw_fn

            def vjp_core(key, n_in_args):
                primals, cots = n_in_args
                _, pull = jax.vjp(lambda *p: raw_fn(key, *p), *primals)
                return pull(tuple(cots))

            self._vjp_jit = jax.jit(vjp_core)
        return self._vjp_jit


def _flatten_out(out):
    """Forward outputs → (list of NDArray, treedef). Supports NDArray or
    (possibly nested) tuple/list of NDArrays."""
    leaves = []

    def walk(o):
        if isinstance(o, NDArray):
            leaves.append(o)
            return ("leaf", len(leaves) - 1)
        if isinstance(o, (tuple, list)):
            return ("seq", type(o).__name__, [walk(i) for i in o])
        raise TypeError(f"hybridized forward must return NDArrays, got {type(o)}")

    tree = walk(out)
    return leaves, tree


def _unflatten_out(tree, leaves):
    kind = tree[0]
    if kind == "leaf":
        return leaves[tree[1]]
    _, tname, children = tree
    seq = [_unflatten_out(c, leaves) for c in children]
    return tuple(seq) if tname == "tuple" else seq


class HybridBlock(Block):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cache = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._cache = {}
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child.hybridize(active, **kwargs)

    def _invoke(self, *args, **kwargs):
        if self._active and not _trace.active and not kwargs:
            if all(isinstance(a, NDArray) for a in args):
                return self._call_cached(*args)
        return super()._invoke(*args, **kwargs)

    # -- the TPU CachedOp -------------------------------------------------
    def _call_cached(self, *args):
        params = list(self.collect_params().values())
        try:
            param_nds = [p.data() for p in params]
        except DeferredInitializationError:
            # one shape-inference pass, no aux drift
            with autograd.pause(False), \
                    _prof.build.span("mxtpu.setup.shapes", "shapes_s"):
                super()._invoke(*args)
            params = list(self.collect_params().values())
            param_nds = [p.data() for p in params]

        training = autograd.is_training()
        sig = (tuple((tuple(a.shape), str(a._data.dtype)) for a in args), training)
        entry = self._cache.get(sig)
        if entry is None:
            if _flight._REC is not None:
                _flight.record("compile", "jit.compile:" + self.name,
                               {"signature": repr(sig)})
            if _prof._ACTIVE:
                # jit compile-cache miss: the recorded span covers
                # _build_cache's abstract trace; the jitted forward's own
                # trace, lowering and load come in its first dispatch and
                # land in the `compile/*` counters (profiler/build.py)
                _prof.counter("jit.cache_miss", "gluon").increment()
                with _prof.Scope("jit.compile:" + self.name, "jit",
                                 sync=False):
                    entry = self._build_cache(params, args, training)
            else:
                entry = self._build_cache(params, args, training)
            self._cache[sig] = entry
        elif _prof._ACTIVE:
            _prof.counter("jit.cache_hit", "gluon").increment()

        key_raw = ndrandom._key()
        n_total = entry.n_real + entry.n_aux
        n_in = len(params) + len(args)

        def node_fn(*raws):  # unjitted: stays on the tape for any re-derivation
            flat = entry.raw_fn(key_raw, *raws)
            return flat[0] if n_total == 1 else tuple(flat)

        def fwd_fn(*raws):  # compiled forward executable
            flat = entry.jitted(key_raw, *raws)
            return flat[0] if n_total == 1 else tuple(flat)

        def vjp_fn(*raws_and_cots):  # compiled backward executable
            primals = tuple(raws_and_cots[:n_in])
            cots = tuple(raws_and_cots[n_in:])
            in_cots = entry.vjp_jit()(key_raw, (primals, cots))
            return in_cots[0] if n_in == 1 else tuple(in_cots)

        outs = _apply(node_fn, param_nds + list(args), n_out=n_total,
                      name=self.name + "_cachedop", fn_fwd=fwd_fn, fn_vjp=vjp_fn)
        if n_total == 1:
            outs = (outs,)
        real, aux = outs[:entry.n_real], outs[entry.n_real:]
        for p, new in zip(entry.aux_params, aux):
            p._data._data = new._data  # write back outside the tape
        return _unflatten_out(entry.out_treedef, list(real))

    def _build_cache(self, params, args, training):
        from ..ops import select as _sel
        sub_ids = [id(p) for p in params]
        n_p = len(params)
        out_info = {}

        def raw_fn(key_raw, *raws):
            p_raws, a_raws = raws[:n_p], raws[n_p:]
            sub = dict(zip(sub_ids, p_raws))
            with _ParamTraceScope(sub), autograd._Scope(False, training), \
                    ndrandom._TraceKeyScope(key_raw):
                nd_args = [NDArray(r) for r in a_raws]
                out = self.forward(*nd_args)
                leaves, tree = _flatten_out(out)
                aux_items = [(_trace.params_seen[i], raw)
                             for i, raw in _trace.aux_updates.items()]
            out_info["tree"] = tree
            out_info["aux_params"] = [p for p, _ in aux_items]
            return tuple(x._data for x in leaves) + tuple(raw for _, raw in aux_items)

        jitted = jax.jit(raw_fn)
        # Abstract trace once to learn output structure (no device work).
        # The kernel-selection layer (ops/select) logs which pallas
        # kernels this signature's trace picked; the decisions go to the
        # flight recorder so "which kernels did my model get" is
        # answerable from a crash dump.
        p_raws = [p.data()._data for p in params]
        dummy_key = jax.random.PRNGKey(0)
        with _sel.capture() as kernel_log:
            shapes = jax.eval_shape(raw_fn, dummy_key, *p_raws,
                                    *[a._data for a in args])
        if kernel_log and _flight._REC is not None:
            _flight.record("compile", "pallas.selection:" + self.name,
                           {"decisions": kernel_log[:32]})
        ps = _perfscope._PS
        if ps is not None and ps.capture_jit_cache:
            # roofline verdict for this signature's forward executable
            # (host-side lowering only; one extra trace per compile —
            # the reason jit-cache capture is gated on perfscope being
            # armed rather than always-on). Under a registered mesh the
            # same hook feeds commscope's collective extraction (mode
            # unknown for a bare forward, so its resharding detector
            # stays conservative here — docs/commscope.md)
            shape0 = tuple(args[0].shape) if args else ()
            _perfscope.analyze_jit(
                jitted, (dummy_key, *p_raws, *[a._data for a in args]),
                name=f"jit:{self.name}:{'x'.join(map(str, shape0))}",
                dtype=(args[0]._data.dtype if args else "float32"),
                kind="jit_cache",
                extra={"training": training,
                       "pallas_selections": len(kernel_log or ())})
        n_aux = len(out_info["aux_params"])
        n_real = len(shapes) - n_aux
        return _CacheEntry(raw_fn, jitted, n_real, n_aux,
                           out_info["aux_params"], out_info["tree"])

    def export(self, path, epoch=0):
        """Parity: HybridBlock.export (python/mxnet/gluon/block.py:export) —
        writes `path-symbol.json` + `path-{epoch:04d}.params` (checkpoint
        format, `arg:`/`aux:` prefixes) loadable by SymbolBlock.imports or
        Module. The graph comes from symbol tracing the eager forward
        (gluon/symbolize.py); blocks whose forward uses raw jax closures
        (custom `_apply` fns) cannot be traced and raise
        NotImplementedError — for those, save_parameters still works."""
        from .symbolize import trace_symbol
        from .. import ndarray as nd_mod
        sym, arg_params, aux_params = trace_symbol(self)
        sym.save(f"{path}-symbol.json")
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd_mod.save(f"{path}-{epoch:04d}.params", save_dict)
        return sym, arg_params, aux_params

    def freeze(self, input_shape, dtype="float32", **kwargs):
        """Export→serve handoff without the disk round trip: snapshot
        this block's parameters and AOT-compile per-bucket inference
        executables (see serving.FrozenModel). `input_shape` is the
        PER-SAMPLE shape (no batch dim). The returned FrozenModel is
        immutable — further training of this block does not affect it.
        For the on-disk flow, pair `export()` with
        `serving.FrozenModel.from_exported(prefix, input_shape)`."""
        from ..serving import FrozenModel
        return FrozenModel(self, input_shape, dtype=dtype, **kwargs)

    def forward(self, *args, **kwargs):
        raise NotImplementedError


class SymbolBlock(HybridBlock):
    """Wrap a (bound-able) Symbol graph as a Gluon block (parity:
    python/mxnet/gluon/block.py SymbolBlock) — the serving/fine-tuning
    bridge between the Symbol and Gluon APIs: import a saved symbol +
    checkpoint, then treat it as an ordinary HybridBlock (compose, train,
    hybridize).

    TPU-native: forward evaluates the graph through the same jnp-level
    graph runner the Executor compiles, recorded on the autograd tape as
    one node (`_apply`), so eager backward and the hybridized CachedOp both
    run the graph as fused XLA computations.
    """

    def __init__(self, outputs, inputs, params=None):
        from .. import symbol as sym_mod
        from ..symbol import _topo
        from ..symbol.executor import _graph_runner

        super().__init__(prefix="", params=None)
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        if isinstance(inputs, (str, sym_mod.Symbol)):
            inputs = [inputs]
        input_names = [i.name if isinstance(i, sym_mod.Symbol) else str(i)
                       for i in inputs]
        self._symbol = outputs
        self._input_names = input_names
        arg_names = outputs.list_arguments()
        aux_names = outputs.list_auxiliary_states()
        missing = [n for n in input_names if n not in arg_names]
        if missing:
            raise ValueError(f"inputs {missing} are not arguments of the "
                             f"symbol (arguments: {arg_names})")
        self._arg_names = arg_names
        self._aux_names = aux_names
        param_names = [n for n in arg_names if n not in input_names]

        shared = dict(params.items()) if params is not None else {}
        self._arg_params_list = []
        for n in param_names:
            if n in shared:
                self._params.update([(n, shared[n])])
                self._arg_params_list.append(shared[n])
            else:
                self._arg_params_list.append(
                    self._params.get(n, shape=None, allow_deferred_init=True))
        self._aux_params_list = []
        for n in aux_names:
            if n in shared:
                self._params.update([(n, shared[n])])
                self._aux_params_list.append(shared[n])
            else:
                self._aux_params_list.append(
                    self._params.get(n, shape=None, grad_req="null",
                                     init="zeros", allow_deferred_init=True))

        order = _topo(outputs._entries)
        var_by_name = {n.name: n for n in order if n.is_var}
        self._runner = _graph_runner(outputs._entries,
                                     [var_by_name[n] for n in arg_names],
                                     [var_by_name[n] for n in aux_names])
        self._n_out = len(outputs._entries)
        # positions of inputs vs params within the symbol's argument order
        self._input_pos = [arg_names.index(n) for n in input_names]
        self._param_pos = [arg_names.index(n) for n in param_names]

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load `prefix-symbol.json` (+ optional `prefix-NNNN.params` in the
        checkpoint format, `arg:`/`aux:` prefixes) into a SymbolBlock."""
        from .. import ndarray as nd_mod
        from .. import symbol as sym_mod

        symbol = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        block = SymbolBlock(symbol, input_names)
        if param_file is not None:
            loaded = nd_mod.load(param_file)
            by_name = {}
            for k, v in loaded.items():
                by_name[k.split(":", 1)[1] if ":" in k else k] = v
            for name, p in block._params.items():
                if name in by_name:
                    p.set_data(by_name[name])
                else:
                    raise KeyError(f"Parameter {name} missing from "
                                   f"{param_file}")
        return block

    def _complete_deferred(self, args):
        """Finish deferred param init by running symbol shape inference with
        the observed input shapes."""
        pending = [p for p in self._arg_params_list + self._aux_params_list
                   if p._data is None]
        if not pending:
            return
        shapes = {n: tuple(a.shape)
                  for n, a in zip(self._input_names, args)}
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        for pos, p in zip(self._param_pos, self._arg_params_list):
            if p._data is None and arg_shapes[pos] is not None:
                p.shape = arg_shapes[pos]
        for s, p in zip(aux_shapes, self._aux_params_list):
            if p._data is None and s is not None:
                p.shape = s
        for p in pending:
            if p._deferred is not None:
                p.finish_deferred_init()
            if p._data is None:
                raise DeferredInitializationError(
                    f"Parameter {p.name}: call initialize() before forward")

    def forward(self, *args):
        from ..symbol import _Runtime

        if len(args) != len(self._input_names):
            raise ValueError(f"SymbolBlock expects {len(self._input_names)} "
                             f"inputs {self._input_names}, got {len(args)}")
        self._complete_deferred(args)
        param_nds = [p.data() for p in self._arg_params_list]
        aux_nds = [p.data() for p in self._aux_params_list]
        is_train = autograd.is_training()
        key = ndrandom._key()
        runner = self._runner
        n_in, n_p = len(args), len(param_nds)
        n_out, n_aux = self._n_out, len(aux_nds)
        n_args_total = len(self._arg_names)
        input_pos, param_pos = self._input_pos, self._param_pos

        def f(*raws):
            in_raws = raws[:n_in]
            p_raws = raws[n_in:n_in + n_p]
            aux_raws = raws[n_in + n_p:]
            arg_raws = [None] * n_args_total
            for pos, r in zip(input_pos, in_raws):
                arg_raws[pos] = r
            for pos, r in zip(param_pos, p_raws):
                arg_raws[pos] = r
            rt = _Runtime(is_train, key)
            outs, new_aux = runner(rt, arg_raws, aux_raws)
            flat = tuple(outs) + tuple(new_aux)
            # a 1-tuple under _apply(n_out=1) would stack into a bogus
            # leading axis (bit every no-aux graph, e.g. the causal LM)
            return flat[0] if len(flat) == 1 else flat

        res = _apply(f, list(args) + param_nds + aux_nds,
                     n_out=n_out + n_aux, name="symbolblock")
        if n_out + n_aux == 1:
            res = (res,)
        outs, new_aux = res[:n_out], res[n_out:]
        if is_train:
            for p, new in zip(self._aux_params_list, new_aux):
                p.update_aux(new._data)
        return outs[0] if n_out == 1 else list(outs)
