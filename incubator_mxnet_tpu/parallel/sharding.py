"""mxtpu.sharding — mesh-native GSPMD parallelism through Gluon.

The reference's distributed story is kvstore RPC (ps-lite) or NCCL rings;
PAPER.md §1 maps it onto `jax.sharding.Mesh` + GSPMD instead: annotate
where every tensor LIVES and let XLA insert the collectives. This module
is the annotation/resolution layer that makes that work through Gluon:

* **process-global named mesh** — `set_mesh(make_mesh({'dp': -1,
  'mp': 2}))` registers THE mesh every sharded component resolves
  against (Trainer/TrainLoop/FusedTrainStep pick it up without plumbing
  a mesh argument through user code; `use_mesh` scopes it);
* **logical axis rules** — parameter annotations may name LOGICAL axes
  (``'model'``, ``'batch'``, ``'expert'``, …) that resolve to whatever
  mesh axis the rule table maps them to (``('model', 'mp')``), so the
  same annotated net runs on a ``(dp,)``, ``(dp, mp)`` or ``(dp, tp)``
  mesh without re-annotation — the SNIPPETS.md exemplar's "8-chip v4 to
  6000-chip v5p without changing application code" contract;
* **per-Block annotation** — `Block.shard(spec)` (gluon/block.py)
  attaches specs to Gluon parameters; `auto_shard(net)` applies the
  default rule table (Dense kernels and Embedding tables on the model
  axis, biases/norms replicated, everything else data-parallel);
* **resolution** — `resolve_param(param, mesh)` turns an annotation into
  a concrete `NamedSharding`, mapping logical axes through the active
  rules and falling back to replicated when a dim doesn't divide the
  mesh axis (annotation is a layout hint, never a correctness
  constraint — the fallback is counted, not silent);
* **telemetry** — the `sharding.*` counter family (enforced by
  tools/trace_check.py) publishes mesh shape, per-param spec counts and
  per-device parameter/optimizer-state bytes through the shared
  registry, so every exporter (Prometheus, flight, artifact json) sees the
  layout actually compiled.

The execution side lives in parallel/trainer_step.py (the one-jit
fwd+bwd+optimizer program whose in/out shardings carry these
resolutions) and parallel/fsdp.py (zero-style parameter/optimizer-state
sharding). docs/sharding.md has the axis-rule table and the dp vs fsdp
vs mp decision guide.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import profiler as _prof

__all__ = ["set_mesh", "get_mesh", "clear_mesh", "use_mesh",
           "data_axis", "model_axis", "axis_rules", "current_rules",
           "resolve_axis", "resolve_spec", "resolve_param", "auto_shard",
           "publish_param_stats", "summary", "MODES", "DEFAULT_RULES"]

# Trainer/TrainLoop/FusedTrainStep sharding modes (docs/sharding.md):
#   dp    pure data parallel — params replicated, batch sharded over the
#         data axis, XLA's psum is the gradient all-reduce
#   fsdp  dp + zero-style: unannotated params AND optimizer states live
#         sharded over the data axis, all-gathered in-program
#   auto  dp + the default rule table applied to the net (Dense kernels /
#         Embedding tables on the model axis when the mesh has one)
MODES = ("dp", "fsdp", "auto")

# Mesh-axis name conventions, in detection-priority order. `dp`/`mp` are
# the documented spellings; `tp` is the seed helpers' tensor-parallel
# name and stays recognized so existing annotations keep working.
DATA_AXES = ("dp", "data", "batch")
MODEL_AXES = ("mp", "tp", "model")

# Logical-axis rule table: (logical name, mesh axis), first pair whose
# mesh axis exists in the active mesh wins. Users prepend overrides with
# `axis_rules`. Unmatched logical names resolve to None (replicated dim).
DEFAULT_RULES = (
    ("model", "mp"), ("model", "tp"),
    ("batch", "dp"), ("batch", "data"),
    ("hidden", "mp"), ("hidden", "tp"),
    ("vocab", "mp"), ("vocab", "tp"),
    ("heads", "mp"), ("heads", "tp"),
    ("expert", "ep"),
    ("seq", "sp"),
)

_lock = threading.Lock()
_MESH: Mesh | None = None


class _RulesState(threading.local):
    """The axis-rule overlay is THREAD-LOCAL (like jax's own config
    scopes): two threads' `with axis_rules(...)` blocks can never
    corrupt each other's restore path. None means DEFAULT_RULES."""

    def __init__(self):
        self.rules = None


_rules_state = _RulesState()
# last published layout stats (summary() reads this)
_LAST: dict = {}


# --------------------------------------------------------------------------
# mesh registry
# --------------------------------------------------------------------------

def _publish_mesh_gauges(mesh: Mesh | None) -> None:
    """Keep the layout gauges truthful in BOTH directions: a cleared
    registry must read 0 devices, not the last mesh's shape."""
    if mesh is None:
        for g in ("mesh_devices", "mesh_dp", "mesh_mp"):
            _prof.set_gauge("sharding." + g, 0, "sharding")
        return
    _prof.set_gauge("sharding.mesh_devices", int(mesh.size), "sharding")
    _prof.set_gauge("sharding.mesh_dp",
                    int(mesh.shape.get(data_axis(mesh) or "", 1)),
                    "sharding")
    _prof.set_gauge("sharding.mesh_mp",
                    int(mesh.shape.get(model_axis(mesh) or "", 1)),
                    "sharding")


def set_mesh(mesh: Mesh | None) -> Mesh | None:
    """Register the process-global mesh every sharded component resolves
    against. Returns the mesh. `set_mesh(None)` clears (== clear_mesh)."""
    global _MESH
    with _lock:
        _MESH = mesh
    _publish_mesh_gauges(mesh)
    return mesh


def get_mesh(required: bool = False) -> Mesh | None:
    """The process-global mesh, or None. required=True raises instead."""
    if required and _MESH is None:
        raise RuntimeError(
            "no global mesh registered; call "
            "sharding.set_mesh(make_mesh({'dp': -1})) first")
    return _MESH


def clear_mesh() -> None:
    global _MESH
    with _lock:
        _MESH = None
    _publish_mesh_gauges(None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Scope the global mesh: `with sharding.use_mesh(mesh): ...`."""
    prev = _MESH
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def _first_present(names, mesh: Mesh | None) -> str | None:
    if mesh is None:
        return None
    for n in names:
        if n in mesh.shape:
            return n
    return None


def data_axis(mesh: Mesh | None = None) -> str | None:
    """The mesh's data-parallel axis name ('dp'/'data'/'batch'), or None."""
    return _first_present(DATA_AXES, mesh if mesh is not None else _MESH)


def model_axis(mesh: Mesh | None = None) -> str | None:
    """The mesh's model-parallel axis name ('mp'/'tp'/'model'), or None."""
    return _first_present(MODEL_AXES, mesh if mesh is not None else _MESH)


# --------------------------------------------------------------------------
# logical axis rules
# --------------------------------------------------------------------------

@contextlib.contextmanager
def axis_rules(*pairs):
    """Prepend logical-axis rules for the scope:

        with sharding.axis_rules(("hidden", "mp"), ("vocab", None)):
            net.shard(P("hidden", None))

    Each pair is (logical_name, mesh_axis_or_None); user pairs take
    priority over DEFAULT_RULES. Mapping a logical name to None pins it
    replicated even if a default rule would shard it. The overlay is
    thread-local — resolve on the thread that entered the scope."""
    for p in pairs:
        if (not isinstance(p, (tuple, list)) or len(p) != 2
                or not isinstance(p[0], str)):
            raise ValueError(
                f"axis_rules pairs must be (logical, mesh_axis) 2-tuples, "
                f"got {p!r}")
    prev = _rules_state.rules
    _rules_state.rules = tuple(tuple(p) for p in pairs) + current_rules()
    try:
        yield
    finally:
        _rules_state.rules = prev


def current_rules() -> tuple:
    return _rules_state.rules if _rules_state.rules is not None \
        else DEFAULT_RULES


def resolve_axis(name, mesh: Mesh | None = None):
    """One spec entry → mesh axis (or None → replicated dim). Mesh axis
    names pass through; logical names map through the active rules; a
    name matching neither replicates (never errors — portability)."""
    mesh = mesh if mesh is not None else _MESH
    if name is None or mesh is None:
        return None
    if name in mesh.shape:
        return name
    for logical, ax in current_rules():
        if logical == name:
            if ax is None:
                return None
            if ax in mesh.shape:
                return ax
    return None


def resolve_spec(spec, mesh: Mesh | None = None) -> P:
    """PartitionSpec with logical names → PartitionSpec of mesh axes."""
    mesh = mesh if mesh is not None else _MESH
    if spec is None:
        return P()
    out = []
    for entry in spec:
        if isinstance(entry, (tuple, list)):
            axes = [resolve_axis(a, mesh) for a in entry]
            axes = [a for a in axes if a is not None]
            out.append(tuple(axes) if len(axes) > 1
                       else (axes[0] if axes else None))
        else:
            out.append(resolve_axis(entry, mesh))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _divides(shape, spec: P, mesh: Mesh) -> bool:
    for d, ax in enumerate(spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if d >= len(shape) or shape[d] % size:
            return False
    return True


def _spec_names(spec):
    """The axis names a raw annotation mentions (flattened, None-free)."""
    if spec is None:
        return []
    return [a for e in spec if e is not None
            for a in (e if isinstance(e, (tuple, list)) else (e,))]


def replicate_pinned(spec, mesh: Mesh | None = None) -> bool:
    """True when an annotation EXPLICITLY asks for replication under the
    active rules: `P()` / all-None entries, or a named entry the rules
    map to None before any mesh-resolvable mapping (an axis_rules pin).
    An annotation whose names merely don't exist on this mesh (e.g.
    P('model', None) on a dp-only mesh) is NOT a pin — it dissolved,
    and callers with a default (FSDP) may still apply it."""
    if spec is None:
        return False
    names = _spec_names(spec)
    if not names:
        return True                      # P() / P(None, ...)
    mesh = mesh if mesh is not None else _MESH
    for name in names:
        if mesh is not None and name in mesh.shape:
            return False
        for logical, ax in current_rules():
            if logical == name:
                if ax is None:
                    return True          # explicit (name, None) pin
                if mesh is not None and ax in mesh.shape:
                    return False
    return False


def resolve_param(param, mesh: Mesh | None = None,
                  default_spec=None) -> NamedSharding:
    """A Parameter's annotation → concrete NamedSharding on `mesh`.

    Logical axes map through the active rules; a spec that dissolves
    (names missing from this mesh) or whose sharded dims don't divide
    the mesh axes falls back to replicated — counted in
    `sharding.fallback_replicated`, never silent. `default_spec`
    applies when the param carries no annotation (the FSDP path passes
    its dp-leading spec here)."""
    mesh = mesh if mesh is not None else get_mesh(required=True)
    _prof.counter("sharding.resolves", "sharding").increment()
    raw = param._sharding if param._sharding is not None else default_spec
    spec = resolve_spec(raw, mesh)
    if spec == P():
        if _spec_names(raw) and not replicate_pinned(raw, mesh):
            # a real annotation dissolved on this mesh — the counted
            # fallback, same as the non-dividing case below
            _prof.counter("sharding.fallback_replicated",
                          "sharding").increment()
        return NamedSharding(mesh, P())
    shape = param.shape
    if shape is None or not _divides(shape, spec, mesh):
        _prof.counter("sharding.fallback_replicated", "sharding").increment()
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, spec)


# --------------------------------------------------------------------------
# per-Block defaults (the axis-rule table's "auto" column)
# --------------------------------------------------------------------------

# Block classes whose 2-D `weight` defaults onto the model axis: Dense
# kernels are (units, in_units) — sharding dim 0 is Megatron
# column-parallel; Embedding tables are (vocab, dim) — sharding dim 0
# splits the vocab. Biases/norm scales are 1-D and stay replicated, as
# do conv kernels (spatial dims rarely divide, and dp is the win there).
_AUTO_MODEL_BLOCKS = ("Dense", "Embedding")


def auto_shard(net, mesh: Mesh | None = None, overwrite: bool = False):
    """Apply the default rule table to a Gluon block tree: every Dense /
    Embedding `weight` gets the logical P('model', None) annotation
    (resolved to the mesh's mp/tp axis at build, replicated if the mesh
    has none). Existing annotations are kept unless overwrite=True.
    Returns `net` for chaining.

    This WRITES annotations (visible, clearable with net.shard(None)) —
    the explicit form. The executor's sharding='auto' mode instead uses
    :func:`auto_specs`, which leaves the net untouched so a later
    sharding='dp' build of the same net is not silently model-sharded."""
    def visit(blk):
        if type(blk).__name__ in _AUTO_MODEL_BLOCKS:
            w = getattr(blk, "weight", None)
            if w is not None and (overwrite or w._sharding is None):
                w._sharding = P("model", None)
        for child in getattr(blk, "_children", {}).values():
            visit(child)
    visit(net)
    return net


def auto_specs(net) -> dict:
    """Non-mutating auto_shard: the default-rule annotations as an
    ephemeral {id(Parameter): PartitionSpec} map for unannotated Dense /
    Embedding weights, consumed as resolve_param's default_spec by the
    executor's 'auto' mode. User annotations always win (absent here)."""
    out = {}

    def visit(blk):
        if type(blk).__name__ in _AUTO_MODEL_BLOCKS:
            w = getattr(blk, "weight", None)
            if w is not None and w._sharding is None:
                out[id(w)] = P("model", None)
        for child in getattr(blk, "_children", {}).values():
            visit(child)
    visit(net)
    return out


# --------------------------------------------------------------------------
# telemetry
# --------------------------------------------------------------------------

def _bytes_on_device(raws, device) -> int:
    """Physical bytes the given device holds for these arrays — the
    per-device cost a sharded layout actually pays (a replicated array
    costs its full size; an FSDP shard 1/dp of it). Delegates to the
    diagnostics ledger's shard walker so the gauge and the reconcile
    census can never disagree. Shardless host buffers (key None) count
    toward the queried device."""
    from ..diagnostics.memory import shard_bytes_by_device
    by_dev = shard_bytes_by_device(raws)
    return by_dev.get(device, 0) + by_dev.get(None, 0)


def publish_param_stats(params, states=None, mesh: Mesh | None = None,
                        mode: str | None = None) -> dict:
    """Count the resolved layout and publish the sharding.* gauges.

    Called by FusedTrainStep after its first dispatch (params are live,
    concrete jax.Arrays then). Returns — and caches for `summary()` —
    the dict of `extra.sharding.params` (tools/trace_check.py)."""
    mesh = mesh if mesh is not None else _MESH
    d_ax, m_ax = data_axis(mesh), model_axis(mesh)
    n_model = n_data = n_repl = 0
    raws = []
    for p in params:
        raw = p.data()._data
        raws.append(raw)
        spec = getattr(getattr(raw, "sharding", None), "spec", None)
        flat = [a for e in (spec or ()) if e is not None
                for a in (e if isinstance(e, tuple) else (e,))]
        if m_ax is not None and m_ax in flat:
            n_model += 1
        elif d_ax is not None and d_ax in flat:
            n_data += 1
        else:
            n_repl += 1
    stats = {
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "mode": mode,
        "fsdp": mode == "fsdp",
        "params_total": len(list(params)),
        "params_model_sharded": n_model,
        "params_data_sharded": n_data,
        "params_replicated": n_repl,
    }
    _prof.set_gauge("sharding.params_total", stats["params_total"],
                    "sharding")
    _prof.set_gauge("sharding.params_model_sharded", n_model, "sharding")
    _prof.set_gauge("sharding.params_data_sharded", n_data, "sharding")
    _prof.set_gauge("sharding.params_replicated", n_repl, "sharding")
    _prof.set_gauge("sharding.fsdp", int(mode == "fsdp"), "sharding")
    if mesh is not None:
        dev0 = np.ravel(np.asarray(mesh.devices, dtype=object))[0]
        pb = _bytes_on_device(raws, dev0)
        stats["param_bytes_per_device"] = pb
        _prof.set_gauge("sharding.param_bytes_per_device", pb, "sharding")
        if states is not None:
            import jax
            sb = _bytes_on_device(
                [leaf for leaf in jax.tree_util.tree_leaves(states)], dev0)
            stats["state_bytes_per_device"] = sb
            _prof.set_gauge("sharding.state_bytes_per_device", sb,
                            "sharding")
    _LAST.clear()
    _LAST.update(stats)
    return stats


def summary() -> dict:
    """The last published layout (mesh shape, mode, spec counts,
    per-device bytes), in the `extra.sharding` shape."""
    return dict(_LAST)
