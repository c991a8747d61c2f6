"""mxtpu.autotune.cache — persisted tuning winners with provenance.

One JSON file per key under ``MXTPU_AUTOTUNE_CACHE`` (default
``~/.cache/mxtpu/autotune``), keyed by **(model fingerprint, mesh
shape, device kind)** — the three things that change what the right
knobs are. Every entry carries the FULL measurement provenance (winner
score + the default config's measurement + the trial table), so a
cached decision is always auditable: ``mxdiag.py tune`` renders a
cache-hit run's winner-vs-default delta from the entry alone.

Trust rules (pinned by tests):

* a corrupt file (unreadable JSON, wrong shape) is REJECTED and counted
  (``autotune.cache_rejects``), never raised through;
* a schema bump rejects old entries — a future format change re-tunes
  rather than guessing at field meanings;
* the entry's OWN recorded key fields must match the lookup (device
  kind above all: a winner tuned on CPU must never configure a TPU run
  — same mesh, same fingerprint rules);
* writes are atomic (tmp + rename): a torn write is never a valid
  entry.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time

from .knobs import KnobConfig

__all__ = ["TuningCache", "SCHEMA", "fingerprint",
           "current_device_kind", "normalize_device_kind"]

SCHEMA = "mxtpu.autotune-cache/1"


def fingerprint(model=None, tag=None, batch=None, dtype=None) -> str:
    """Model fingerprint for the cache key. ``model`` (a Gluon Block):
    structural sha over sorted (param name, shape, dtype) — two nets
    with the same architecture tune identically; ``tag``: a caller
    label (the bench model tag) used as-is. Batch and dtype fold in —
    geometry changes the right knobs."""
    if model is not None and hasattr(model, "collect_params"):
        h = hashlib.sha256()
        params = model.collect_params()
        # creation-order (index, shape, dtype), NOT param names: gluon
        # auto-names count globally (dense0, dense1, ...), so two
        # identical nets built in one process would otherwise never
        # share a cache key
        for i, name in enumerate(params.keys()):
            p = params[name]
            h.update(f"{i}:{getattr(p, 'shape', None)}:"
                     f"{getattr(p, 'dtype', None)};".encode())
        tag = f"{tag or type(model).__name__}-{h.hexdigest()[:12]}"
    parts = [str(tag or "model")]
    if batch:
        parts.append(f"b{int(batch)}")
    if dtype:
        parts.append(str(dtype))
    return "|".join(parts)


def normalize_device_kind(kind) -> str:
    """Canonical device-kind spelling for cache keys: lowercased,
    stripped. jax reports 'TPU v4' raw while perfscope's peaks table
    records 'tpu v4' — every key producer (the tuner, bench, the
    sweep's artifact-derived ingestion) must land on ONE spelling or
    sweep-stored winners are never found by the driver's lookup."""
    return str(kind or "unknown").strip().lower() or "unknown"


def current_device_kind() -> str:
    """The attached device's kind string (the cache-key leg that keeps a
    CPU-tuned winner off a TPU run), normalized. A process that has not
    opened a backend asks a CHILD: the search's trials are children that
    need the chip, and a parent that had looked for itself would hold it
    (context.py, "one process per chip")."""
    from .. import context
    if context.backend_opened():
        import jax
        return normalize_device_kind(jax.devices()[0].device_kind)
    return normalize_device_kind(context.devices_seen_by_a_child()[1])


def _count_reject():
    try:
        from ..profiler import counter as _counter
        _counter("autotune.cache_rejects", "autotune").increment()
    except Exception:  # noqa: BLE001
        pass


class TuningCache:
    """File-backed winner store. All methods are best-effort: IO errors
    degrade to a miss (the tuner re-searches), never to a crash."""

    def __init__(self, root=None):
        from .knobs import env_str
        self.root = (root
                     or env_str("MXTPU_AUTOTUNE_CACHE")
                     or os.path.join(os.path.expanduser("~"), ".cache",
                                     "mxtpu", "autotune"))
        self.rejects = 0          # this instance's rejected-entry count

    # -- keying -----------------------------------------------------------
    @staticmethod
    def _norm_mesh(mesh):
        return str(mesh).strip() if mesh else None

    def path_for(self, fp: str, mesh, device_kind: str) -> str:
        key = (f"{fp}|{self._norm_mesh(mesh)}|"
               f"{normalize_device_kind(device_kind)}")
        h = hashlib.sha256(key.encode()).hexdigest()[:16]
        return os.path.join(self.root, f"at_{h}.json")

    # -- read -------------------------------------------------------------
    def lookup(self, fp: str, mesh, device_kind: str):
        """The stored entry for this key, or None (miss). Corrupt and
        stale entries are rejected + counted, and report as a miss."""
        path = self.path_for(fp, mesh, device_kind)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            self._reject(path, "unreadable/invalid JSON")
            return None
        if not isinstance(doc, dict):
            self._reject(path, "not a JSON object")
            return None
        if doc.get("schema") != SCHEMA:
            self._reject(path, f"schema {doc.get('schema')!r} != "
                               f"{SCHEMA!r} (schema bump: re-tune)")
            return None
        # the entry's own key fields must MATCH the lookup — the hash is
        # an address, not a proof; device kind is the safety-critical leg
        for field, want in (("fingerprint", fp),
                            ("mesh", self._norm_mesh(mesh)),
                            ("device_kind",
                             normalize_device_kind(device_kind))):
            if doc.get(field) != want:
                self._reject(path, f"{field} mismatch: entry "
                                   f"{doc.get(field)!r} vs lookup "
                                   f"{want!r}")
                return None
        try:
            KnobConfig.from_dict(doc.get("winner"))
        except ValueError as e:
            self._reject(path, f"unparseable winner config: {e}")
            return None
        return doc

    def _reject(self, path, why):
        self.rejects += 1
        _count_reject()
        import warnings
        warnings.warn(f"autotune cache entry {path} rejected ({why}); "
                      f"treating as a miss — the tuner will re-search",
                      stacklevel=3)

    # -- write ------------------------------------------------------------
    def store(self, fp: str, mesh, device_kind: str, winner: KnobConfig,
              score: dict, default=None, trials=None, diagnosis=None,
              provenance=None):
        """Persist a winner with full measurement provenance. Atomic;
        best-effort (an unwritable cache dir costs persistence, not the
        run). Returns the entry dict (written or not)."""
        entry = {
            "schema": SCHEMA,
            "fingerprint": fp,
            "mesh": self._norm_mesh(mesh),
            "device_kind": normalize_device_kind(device_kind),
            "winner": winner.to_dict(),
            "score": dict(score or {}),
            "default": dict(default) if default else None,
            "diagnosis": diagnosis,
            "provenance": provenance
            or (score or {}).get("provenance"),
            "trials": list(trials or []),
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        path = self.path_for(fp, mesh, device_kind)
        try:
            os.makedirs(self.root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(entry, f, indent=1)
            os.replace(tmp, path)          # atomic: torn write != entry
        except OSError as e:
            import warnings
            warnings.warn(f"autotune cache write failed ({e}); winner "
                          f"not persisted", stacklevel=2)
        return entry

    # -- sweep ingestion --------------------------------------------------
    def ingest(self, results, fp: str, mesh, device_kind: str):
        """Adopt the best OK trial of a manual sweep as this key's
        winner — sweep rows and tuner trials are the same record shape
        by construction, so a manual protocol feeds the same cache the
        tuner reads. Returns
        the stored entry, or None when no usable trial."""
        from .trial import score as _score
        ok = [r for r in results if getattr(r, "ok", False)
              and r.config is not None]
        if not ok:
            return None
        best = max(ok, key=lambda r: _score(r.measurement))
        m = best.measurement or {}
        return self.store(
            fp, mesh, device_kind, best.config,
            score={"busy_fraction": m.get("busy_fraction"),
                   "step_ms": m.get("step_ms"), "mfu": m.get("mfu"),
                   "value": m.get("value"),
                   "provenance": m.get("provenance")},
            trials=[r.row() for r in results],
            provenance=m.get("provenance"))
