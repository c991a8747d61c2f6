"""Shared on-disk AOT compile cache — replica N+1 skips the XLA compile.

A `FrozenModel` pays its compile cost at construction, per bucket.
That is the right trade for one replica (deploy-time, not
request-time), but a fleet multiplies it: N replicas of the *same*
model recompile the *same* executables N times, so replica N+1's
warmup costs exactly as much as replica 0's. The params are runtime
*arguments* of the raw serving function (not baked constants), so two
freezes of architecturally identical blocks lower to byte-identical
StableHLO — the compile is pure waste after the first replica.

`CompileCache` keys on ``sha256(lowered StableHLO text + jax version +
backend)`` and stores `jax.experimental.serialize_executable` payloads:

* **in-process layer** — a dict of live compiled executables (XLA
  executables are immutable and thread-safe to execute), so co-hosted
  replicas share the very same executable object;
* **on-disk layer** — the serialized payload under ``<dir>/<key>.jexec``
  (atomic tmp+rename writes, so concurrent replica processes can share
  one directory), so a *new process* — replica N+1 on another port, a
  restarted replica mid-deploy — deserializes instead of compiling.

Both ``load`` and ``store`` are total: any surprise (version skew, a
torn file, an unpicklable tree) costs one ``fleet.compile_cache_errors``
increment and falls back to a fresh compile — a cache can make a deploy
faster, never break it. Hits/misses/stores are counted in the governed
``fleet`` family so the smoke can *prove* replica 2 skipped its
compiles rather than trusting a wall-clock diff.

`FrozenModel` takes the cache as an explicit ``compile_cache=`` duck:
anything with ``load(lowered)`` / ``store(lowered, compiled)``. The
serving layer stays fleet-agnostic; `ReplicaSet` wires the shared
instance through.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import threading

import jax

from .. import profiler as _prof

__all__ = ["CompileCache", "shared_cache", "set_shared_cache"]


def _c(name):
    return _prof.counter(name, "fleet")


class CompileCache:
    """Two-layer (process dict + directory) AOT executable cache."""

    def __init__(self, path):
        self.path = str(path)
        os.makedirs(self.path, exist_ok=True)
        self._mem = {}
        self._lock = threading.Lock()

    # -- keying -----------------------------------------------------------
    @staticmethod
    def key_for(lowered) -> str:
        """Content key of one lowered bucket: the StableHLO text pins
        the program, the jax version + backend pin the serialization
        format and the runtime it must load into."""
        h = hashlib.sha256()
        h.update(lowered.as_text().encode())
        h.update(jax.__version__.encode())
        h.update(jax.default_backend().encode())
        return h.hexdigest()

    def _file_for(self, key) -> str:
        return os.path.join(self.path, key + ".jexec")

    # -- lookup -----------------------------------------------------------
    def load(self, lowered):
        """The compiled executable for this lowering, or None on miss.
        Never raises — a cache surprise costs a compile, not the
        deploy."""
        try:
            key = self.key_for(lowered)
            with self._lock:
                hit = self._mem.get(key)
            if hit is not None:
                _c("fleet.compile_cache_hits").increment()
                return hit
            path = self._file_for(key)
            if not os.path.exists(path):
                _c("fleet.compile_cache_misses").increment()
                return None
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            compiled = deserialize_and_load(payload, in_tree, out_tree)
            with self._lock:
                self._mem[key] = compiled
            _c("fleet.compile_cache_hits").increment()
            return compiled
        except Exception:  # noqa: BLE001 — total by contract
            _c("fleet.compile_cache_errors").increment()
            return None

    def store(self, lowered, compiled):
        """Serialize one freshly compiled executable into both layers
        (atomic tmp+rename so a concurrent reader never sees a torn
        file). Never raises."""
        try:
            key = self.key_for(lowered)
            with self._lock:
                self._mem[key] = compiled
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(compiled)
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump((payload, in_tree, out_tree), f)
                os.replace(tmp, self._file_for(key))
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            _c("fleet.compile_cache_stores").increment()
        except Exception:  # noqa: BLE001 — total by contract
            _c("fleet.compile_cache_errors").increment()

    def entries(self) -> int:
        """On-disk entry count (diagnostics only)."""
        try:
            return sum(1 for n in os.listdir(self.path)
                       if n.endswith(".jexec"))
        except OSError:
            return 0

    def __repr__(self):
        return f"CompileCache({self.path!r}, entries={self.entries()})"


# ---------------------------------------------------------------------------
# process-wide default (ReplicaSet's fallback), resolved once from the
# MXTPU_FLEET_CACHE knob
# ---------------------------------------------------------------------------

_shared_lock = threading.Lock()
_shared = {"cache": None, "resolved": False}


def shared_cache():
    """The process-wide default CompileCache, or None. Resolved once
    from ``MXTPU_FLEET_CACHE`` (a directory path; empty/unset means no
    cache) unless `set_shared_cache` installed one explicitly."""
    with _shared_lock:
        if not _shared["resolved"]:
            from ..settings import env_str
            path = env_str("MXTPU_FLEET_CACHE", "")
            _shared["cache"] = CompileCache(path) if path else None
            _shared["resolved"] = True
        return _shared["cache"]


def set_shared_cache(cache):
    """Install (or clear, with None) the process-wide default. Accepts
    a CompileCache or a directory path."""
    if isinstance(cache, str):
        cache = CompileCache(cache)
    with _shared_lock:
        _shared["cache"] = cache
        _shared["resolved"] = True
    return cache
