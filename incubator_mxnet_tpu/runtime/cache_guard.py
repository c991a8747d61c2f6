"""Persistent compile-cache integrity guard.

PR 4 found that this jaxlib's CPU backend can MIS-DESERIALIZE
persistent-compilation-cache entries for donated fused-train-step
executables: a process that re-reads executables written by a previous
process gets garbage numerics (1e19 → nan losses) with no error raised.
The fused-step test module was opted out of the cache wholesale; that
made tests safe but left production runs paying a full recompile every
process start — or worse, silently training on garbage when the cache
was enabled anyway.

This module is the re-entry path: a one-time-per-process CANARY that
exercises the exact failure shape (a donated, multi-output, scanned XLA
program) THROUGH the persistent cache and checks the result against its
analytic value. The canary uses dyadic constants (0.5/0.25) so every
intermediate is exact in float32 — the comparison is bitwise, not a
tolerance. On the first process start the canary compiles fresh and
WRITES its cache entry (cheap: a 4-step scan over an (8,128) tile); on
every later start the canary compile is a cache READ, so corrupt
deserialization shows up here — before the real train step compiles —
and the guard disables the persistent cache for the process (with a
warning and a `compile_cache.guard_tripped` counter) instead of letting
training proceed on a broken executable.

`FusedTrainStep` runs the check before its first build; chip_smoke.py
arms it right after backend init. MXTPU_CACHE_GUARD=0 skips
the check (trust the cache).

:func:`use_compile_cache` is the one place an entry point
(benchmark/run.py, chip_smoke.py) chooses where the cache lives.
"""
from __future__ import annotations

import os
import threading
import warnings
from contextlib import contextmanager

import numpy as np

__all__ = ["check", "verdict", "donated_read_quarantine",
           "use_compile_cache", "_reset_for_tests"]

# None = not yet checked; True = cache ok (or not in use); False = tripped
_VERDICT = None


def use_compile_cache(checkout: str) -> str:
    """Turn the persistent compile cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and the
    only one: nothing is set in code. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` — the path is part of the cache key,
    so a directory named after a pid, a time or a temporary name would
    never hit."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir

# -- donated-executable read quarantine (PR 17) ---------------------------
#
# The canary certifies ONE cache read per process; PR 17's flake hunt
# showed the donated-executable corruption is PROBABILISTIC PER READ
# (resilience suite: 6/10 process crashes with a warm cache vs 1/12
# with the cache wiped before every run — heap corruption detonating at
# later allocations, i.e. a deserialized executable whose donation
# aliasing writes through stale addresses). So donated fused-step
# executables must never read the cache at all. Toggling
# ``jax_enable_compilation_cache`` around the dispatch does NOT do
# this: ``compilation_cache.is_cache_used`` latches its verdict at the
# first compile of the process and ignores the flag afterwards. The
# quarantine therefore filters the read primitive itself
# (``get_executable_and_time`` → miss while quarantined); cache WRITES
# still happen, serialization is sound — only deserialization is not.

_READ_QUARANTINE = threading.local()


def _install_read_filter():
    from jax._src import compilation_cache as cc
    if getattr(cc, "_mxtpu_donated_read_filter", None) is not None:
        return
    real_get = cc.get_executable_and_time

    def _filtered_get(cache_key, compile_options, backend,
                      executable_devices):
        if getattr(_READ_QUARANTINE, "on", False):
            return None, None        # forced miss -> fresh backend compile
        return real_get(cache_key, compile_options, backend,
                        executable_devices)

    cc.get_executable_and_time = _filtered_get
    cc._mxtpu_donated_read_filter = real_get


@contextmanager
def donated_read_quarantine():
    """Force persistent-compile-cache MISSES for any compile triggered
    inside the scope (this thread only). Entered by FusedTrainStep
    around every donating dispatch on XLA:CPU — the compile, when one
    happens, then always goes through the sound fresh-compile path."""
    _install_read_filter()
    prev = getattr(_READ_QUARANTINE, "on", False)
    _READ_QUARANTINE.on = True
    try:
        yield
    finally:
        _READ_QUARANTINE.on = prev


def verdict():
    """The cached canary verdict (None when the check hasn't run)."""
    return _VERDICT


def check(force=False) -> bool:
    """Run the persistent-cache canary once per process. Returns True when
    the cache read path is sound (or no persistent cache is configured);
    False when corruption was detected and the cache has been disabled."""
    global _VERDICT
    if _VERDICT is None or force:
        _VERDICT = _run()
    return _VERDICT


def _disabled_by_env():
    from ..settings import env_flag
    return not env_flag("MXTPU_CACHE_GUARD", True)


def _cache_active():
    import jax
    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)


def _run() -> bool:
    from .. import profiler as _prof

    if _disabled_by_env():
        return True
    if not _cache_active():
        return True                 # nothing to guard

    import jax

    # the canary must actually flow THROUGH the persistent cache: lower
    # the size/time thresholds for its one tiny compile, restore after
    overrides = {"jax_persistent_cache_min_entry_size_bytes": -1,
                 "jax_persistent_cache_min_compile_time_secs": 0.0}
    old = {k: getattr(jax.config, k) for k in overrides}
    for k, v in overrides.items():
        jax.config.update(k, v)
    try:
        got_c, got_s = _canary_values()
        exp = _expected()
        ok = (np.array_equal(got_s, exp)
              and np.array_equal(got_c, np.full((8, 128), exp[-1],
                                                np.float32)))
        if not ok:
            _trip(f"canary mismatch: expected row values {exp.tolist()}, "
                  f"got {got_s.tolist()}")
            return False
        _prof.set_gauge("compile_cache.canary_ok", 1)
        return True
    except Exception as e:  # noqa: BLE001 — failure to run == can't trust it
        _trip(f"canary raised {type(e).__name__}: {e}")
        return False
    finally:
        for k, v in old.items():
            jax.config.update(k, v)


def _canary_values():
    """Compile+run the canary program (donated carry, scan, two outputs —
    the fused-step executable family) and return its concrete outputs.
    Split out so tests can monkeypatch a corrupted read."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def prog(w, xs):
        def one(c, x):
            c = c * 0.5 + x
            return c, c[0, 0]
        c, heads = lax.scan(one, w, xs)
        return c, heads

    f = jax.jit(prog, donate_argnums=(0,))
    w = jnp.full((8, 128), 1.0, jnp.float32)
    xs = jnp.full((4, 8, 128), 0.25, jnp.float32)
    with warnings.catch_warnings():
        # CPU ignores donation with a warning; that's fine for the canary
        warnings.simplefilter("ignore")
        c, heads = f(w, xs)
    return np.asarray(c), np.asarray(heads)


def _expected():
    # c_{i} = c_{i-1} * 0.5 + 0.25 from 1.0 — all dyadic, exact in f32
    vals, c = [], 1.0
    for _ in range(4):
        c = c * 0.5 + 0.25
        vals.append(c)
    return np.asarray(vals, np.float32)


def _trip(why):
    from .. import profiler as _prof
    import jax

    warnings.warn(
        "persistent compile-cache integrity canary FAILED — disabling the "
        "persistent compilation cache for this process (executables "
        "deserialized from a previous run cannot be trusted; recompiling "
        f"fresh). Detail: {why}. Delete the cache directory "
        f"({getattr(jax.config, 'jax_compilation_cache_dir', '?')}) to "
        "clear the corrupt entries.", RuntimeWarning, stacklevel=3)
    jax.config.update("jax_enable_compilation_cache", False)
    from jax._src import compilation_cache as cc
    cc.reset_cache()                # drop the already-initialized object
    _prof.counter("compile_cache.guard_tripped").increment()
    _prof.set_gauge("compile_cache.canary_ok", 0)


def _reset_for_tests():
    global _VERDICT
    _VERDICT = None
