"""mxtpu.mxlint.runtime — the strict-mode jit-program auditor.

The static half of mxlint proves properties of the SOURCE; this module
audits what the process actually DOES. Armed (``MXTPU_STRICT=1``, or
``enable()``), three detectors watch
the steady loop:

* **host-sync detection** — :meth:`StrictAuditor.guarded` wraps each
  steady-loop dispatch in ``jax.transfer_guard_device_to_host
  ("disallow")`` AND a framework-level sync sentinel (the NDArray
  materialization entry points — ``asnumpy``/``asscalar``/
  ``__array__``/``wait_to_read`` — report into the auditor while a
  guarded dispatch is on this thread's stack). Two channels because the
  CPU backend's zero-copy arrays never trip jax's transfer guard, and
  tier-1 must be able to prove the detector fires; on a real
  accelerator both channels watch (on CPU the jax guard is additionally
  DISARMED outright — see ``_JAX_GUARD_OK``: this jaxlib's disallow
  guard destabilizes concurrent ``device_put``). A trip counts
  ``mxlint.transfer_guard_trips`` + flight breadcrumb + structured
  event. On CPU the sentinel counts WITHOUT perturbing the dispatch —
  the run completes; on an accelerator a jax-guard trip aborts the
  dispatch mid-flight (the XLA execution already ran and may have
  donated its inputs — no side-effect-safe re-run exists), so strict
  mode re-raises it as a counted, loud failure.
* **recompile-storm detection** — perfscope's ``record_program`` pushes
  every compile capture here (one predicate when off). After
  :meth:`mark_warmup_done`, a capture for an already-seen program name
  is a steady-state recompile: counted ``mxlint.recompiles`` and NAMED
  (the offender list lands in ``extra.mxlint.recompiled_programs``).
* **donation-violation detection** — a read of an already-donated
  (deleted) buffer inside a guarded dispatch raises jax's
  "Array has been deleted"; the auditor counts it
  (``mxlint.donation_violations``) before re-raising — unlike a host
  sync, a deleted-buffer read has no safe re-run.

Off-path cost: one ``_AUD is None`` predicate per hook (the healthmon/
devicescope module-global discipline), pinned by the overhead test.

``extra.mxlint`` (validated by trace_check's ``check_mxlint_extra``)::

    {"strict": true, "findings": 0, "transfer_guard_trips": 0,
     "allowed_syncs": 0, "recompiles": 0, "recompiled_programs": [],
     "donation_violations": 0, "guarded_dispatches": 200}

or the disabled shape ``{"strict": false}``.
"""
from __future__ import annotations

import contextlib
import threading

from ..diagnostics import flight as _flight
from ..profiler.counters import counter as _counter, set_gauge as _gauge
from .families import FAMILY_TABLES

__all__ = ["StrictAuditor", "enable", "disable", "enabled",
           "enable_from_env", "auditor", "guarded", "allowed_sync",
           "mark_warmup_done", "bench_extra", "settle", "MXLINT_FAMILIES"]

MXLINT_FAMILIES = FAMILY_TABLES["mxlint"]

# module global: None = strict mode off (THE fast-path predicate)
_AUD = None


def _classify_error(e: BaseException) -> str:
    msg = str(e).lower()
    if "deleted" in msg or "donated" in msg:
        return "donation"
    if "transfer" in msg and ("disallow" in msg or "guard" in msg):
        return "transfer"
    return "other"


# None = undetermined; the jax disallow-guard is armed only on real
# accelerators. On XLA:CPU it is BOTH useless (zero-copy arrays never
# trip it — measured) and dangerous: entering ONE empty, properly
# exited `transfer_guard_device_to_host("disallow")` scope destabilizes
# the CPU client's concurrent device_put (probed on this jaxlib: ~40%
# segfault rate in the prefetcher worker under the resilience suite
# afterwards, 0% without; the "allow" level is clean). The NDArray
# sentinel is the CPU detection channel.
_JAX_GUARD_OK = None


def _jax_guard_usable() -> bool:
    global _JAX_GUARD_OK
    if _JAX_GUARD_OK is None:
        try:
            import jax
            jax.transfer_guard_device_to_host  # noqa: B018 — probe
            _JAX_GUARD_OK = jax.default_backend() != "cpu"
        except Exception:  # noqa: BLE001 — no backend / old jax
            _JAX_GUARD_OK = False
    return _JAX_GUARD_OK


@contextlib.contextmanager
def _d2h_guard(level: str):
    """jax's device-to-host transfer guard on real accelerators; a
    no-op on CPU / without a backend (see _JAX_GUARD_OK above — the
    auditor's NDArray sentinel still watches everywhere)."""
    if not _jax_guard_usable():
        yield
        return
    import jax
    with jax.transfer_guard_device_to_host(level):
        yield


class StrictAuditor:
    """Per-process strict-mode state. Constructed via :func:`enable`."""

    def __init__(self):
        self._c_dispatches = _counter("mxlint.guarded_dispatches",
                                      "mxlint")
        self._c_trips = _counter("mxlint.transfer_guard_trips", "mxlint")
        self._c_allowed = _counter("mxlint.allowed_syncs", "mxlint")
        self._c_recompiles = _counter("mxlint.recompiles", "mxlint")
        self._c_donations = _counter("mxlint.donation_violations",
                                     "mxlint")
        self._lock = threading.Lock()
        self._seen_programs: set = set()
        self._recompiled: dict = {}       # name -> count after warmup
        self._warmed = False
        # guarded-dispatch depth per thread: the sync sentinel only
        # counts syncs that happen INSIDE a guarded dispatch on the
        # same thread (the end-of-loop loss fetch is outside, legit)
        self._local = threading.local()

    # -- per-dispatch guard ----------------------------------------------
    def guarded(self, thunk):
        """Run one steady-loop dispatch under the host-sync guard."""
        self._c_dispatches.increment()
        st = self._local
        st.depth = getattr(st, "depth", 0) + 1
        st.noted = False
        try:
            try:
                with _d2h_guard("disallow"):
                    return thunk()
            except Exception as e:  # noqa: BLE001 — classified below
                kind = _classify_error(e)
                if kind == "donation":
                    self._record("donation_violation", repr(e)[:200])
                    raise
                if kind == "transfer":
                    # the NDArray sentinel may have already counted this
                    # very sync before jax raised — one trip, not two
                    if not st.noted:
                        self._record("host_sync", repr(e)[:200])
                    # by the time the guard raised, the XLA dispatch
                    # already executed (and may have donated its
                    # inputs): re-running would double-apply the
                    # update or read deleted buffers. On an
                    # accelerator a guarded host sync is therefore a
                    # COUNTED, LOUD failure; the CPU path (jax guard
                    # disarmed, sentinel counts without raising) is
                    # the one that detects-and-continues.
                    raise
                raise
        finally:
            st.depth -= 1

    def note_sync(self, what: str):
        """NDArray materialization sentinel (pushed into the ndarray
        module by :func:`enable`). Counts only inside a guarded
        dispatch on this thread, and only when not explicitly
        allowed."""
        st = self._local
        if getattr(st, "depth", 0) <= 0 or getattr(st, "allowed", 0) > 0:
            return
        st.noted = True
        self._record("host_sync", what)

    @contextlib.contextmanager
    def allowed_sync(self, reason: str):
        """Declare a deliberate host sync inside a guarded region (a
        debugging fetch, a boundary barrier): counted separately, never
        a trip."""
        self._c_allowed.increment()
        st = self._local
        st.allowed = getattr(st, "allowed", 0) + 1
        try:
            with _d2h_guard("allow"):
                yield
        finally:
            st.allowed -= 1

    # -- recompile detector ----------------------------------------------
    def mark_warmup_done(self):
        """Everything compiled so far was warmup; from here on, a
        re-capture of a known program is a steady-state recompile."""
        with self._lock:
            self._warmed = True

    def note_program(self, name: str, kind: str = "program"):
        """perfscope ``record_program`` hook (one predicate when strict
        is off)."""
        with self._lock:
            if self._warmed and name in self._seen_programs:
                self._recompiled[name] = self._recompiled.get(name, 0) + 1
                recompile = True
            else:
                self._seen_programs.add(name)
                recompile = False
        if recompile:
            self._record("recompile", name)

    # -- reporting --------------------------------------------------------
    def _record(self, what: str, detail: str):
        """One finding on all three surfaces at once (the healthmon
        discipline): counter + flight breadcrumb + structured event."""
        cmap = {"host_sync": self._c_trips,
                "recompile": self._c_recompiles,
                "donation_violation": self._c_donations}
        cmap[what].increment()
        if _flight._REC is not None:
            _flight.record("alert", f"mxlint.{what}", {"detail": detail})
        try:
            from .. import healthmon as _hm
            if _hm._HM is not None:
                _hm._HM.events.emit("alert", f"mxlint.{what}",
                                    args={"detail": detail})
        except Exception:  # noqa: BLE001 — reporting must never raise
            pass

    def findings(self) -> int:
        return (int(self._c_trips.value) + int(self._c_recompiles.value)
                + int(self._c_donations.value))

    def bench_extra(self) -> dict:
        with self._lock:
            recompiled = sorted(self._recompiled)
        return {
            "strict": True,
            "findings": self.findings(),
            "transfer_guard_trips": int(self._c_trips.value),
            "allowed_syncs": int(self._c_allowed.value),
            "recompiles": int(self._c_recompiles.value),
            "recompiled_programs": recompiled,
            "donation_violations": int(self._c_donations.value),
            "guarded_dispatches": int(self._c_dispatches.value),
        }


# ---------------------------------------------------------------------------
# module surface (the _AUD predicate discipline)
# ---------------------------------------------------------------------------

def enable() -> StrictAuditor:
    """Arm strict mode: install the NDArray sync sentinel and the
    perfscope recompile hook, publish ``mxlint.strict=1``."""
    global _AUD
    if _AUD is not None:
        return _AUD
    _AUD = StrictAuditor()
    from .. import ndarray as _nd
    from ..perfscope import cost as _cost
    _nd._STRICT_SYNC = _AUD.note_sync
    _cost._STRICT_HOOK = _AUD.note_program
    _gauge("mxlint.strict", 1, "mxlint")
    return _AUD


def disable():
    global _AUD
    if _AUD is None:
        return
    from .. import ndarray as _nd
    from ..perfscope import cost as _cost
    _nd._STRICT_SYNC = None
    _cost._STRICT_HOOK = None
    _AUD = None
    _gauge("mxlint.strict", 0, "mxlint")


def enabled() -> bool:
    return _AUD is not None


def enable_from_env():
    """MXTPU_STRICT=1 arms the auditor at import (like MXTPU_HEALTHMON;
    raw read allowlisted — this runs during package init, before the
    knob home is guaranteed importable)."""
    import os
    if os.environ.get("MXTPU_STRICT", "") == "1":
        enable()


def auditor():
    return _AUD


def guarded(thunk):
    """Run a dispatch under the strict guard, or plainly when off (the
    one-predicate off path)."""
    if _AUD is None:
        return thunk()
    return _AUD.guarded(thunk)


@contextlib.contextmanager
def allowed_sync(reason: str):
    if _AUD is None:
        yield
        return
    with _AUD.allowed_sync(reason):
        yield


def mark_warmup_done():
    if _AUD is not None:
        _AUD.mark_warmup_done()


def settle():
    """Publish end-of-run gauges (a driver calls this before it reads
    ``bench_extra()``)."""
    if _AUD is not None:
        _gauge("mxlint.findings", _AUD.findings(), "mxlint")


def bench_extra() -> dict:
    """The ``extra.mxlint`` payload, or the disabled shape."""
    if _AUD is None:
        return {"strict": False}
    settle()
    return _AUD.bench_extra()
