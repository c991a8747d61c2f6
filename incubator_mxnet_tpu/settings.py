"""mxtpu.settings — the ONE home of the package's environment reads.

Every ``MXTPU_*`` name the package reads resolves here, at the bottom of
the package (this module imports nothing of it), in one order:

    call-site argument  >  MXTPU_*  >  default

* **call-site argument** — an explicit Python argument always wins
  (``TrainLoop(chunk=8)``, ``Trainer(loop_chunk=4)``).
* **MXTPU_*** — the process-level spelling.
* **default** — the setting's documented default (README.md, Settings).

:func:`resolve` serves the four settings more than one layer asks for
and returns ``(value, source)``:

==================  ====================================================
``loop_chunk``      ``MXTPU_LOOP_CHUNK`` — micro-steps compiled into one
                    XLA program (0 = stepwise)
``prefetch_depth``  ``MXTPU_PREFETCH_DEPTH`` — io.DevicePrefetcher's
                    device-side buffer depth (>= 1)
``io_workers``      ``MXTPU_IO_WORKERS`` — io.Pipeline's decode-pool
                    width (>= 1)
``pallas``          kernel master switch from its three spellings
                    (``MXTPU_PALLAS`` / ``MXTPU_NO_PALLAS`` /
                    ``MXTPU_FORCE_PALLAS``): ``auto`` / ``on`` /
                    ``force`` / ``off``, off > force > on
==================  ====================================================

Everything else has one spelling and reads through ``env_raw`` /
``env_str`` / ``env_int`` / ``env_float`` / ``env_flag``: one
truthy-spelling table, one error policy. mxlint's ``raw-env-read`` rule
holds every other module of the package to this one.
"""
from __future__ import annotations

import os
import warnings

__all__ = ["FIELDS", "resolve", "reset_warned",
           "env_raw", "env_str", "env_int", "env_float", "env_flag",
           "TRUE_SPELLINGS", "FALSE_SPELLINGS"]

FIELDS = ("loop_chunk", "prefetch_depth", "io_workers", "pallas")

_DEFAULTS = {"loop_chunk": 0, "prefetch_depth": 2, "io_workers": 2,
             "pallas": "auto"}

_ENV = {"loop_chunk": "MXTPU_LOOP_CHUNK",
        "prefetch_depth": "MXTPU_PREFETCH_DEPTH",
        "io_workers": "MXTPU_IO_WORKERS"}

# warnings fire once per key per process
_WARNED: set = set()


def reset_warned() -> None:
    """Test hook: re-arm the once-per-process warnings."""
    _WARNED.clear()


def _parse(field: str, raw: str) -> int:
    """Parse one env string into the setting's type. Raises ValueError
    on garbage — a mistyped setting must fail loudly, not silently
    default."""
    v = int(raw)
    # loop_chunk 0 = stepwise is legal; a zero buffer depth or pool
    # width is not — reject HERE, naming the field, so every consumer
    # sees the same verdict for the same env value
    floor = 0 if field == "loop_chunk" else 1
    if v < floor:
        raise ValueError(f"{field} must be >= {floor}, got {v}")
    return v


def _warn_once(key: str, msg: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, stacklevel=4)


def _resolve_pallas():
    """The pallas master switch from its three spellings, in
    ops/pallas.enabled()'s order (off > force > on > auto). Spellings
    that disagree warn once; the higher one wins."""
    master = os.environ.get("MXTPU_PALLAS", "").strip().lower()
    no = os.environ.get("MXTPU_NO_PALLAS", "").strip().lower() \
        not in ("", "0", "false")
    force = os.environ.get("MXTPU_FORCE_PALLAS", "").strip().lower() \
        not in ("", "0", "false")
    votes = {}
    if master in ("0", "false", "off"):
        votes["MXTPU_PALLAS"] = "off"
    elif master == "force":
        votes["MXTPU_PALLAS"] = "force"
    elif master in ("1", "true", "on"):
        votes["MXTPU_PALLAS"] = "on"
    if no:
        votes["MXTPU_NO_PALLAS"] = "off"
    if force:
        votes["MXTPU_FORCE_PALLAS"] = "force"
    for mode in ("off", "force", "on"):
        names = [n for n, m in votes.items() if m == mode]
        if names:
            losers = [(n, m) for n, m in votes.items() if m != mode]
            if losers:
                _warn_once(
                    "pallas",
                    f"setting 'pallas': {names[0]}={mode!r} and "
                    f"{losers[0][0]}={losers[0][1]!r} disagree — "
                    f"{names[0]} wins (off > force > on)")
            return mode, names[0]
    return None, None


def resolve(field: str, call_site=None):
    """Resolve ONE setting: call-site argument > ``MXTPU_*`` > default.
    Returns ``(value, source)`` where source names what decided:
    ``"call_site"``, the env var's name or ``"default"``."""
    if field not in FIELDS:
        raise ValueError(f"unknown setting {field!r}; expected one of "
                         f"{FIELDS}")
    if call_site is not None:
        return call_site, "call_site"
    if field == "pallas":
        mode, src = _resolve_pallas()
        if mode is not None:
            return mode, src
    else:
        name = _ENV[field]
        raw = env_raw(name)
        if raw is not None:
            return _parse(field, raw), name
    return _DEFAULTS[field], "default"


# the one boolean spelling table
TRUE_SPELLINGS = ("1", "true", "on", "yes")
FALSE_SPELLINGS = ("0", "false", "off", "no", "")


def env_raw(name: str, call_site=None):
    """The raw stripped env string, or None when unset/empty (an empty
    export is "unset", matching every historical call site)."""
    if call_site is not None:
        return call_site
    v = os.environ.get(name, "")
    v = v.strip()
    return v or None


def env_str(name: str, default=None, call_site=None):
    v = env_raw(name, call_site)
    return default if v is None else v


def _env_num(name, default, call_site, on_error, cast):
    if call_site is not None:
        return cast(call_site)
    raw = env_raw(name)
    if raw is None:
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError) as e:
        if on_error == "default":
            # never-raise consumers (analysis paths, crash paths): a
            # typo'd knob degrades to the default, once, loudly
            _warn_once(name + "/parse",
                       f"knob {name}={raw!r} is not a valid "
                       f"{cast.__name__}; using default {default!r}")
            return default
        raise ValueError(f"knob {name}={raw!r}: {e}") from e


def env_int(name: str, default=None, call_site=None,
            on_error: str = "raise"):
    """Integer knob. ``on_error="default"`` for never-raise consumers;
    the default policy fails loudly — a mistyped knob must not
    silently become the default."""
    return _env_num(name, default, call_site, on_error, int)


def env_float(name: str, default=None, call_site=None,
              on_error: str = "raise"):
    return _env_num(name, default, call_site, on_error, float)


def env_flag(name: str, default: bool = False, call_site=None) -> bool:
    """Boolean knob over the ONE spelling table. Never raises: arming
    flags are read at import/enable time, where a typo must degrade
    (to the default, with a once-per-process warning), not crash the
    process."""
    if call_site is not None:
        return bool(call_site)
    raw = env_raw(name)
    if raw is None:
        return default
    low = raw.lower()
    if low in TRUE_SPELLINGS:
        return True
    if low in FALSE_SPELLINGS:
        return False
    _warn_once(name + "/flag",
               f"knob {name}={raw!r} is not a boolean spelling "
               f"({TRUE_SPELLINGS} / {FALSE_SPELLINGS[:-1]}); using "
               f"default {default!r}")
    return default
