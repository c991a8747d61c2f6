"""TPU/XLA bridge for the profiler.

Two jobs:

* :func:`annotation` — when the default backend is a TPU, wrap host-side
  :class:`~incubator_mxnet_tpu.profiler.Scope` regions in
  ``jax.profiler.TraceAnnotation`` so they line up with the XLA device
  trace (TensorBoard/Perfetto shows the host scope spanning the device
  ops it dispatched), whether or not the mx profiler is running: any
  ``jax.profiler.start_trace`` session then holds the program's spans on
  the device operations' clock, and with no session on a TraceMe costs
  well under a microsecond. On CPU/GPU backends this returns None — the
  host Chrome trace is the single source and the annotation would be
  dead weight in the hot path.
* :func:`start_device_trace` / :func:`stop_device_trace` — drive
  ``jax.profiler`` for a full XLA capture when
  ``set_config(profile_xla=True)`` — and for mxtpu.devicescope's
  bounded capture windows, which need to know whether the capture
  actually armed (jax allows ONE active trace per process, so a window
  opened while ``profile_xla`` is tracing must DECLINE, not silently
  share the artifact): ``start_device_trace`` returns True only when
  this call started a fresh trace.

Backend detection is done once and cached; everything degrades to a no-op
if jax's profiler is unavailable (e.g. stripped builds)."""
from __future__ import annotations

_is_tpu = None          # tri-state: None = not yet probed
_tracing = False


def on_tpu() -> bool:
    global _is_tpu
    if _is_tpu is None:
        try:
            import jax
            _is_tpu = jax.default_backend() == "tpu"
        except Exception:
            _is_tpu = False
    return _is_tpu


def annotation(name: str, step_num: int | None = None):
    """A TraceAnnotation context manager for `name` on TPU, else None.
    With a `step_num` it is a StepTraceAnnotation: the device plane of a
    jax trace then groups its operations by the program's own steps."""
    if not on_tpu():
        return None
    try:
        import jax
        if step_num is not None:
            return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return None


def start_device_trace(logdir: str) -> bool:
    """Start a jax profiler trace into ``logdir``. Returns True when
    THIS call armed a fresh trace; False when one is already running
    (ours or anyone's — jax allows one per process) or the profiler is
    unavailable. Callers that need exclusivity (devicescope windows)
    key off the return value."""
    global _tracing
    if _tracing:
        return False
    try:
        import jax
        jax.profiler.start_trace(logdir)
        _tracing = True
        return True
    except Exception:
        return False              # already tracing / profiler unavailable


def stop_device_trace():
    global _tracing
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception:
        pass                      # never started / profiler unavailable
    _tracing = False


def tracing() -> bool:
    """True while a device trace started here is running."""
    return _tracing
