"""Device contexts — the TPU-native analogue of MXNet's mx.cpu()/mx.gpu().

Reference parity: python/mxnet/context.py (Context, cpu, gpu, num_gpus,
current_context, context scope via `with`). Here a Context wraps a
`jax.Device`; `tpu(i)` is a first-class device alongside `cpu(i)`, per the
north star. Placement happens through `jax.device_put`; compute launched on
arrays resident on a device runs there (XLA), so MXNet's stream semantics
map onto XLA's async dispatch.
"""
from __future__ import annotations

import subprocess
import sys
import threading

import jax


class Context:
    """A device context. ``with ctx:`` scopes the default context."""

    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = str(device_type)
        self.device_id = int(device_id)
        self._device = None  # resolved lazily

    # -- resolution -------------------------------------------------------
    @property
    def device(self) -> jax.Device:
        if self._device is None:
            self._device = _resolve_device(self.device_type, self.device_id)
        return self._device

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    # -- scoping ----------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = []
        Context._tls.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()
        return False

    @classmethod
    def current(cls) -> "Context":
        stack = getattr(cls._tls, "stack", None)
        if stack:
            return stack[-1]
        return default_context()


def _local(devs):
    """Process-local (addressable) devices only: in a multi-process
    cluster `mx.cpu(0)`/`mx.tpu(0)` means THIS worker's device 0, exactly
    as the reference's `mx.gpu(0)` is local to its worker — and jax
    refuses to place data on another process's devices anyway."""
    mine = [d for d in devs if d.process_index == jax.process_index()]
    return mine or devs


def _platform_devices(platform: str):
    try:
        return _local(jax.devices(platform))
    except RuntimeError:
        return []


def _resolve_device(device_type: str, device_id: int) -> jax.Device:
    """`device_type` IS the jax platform name: `tpu(0)` resolves on a
    machine with a TPU and raises everywhere else — no stand-in."""
    devs = _platform_devices(device_type)
    if not devs:
        raise ValueError(
            f"No device of type {device_type!r} available (jax platforms: "
            f"{[d.platform for d in jax.devices()]})"
        )
    if device_id >= len(devs):
        raise ValueError(f"{device_type}({device_id}) out of range: {len(devs)} available")
    return devs[device_id]


_default_ctx = None


def default_context() -> Context:
    """Default context: the first device of JAX's default backend (TPU on a
    TPU host, CPU in the test environment)."""
    global _default_ctx
    if _default_ctx is None:
        dev = _local(jax.devices())[0]
        ctx = Context(dev.platform, 0)
        ctx._device = dev
        _default_ctx = ctx
    return _default_ctx


def current_context() -> Context:
    return Context.current()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def num_tpus() -> int:
    return len(_platform_devices("tpu"))


def num_gpus() -> int:
    return len(_platform_devices("gpu"))


def ctx_from_device(dev: jax.Device) -> Context:
    ctx = Context(dev.platform, dev.id)
    ctx._device = dev
    return ctx


# -- one process per chip ---------------------------------------------------
#
# A TPU chip belongs to one process at a time: the first process that opens
# the backend takes every local chip and keeps them until it exits. A
# launcher whose CHILDREN need the device (spawned fleet workers)
# therefore has to stay off jax until they are done — and can ask
# these two what is the case.

def backend_opened() -> bool:
    """Has THIS process opened a jax backend yet (and, on a TPU host,
    taken the chips)?"""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def holds_accelerator() -> bool:
    """Has THIS process opened a backend that is not the CPU? Then no
    child process can have the device any more."""
    return backend_opened() and jax.default_backend() != "cpu"


def devices_seen_by_a_child(timeout: float = 300.0):
    """``(platform, device_kind, count)`` as a fresh child process sees
    this host's devices; the child has exited, and let go of the chip,
    when this returns. Raises when the child cannot reach a device — as
    it cannot while another process, this one included, holds the chip."""
    code = ("import jax; d = jax.devices(); "
            "print(d[0].platform, len(d), d[0].device_kind, sep='|')")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout)
    if r.returncode:
        raise RuntimeError(
            "a child process could not open a jax backend (a TPU chip "
            "belongs to one process at a time — is this process, or "
            f"another, holding it?):\n{r.stderr[-1000:]}")
    platform, count, kind = r.stdout.strip().splitlines()[-1].split("|", 2)
    return platform, kind, int(count)


def normalize_device_kind(kind) -> str:
    """Canonical device-kind spelling: lowercased, stripped. jax reports
    'TPU v4' raw while perfscope's peaks table records 'tpu v4' — compare
    device kinds through this (mxlint's ``unnormalized-device-kind``),
    or a raw == is a silent never-match."""
    return str(kind or "unknown").strip().lower() or "unknown"


def gpu_memory_info(device_id=0):
    """Parity: mx.context.gpu_memory_info — (free, total) bytes for the
    accelerator. Backed by the jax device's memory_stats(); raises on
    backends that expose none (the reference raises on non-GPU builds)."""
    import jax
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if device_id >= len(devs):
        raise ValueError(f"no accelerator device {device_id} "
                         f"(have {len(devs)})")
    stats = devs[device_id].memory_stats()
    if not stats:
        raise RuntimeError("device exposes no memory statistics")
    total = stats.get("bytes_limit", stats.get("bytes_reservable_limit"))
    if not total:
        raise RuntimeError("device memory statistics carry no capacity "
                           f"limit (keys: {sorted(stats)})")
    used = stats.get("bytes_in_use", 0)
    return total - used, total
