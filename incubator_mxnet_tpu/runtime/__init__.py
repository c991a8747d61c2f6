"""Native runtime bindings (SURVEY.md §2.27): C++ threaded dependency
engine, pooled host-storage allocator, bounded prefetch queue — the rebuild
of the reference's src/engine + src/storage + src/io prefetcher for
host-side work (device compute is scheduled by XLA's async dispatch).

The .so is built on first import with g++ (no pybind11 — plain C API via
ctypes). On a machine WITHOUT a compiler everything degrades to functional
pure-Python equivalents, so the framework never hard-depends on the native
layer; a compiler that is there and FAILS raises with its stderr.
`native_available()` reports which path is live.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from collections import deque

from .features import Feature, Features, feature_list

__all__ = ["Engine", "StoragePool", "TokenQueue", "native_available",
           "get_engine", "engine_type", "Feature", "Features",
           "feature_list"]

_DIR = os.path.dirname(os.path.abspath(__file__))


def _so_dir():
    """Directory for first-use-compiled .so files: the package dir when
    writable (source checkouts — keeps the artifact next to its source),
    else a user cache dir (read-only site-packages installs must not
    silently lose the native engine)."""
    if os.access(_DIR, os.W_OK):
        return _DIR
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME",
                       os.path.join(os.path.expanduser("~"), ".cache")),
        "incubator_mxnet_tpu")
    os.makedirs(cache, exist_ok=True)
    return cache


def _so_path(stem, src_name):
    """Cache artifact path keyed by a hash of the C++ source: a cached .so
    surviving a package upgrade (the user-cache dir outlives read-only
    site-packages installs) must never be loaded against newer source with
    a changed ABI — the hash suffix makes version skew a cache miss, not a
    crash. Stale siblings from older sources are removed opportunistically."""
    import hashlib
    try:
        with open(os.path.join(_DIR, "src", src_name), "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        return os.path.join(_so_dir(), f"{stem}.so")
    d = _so_dir()
    path = os.path.join(d, f"{stem}.{tag}.so")
    try:
        import re
        for fn in os.listdir(d):
            # only hash-suffixed siblings: a plain <stem>.so may be a
            # developer's deliberate Makefile artifact, not our cache
            if re.fullmatch(re.escape(stem) + r"\.[0-9a-f]{12}\.so", fn) \
                    and os.path.join(d, fn) != path:
                os.unlink(os.path.join(d, fn))
    except OSError:
        pass
    return path


_SO = _so_path("libmxtpu_runtime", "runtime.cc")
_lib = None
_build_failed = False
_build_lock = threading.Lock()


def _build_so(src_name, so_path, extra_flags=()):
    """First-use g++ build of a native component: compiles to a pid-unique
    temp file and os.replace()s it into place (atomic on POSIX), so
    concurrent importers (pytest-xdist, DataLoader workers) never observe
    a partially written .so. Returns the loaded CDLL, or None where there
    is no g++ to run (the pure-Python engine takes over); a g++ that runs
    and fails is a broken build, and raises with the compiler's stderr.

    Two passes: a concurrent process sharing the cache dir (e.g. a
    different package version doing its stale-sibling cleanup) can unlink
    the artifact between our exists() check and CDLL load — rebuild once
    instead of permanently disabling the native engine."""
    for _ in range(2):
        if not os.path.exists(so_path):
            src = os.path.join(_DIR, "src", src_name)
            tmp = f"{so_path}.tmp.{os.getpid()}"
            try:
                r = subprocess.run(["g++", "-O2", "-std=c++17", "-fPIC",
                                    "-shared", "-o", tmp, src, *extra_flags],
                                   capture_output=True, text=True,
                                   timeout=120)
                if r.returncode:
                    raise RuntimeError(
                        f"g++ failed (exit {r.returncode}) building "
                        f"{src_name}:\n{r.stderr[-2000:]}")
                os.replace(tmp, so_path)
            except FileNotFoundError:       # no compiler on this machine
                return None
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        try:
            return ctypes.CDLL(so_path)
        except OSError:
            try:
                os.unlink(so_path)   # corrupt or raced away: rebuild
            except OSError:
                pass
    return None


def _build_and_load():
    """Native engine load, guarded by a double-checked lock."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _build_lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib = _build_so("runtime.cc", _SO, ("-pthread",))
        if lib is None:
            _build_failed = True
            return None
        return _register_and_set(lib)


def _register_and_set(lib):
    global _lib
    lib.mxtpu_engine_create.restype = ctypes.c_void_p
    lib.mxtpu_engine_create.argtypes = [ctypes.c_int]
    lib.mxtpu_engine_destroy.argtypes = [ctypes.c_void_p]
    lib.mxtpu_engine_new_var.restype = ctypes.c_int64
    lib.mxtpu_engine_new_var.argtypes = [ctypes.c_void_p]
    lib.mxtpu_engine_push.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.mxtpu_engine_wait_for_var.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxtpu_engine_wait_all.argtypes = [ctypes.c_void_p]
    lib.mxtpu_pool_create.restype = ctypes.c_void_p
    lib.mxtpu_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.mxtpu_pool_alloc.restype = ctypes.c_void_p
    lib.mxtpu_pool_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.mxtpu_pool_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.mxtpu_pool_stats.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_size_t),
                                     ctypes.POINTER(ctypes.c_size_t)]
    lib.mxtpu_queue_create.restype = ctypes.c_void_p
    lib.mxtpu_queue_create.argtypes = [ctypes.c_size_t]
    lib.mxtpu_queue_destroy.argtypes = [ctypes.c_void_p]
    lib.mxtpu_queue_push.restype = ctypes.c_int
    lib.mxtpu_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.mxtpu_queue_pop.restype = ctypes.c_int
    lib.mxtpu_queue_pop.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint64)]
    lib.mxtpu_queue_close.argtypes = [ctypes.c_void_p]
    lib.mxtpu_queue_size.restype = ctypes.c_size_t
    lib.mxtpu_queue_size.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


_OP_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
_ENGINE_ENV = "MXTPU_ENGINE"


def native_available() -> bool:
    return _build_and_load() is not None


# ---------------------------------------------------------------------------
# dependency engine
# ---------------------------------------------------------------------------

class Engine:
    """MXNet-style dependency engine: `push(fn, const_vars, mutable_vars)`
    runs fn on a worker thread once all its var deps resolve (concurrent
    reads, exclusive writes, program order per var)."""

    def __init__(self, num_threads=None, force_python=False):
        num_threads = num_threads or max(2, (os.cpu_count() or 4) // 2)
        self._lib = None if force_python else _build_and_load()
        self._callbacks = {}          # op id -> (fn, vars) until it runs
        self._cb_lock = threading.Lock()
        self._cb_id = 0
        self._errors = []             # [(exc, frozenset(vars))] until raised
        if self._lib is not None:
            # ONE persistent trampoline for all ops: the C side passes the
            # op id as arg, so no per-op CFUNCTYPE object ever gets freed
            # while a worker thread is inside it
            self._dispatch = _OP_FN(self._run_cb)
            self._h = self._lib.mxtpu_engine_create(num_threads)
        else:
            self._py = _PyEngine(num_threads)

    def _run_cb(self, arg):
        cid = int(arg) if arg is not None else 0
        with self._cb_lock:
            ent = self._callbacks.pop(cid, None)
        if ent is None:
            return
        fn, op_vars = ent
        try:
            fn()
        except BaseException as e:  # noqa: BLE001
            # an exception must not escape into the ctypes trampoline (it
            # would be printed and dropped); stash it and re-raise at the
            # next wait_for_var/wait_all — reference engine error semantics
            with self._cb_lock:
                self._errors.append((e, op_vars))

    def _raise_pending(self, var=None):
        with self._cb_lock:
            if not self._errors:
                return
            if var is None:
                exc, _ = self._errors.pop(0)
            else:
                hit = next((i for i, (_, vs) in enumerate(self._errors)
                            if var in vs), None)
                if hit is None:
                    return
                exc, _ = self._errors.pop(hit)
        raise exc

    def new_var(self) -> int:
        if self._lib is not None:
            if not self._h:
                return -1  # destroyed (GC finalization order)
            return self._lib.mxtpu_engine_new_var(self._h)
        return self._py.new_var()

    def push(self, fn, const_vars=(), mutable_vars=()):
        if self._lib is None:
            self._py.push(fn, const_vars, mutable_vars)
            return
        if not self._h:
            return  # destroyed (GC finalization order)
        with self._cb_lock:
            self._cb_id += 1
            cid = self._cb_id
            self._callbacks[cid] = (
                fn, frozenset(const_vars) | frozenset(mutable_vars))
        cv = (ctypes.c_int64 * max(1, len(const_vars)))(*const_vars)
        mv = (ctypes.c_int64 * max(1, len(mutable_vars)))(*mutable_vars)
        self._lib.mxtpu_engine_push(
            self._h, ctypes.cast(self._dispatch, ctypes.c_void_p),
            ctypes.c_void_p(cid),
            cv, len(const_vars), mv, len(mutable_vars))

    def wait_for_var(self, var: int):
        if self._lib is not None:
            if not self._h:
                self._raise_pending(var)  # still surface stashed errors
                return
            self._lib.mxtpu_engine_wait_for_var(self._h, var)
            self._raise_pending(var)
        else:
            self._py.wait_for_var(var)

    def wait_all(self):
        if self._lib is not None:
            if not self._h:
                self._raise_pending()  # still surface stashed errors
                return
            self._lib.mxtpu_engine_wait_all(self._h)
            self._raise_pending()
        else:
            self._py.wait_all()

    def __del__(self):
        if getattr(self, "_lib", None) is not None and \
                getattr(self, "_h", None):
            try:
                self._lib.mxtpu_engine_destroy(self._h)
            except Exception:
                pass
            self._h = None


class _PyEngine:
    """Pure-Python fallback with the same semantics (GIL-bound):
    reads of a var run concurrently after the last write; a write waits for
    the last write AND all reads issued since it."""

    def __init__(self, num_threads):
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(num_threads)
        self._lock = threading.Lock()
        self._last_write = {}         # var -> future of last write
        self._readers = {}            # var -> futures reading since last write
        self._next = 1
        self._futures = set()

    def new_var(self):
        with self._lock:
            v = self._next
            self._next += 1
            return v

    def push(self, fn, const_vars=(), mutable_vars=()):
        with self._lock:
            deps = []
            for v in const_vars:
                d = self._last_write.get(v)
                if d is not None:
                    deps.append(d)
            for v in mutable_vars:
                d = self._last_write.get(v)
                if d is not None:
                    deps.append(d)
                deps.extend(self._readers.get(v, ()))

            def run():
                for d in deps:
                    d.result()
                fn()

            fut = self._pool.submit(run)
            self._futures.add(fut)
            fut.add_done_callback(lambda f: self._futures.discard(f))
            for v in const_vars:
                self._readers.setdefault(v, []).append(fut)
            for v in mutable_vars:
                self._last_write[v] = fut
                self._readers[v] = []

    def wait_for_var(self, var):
        with self._lock:
            futs = [self._last_write.get(var)] + \
                list(self._readers.get(var, ()))
        for fut in futs:
            if fut is not None:
                fut.result()

    def wait_all(self):
        for fut in list(self._futures):
            fut.result()


_global_engine = None
_global_lock = threading.Lock()


def engine_type() -> str:
    """'native' (C++ threaded engine) unless MXTPU_ENGINE=python or the
    toolchain is unavailable."""
    from ..settings import env_str
    if env_str(_ENGINE_ENV, "native") == "python" or \
            not native_available():
        return "python"
    return "native"


def get_engine() -> Engine:
    """Process-wide engine singleton, honoring MXTPU_ENGINE."""
    global _global_engine
    with _global_lock:
        if _global_engine is None:
            _global_engine = Engine(force_python=engine_type() == "python")
        return _global_engine


# ---------------------------------------------------------------------------
# pooled storage
# ---------------------------------------------------------------------------

class StoragePool:
    """Size-bucketed host buffer pool (reference pooled_storage_manager).
    alloc() returns a ctypes void_p usable as a staging buffer; free()
    returns it to the pool rather than the OS."""

    def __init__(self):
        self._lib = _build_and_load()
        if self._lib is not None:
            self._h = self._lib.mxtpu_pool_create()
        else:
            self._buckets = {}
            self._live = {}
            self._used = 0
            self._pooled = 0
            self._plock = threading.Lock()

    @staticmethod
    def _round(size):
        b = 256
        while b < size:
            b <<= 1
        return b

    def alloc(self, size):
        if self._lib is not None:
            if not self._h:
                return None  # destroyed (GC finalization order)
            return self._lib.mxtpu_pool_alloc(self._h, size)
        b = self._round(size)
        with self._plock:
            lst = self._buckets.get(b)
            if lst:
                buf = lst.pop()
                self._pooled -= b
            else:
                buf = ctypes.create_string_buffer(b)
            addr = ctypes.addressof(buf)
            self._live[addr] = (buf, b)
            self._used += b
            return addr

    def free(self, ptr):
        if self._lib is not None:
            if self._h:
                self._lib.mxtpu_pool_free(self._h, ptr)
            return
        with self._plock:
            ent = self._live.pop(ptr, None)
            if ent is None:
                return
            buf, b = ent
            self._buckets.setdefault(b, []).append(buf)
            self._used -= b
            self._pooled += b

    def stats(self):
        if self._lib is not None:
            if not self._h:
                return {"bytes_in_use": 0, "bytes_pooled": 0}
            used = ctypes.c_size_t()
            pooled = ctypes.c_size_t()
            self._lib.mxtpu_pool_stats(self._h, ctypes.byref(used),
                                       ctypes.byref(pooled))
            return {"bytes_in_use": used.value, "bytes_pooled": pooled.value}
        with self._plock:
            return {"bytes_in_use": self._used, "bytes_pooled": self._pooled}

    def __del__(self):
        if getattr(self, "_lib", None) is not None and \
                getattr(self, "_h", None):
            try:
                self._lib.mxtpu_pool_destroy(self._h)
            except Exception:
                pass
            self._h = None


# ---------------------------------------------------------------------------
# bounded token queue (prefetch pipeline backbone)
# ---------------------------------------------------------------------------

class TokenQueue:
    """Bounded blocking queue of u64 tokens; C-side blocking releases the
    GIL, so producer threads in the native engine and the Python consumer
    overlap. push/pop return False after close()."""

    def __init__(self, capacity):
        self._lib = _build_and_load()
        if self._lib is not None:
            self._h = self._lib.mxtpu_queue_create(capacity)
        else:
            self._q = deque()
            self._cap = max(1, capacity)
            self._qlock = threading.Lock()
            self._not_full = threading.Condition(self._qlock)
            self._not_empty = threading.Condition(self._qlock)
            self._closed = False

    def push(self, token) -> bool:
        if self._lib is not None:
            if not self._h:
                return False  # destroyed (GC finalization order)
            return bool(self._lib.mxtpu_queue_push(self._h, token))
        with self._not_full:
            while not self._closed and len(self._q) >= self._cap:
                self._not_full.wait()
            if self._closed:
                return False
            self._q.append(token)
            self._not_empty.notify()
            return True

    def pop(self):
        """Returns token or None when closed+drained."""
        if self._lib is not None:
            if not self._h:
                return None  # destroyed (GC finalization order)
            tok = ctypes.c_uint64()
            ok = self._lib.mxtpu_queue_pop(self._h, ctypes.byref(tok))
            return tok.value if ok else None
        with self._not_empty:
            while not self._closed and not self._q:
                self._not_empty.wait()
            if not self._q:
                return None
            tok = self._q.popleft()
            self._not_full.notify()
            return tok

    def close(self):
        if self._lib is not None:
            # _h is None once __del__ ran: GC may finalize this queue
            # before an abandoned generator's finally calls close()
            if self._h:
                self._lib.mxtpu_queue_close(self._h)
            return
        with self._qlock:
            self._closed = True
            self._not_full.notify_all()
            self._not_empty.notify_all()

    def __len__(self):
        if self._lib is not None:
            if not self._h:
                return 0
            return self._lib.mxtpu_queue_size(self._h)
        with self._qlock:
            return len(self._q)

    def __del__(self):
        if getattr(self, "_lib", None) is not None and \
                getattr(self, "_h", None):
            try:
                self._lib.mxtpu_queue_destroy(self._h)
            except Exception:
                pass
            self._h = None


# ---------------------------------------------------------------------------
# native JPEG decode (src/imgdec.cc, its own .so linked against libjpeg):
# GIL-free decompression for the record-IO pipeline — the rebuild of the
# reference's opencv decode in src/io/iter_image_recordio_2.cc. A machine
# without g++ only disables this path (callers fall back to PIL); a build
# that fails — a missing libjpeg included — raises, as _build_so says.
# ---------------------------------------------------------------------------

_IMG_SO = _so_path("libmxtpu_imgdec", "imgdec.cc")
_img_lib = None
_img_build_failed = False
_img_lock = threading.Lock()


def _imgdec_lib():
    global _img_lib, _img_build_failed
    if _img_lib is not None:
        return _img_lib
    if _img_build_failed:
        return None
    with _img_lock:
        if _img_lib is not None or _img_build_failed:
            return _img_lib
        lib = _build_so("imgdec.cc", _IMG_SO, ("-ljpeg",))
        if lib is None:
            _img_build_failed = True
            return None
        lib.mxtpu_jpeg_info.restype = ctypes.c_int
        lib.mxtpu_jpeg_info.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        lib.mxtpu_jpeg_decode.restype = ctypes.c_int
        lib.mxtpu_jpeg_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
        lib.mxtpu_jpeg_decode_once.restype = ctypes.c_int
        lib.mxtpu_jpeg_decode_once.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        _img_lib = lib
        return lib


def jpeg_decode_available():
    """True when the native libjpeg decoder built and loaded."""
    return _imgdec_lib() is not None


# PIL's decompression-bomb threshold: the native path enforces the same
# cap so a crafted header can't trigger a multi-GB allocation
_MAX_IMAGE_PIXELS = 178956970


_scratch = threading.local()


def decode_jpeg(data, channels=3):
    """Decode JPEG bytes to an HWC uint8 numpy array via the native
    decoder (channels: 3=RGB, 1=grayscale via libjpeg's Y channel).
    Returns None when the native path is unavailable, the stream is
    corrupt/truncated, or the size exceeds the decompression-bomb cap —
    callers fall back to PIL.

    Hot path does ONE native call (single header parse) into a growable
    per-thread scratch buffer; the pixels are then copied out into an
    exact-size array (one memcpy, still far cheaper than a reparse)."""
    import numpy as _np
    lib = _imgdec_lib()
    if lib is None:
        return None
    data = bytes(data)
    buf = getattr(_scratch, "buf", None)
    if buf is None:
        buf = _scratch.buf = _np.empty(1 << 20, _np.uint8)  # 1 MiB start
    w = ctypes.c_int()
    h = ctypes.c_int()
    for _ in range(2):
        rc = lib.mxtpu_jpeg_decode_once(
            data, len(data), buf.ctypes.data_as(ctypes.c_void_p),
            buf.nbytes, channels, ctypes.byref(w), ctypes.byref(h))
        if rc == 0:
            break
        if rc < 0 or w.value * h.value > _MAX_IMAGE_PIXELS:
            return None
        buf = _scratch.buf = _np.empty(rc, _np.uint8)   # grow + retry
    else:
        return None
    n = w.value * h.value * channels
    return buf[:n].reshape(h.value, w.value, channels).copy()


__all__ += ["decode_jpeg", "jpeg_decode_available"]
