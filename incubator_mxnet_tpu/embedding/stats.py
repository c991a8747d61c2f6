"""Table census + per-step lookup accounting for ``extra.embedding``.

Every :class:`~.blocks.ShardedEmbedding` registers itself here at
construction; :func:`bench_extra` walks the live tables and reports the
numbers the artifact json schema (tools/trace_check.py
``check_embedding_extra``) gates:

* ``table_bytes_logical`` — what a replicated copy of every table costs
  per device (the number memscope would show with no sharding);
* ``table_bytes_per_device`` — what device 0 actually holds, read off
  the jax arrays' addressable shards (ground truth, not an estimate).
  Sharded correctly, this is strictly below logical;
* ``dedup_rate`` / ``rows_touched_per_step`` / ``ids_per_step`` — from
  :func:`observe_batch`, which a driver's eager loop feeds with the
  raw id stream (host-side numpy: the jit'd program cannot count for
  us, and the driver already owns the concrete batch).

dedup_rate = 1 - unique/total: 0.0 means dedup buys nothing, 0.75 means
the gather moves a quarter of the naive traffic. perf_regress.py gates
a drop in this number — a dedup regression is a silent comms blowup.
"""
from __future__ import annotations

import threading
import weakref

import numpy as np

__all__ = ["register_table", "observe_batch", "table_stats", "bench_extra",
           "reset"]

_lock = threading.Lock()
_TABLES: "list[dict]" = []        # {"ref": weakref to block, "name": str}
_STEP = {"ids": 0, "rows": 0, "batches": 0, "dedup_num": 0.0}


def register_table(block) -> None:
    with _lock:
        _TABLES.append({"ref": weakref.ref(block)})
    from ..profiler.counters import set_gauge
    set_gauge("embedding.tables", len(_live_blocks()), "embedding")


def _live_blocks():
    with _lock:
        out = []
        for t in _TABLES:
            b = t["ref"]()
            if b is not None:
                out.append(b)
        return out


def observe_batch(ids, input_dim: int) -> dict:
    """Account one concrete id batch (any shape, any integer/float
    carrier): total ids, unique rows touched, dedup rate. Called from
    a driver's eager loop; cheap host-side numpy."""
    ids = np.asarray(ids)
    total = int(ids.size)
    uniq = int(np.unique(np.rint(ids.reshape(-1)).astype(np.int64)).size)
    rate = 1.0 - (uniq / total) if total else 0.0
    with _lock:
        _STEP["ids"] += total
        _STEP["rows"] += uniq
        _STEP["batches"] += 1
        _STEP["dedup_num"] += rate
    from ..profiler.counters import set_gauge
    set_gauge("embedding.ids_per_step", total, "embedding")
    set_gauge("embedding.rows_touched_per_step", uniq, "embedding")
    set_gauge("embedding.dedup_rate", round(rate, 6), "embedding")
    return {"ids": total, "rows_touched": uniq, "dedup_rate": rate}


def _param_device_bytes(p) -> "tuple[int, int]":
    """(logical_bytes, device0_bytes) for one Parameter; device0 bytes
    read from the raw array's addressable shards when initialized."""
    import jax

    shape = tuple(p._shape or ())
    logical = int(np.prod(shape)) * np.dtype(p.dtype or "float32").itemsize
    dev_bytes = logical      # an uninitialized/unsharded table is replicated
    try:
        raw = p.data()._data
        dev0 = jax.devices()[0]
        shards = [s for s in raw.addressable_shards if s.device == dev0]
        if shards:
            dev_bytes = int(sum(int(np.prod(s.data.shape)) *
                                s.data.dtype.itemsize for s in shards))
    except Exception:  # noqa: BLE001 — census never breaks a run
        pass
    return logical, dev_bytes


def table_stats() -> "list[dict]":
    out = []
    for b in _live_blocks():
        p = getattr(b, "weight", None)
        if p is None:
            continue
        logical, dev = _param_device_bytes(p)
        out.append({
            "name": getattr(p, "name", "weight"),
            "vocab": int(b._input_dim),
            "dim": int(b._output_dim),
            "bytes_logical": logical,
            "bytes_device0": dev,
            "dedup": bool(b._dedup),
            "oor_policy": b._oor_policy,
        })
    return out


def bench_extra() -> dict:
    """The ``extra.embedding`` block for artifact json."""
    from ..profiler.counters import counters as _counters
    from ..profiler.counters import set_gauge as _set_gauge
    tables = table_stats()
    with _lock:
        batches = _STEP["batches"]
        ids = _STEP["ids"] / batches if batches else 0.0
        rows = _STEP["rows"] / batches if batches else 0.0
        rate = _STEP["dedup_num"] / batches if batches else 0.0
    ctrs = _counters()
    logical = sum(t["bytes_logical"] for t in tables)
    per_dev = sum(t["bytes_device0"] for t in tables)
    _set_gauge("embedding.table_bytes_logical", logical, "embedding")
    _set_gauge("embedding.table_bytes_per_device", per_dev, "embedding")
    return {
        "tables": len(tables),
        "table_bytes_logical": logical,
        "table_bytes_per_device": per_dev,
        "rows_total": sum(t["vocab"] for t in tables),
        "ids_per_step": round(ids, 3),
        "rows_touched_per_step": round(rows, 3),
        "dedup_rate": round(rate, 6),
        "oor_policy": (tables[0]["oor_policy"] if tables else "clip"),
        "oor_ids": int(ctrs.get("embedding/embedding.oor_ids", 0)),
        "lookups": int(ctrs.get("embedding/embedding.lookups", 0)),
        "sparse_rows_updated": int(
            ctrs.get("embedding/embedding.sparse_rows_updated", 0)),
        "table_detail": tables,
    }


def reset() -> None:
    with _lock:
        _TABLES.clear()
        _STEP.update({"ids": 0, "rows": 0, "batches": 0, "dedup_num": 0.0})
