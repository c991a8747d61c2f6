"""Monitor (parity: python/mxnet/monitor.py): per-batch inspection of a
Module executor's arrays — outputs, arguments, gradients, aux — with a
stat function and interval. The reference hooks the C++ executor's output
callbacks; here `tic()` snapshots nothing and `toc()` reads the executor
dicts after the step (same observable behavior, no async machinery to
intercept because XLA owns the schedule)."""
from __future__ import annotations

import logging
import re

import numpy as np

from . import profiler as _prof

__all__ = ["Monitor"]


class Monitor:
    def __init__(self, interval, stat_func=None, pattern=".*", sort=False):
        self.interval = max(1, int(interval))
        self.stat_func = stat_func or (lambda x: np.abs(x).mean())
        self.pattern = re.compile(pattern)
        self.sort = sort
        self.step = 0
        self.activated = False
        self._sources = []
        self.queue = []

    def install(self, module_or_exec):
        """Attach to a Module, BucketingModule, or raw Executor. Executors
        are resolved at toc() time, so rebinds and buckets created after
        install are still observed."""
        if not (hasattr(module_or_exec, "_exec")
                or hasattr(module_or_exec, "_buckets")
                or hasattr(module_or_exec, "arg_dict")):
            raise TypeError(f"cannot monitor {type(module_or_exec).__name__};"
                            " expected Module, BucketingModule or Executor")
        self._sources.append(module_or_exec)
        return self

    def _live_execs(self):
        out = []
        for src in self._sources:
            if hasattr(src, "arg_dict"):          # raw Executor
                out.append(src)
            elif hasattr(src, "_buckets"):        # BucketingModule
                out.extend(m._exec for m in src._buckets.values()
                           if m._exec is not None)
            elif getattr(src, "_exec", None) is not None:
                out.append(src._exec)
        return out

    def tic(self):
        """Start-of-batch: arm collection for this step if due."""
        self.activated = (self.step % self.interval == 0)
        self.queue = []
        self.step += 1

    def _collect(self, ex):
        rows = []
        # an executor may have no outputs (e.g. bound for backward only, or
        # a partial bind mid-rebuild) — treat that as an empty output dict
        # instead of indexing blindly
        try:
            outputs = ex.outputs or []
        except Exception:
            outputs = []
        outs = {f"output{i}": o for i, o in enumerate(outputs)}
        for source in (ex.arg_dict, ex.aux_dict, ex.grad_dict, outs):
            for name, arr in source.items():
                tag = name if source is not ex.grad_dict else name + "_grad"
                if arr is None or not self.pattern.match(tag):
                    continue
                rows.append((self.step - 1, tag,
                             self.stat_func(np.asarray(arr._data))))
        return rows

    def toc(self):
        """End-of-batch: collect stats from every installed executor. Each
        scalar stat is also published as a `monitor/<tag>` gauge in the
        profiler counters registry — the single stats path shared with
        the profiler's consumers."""
        if not self.activated:
            return []
        res = []
        for ex in self._live_execs():
            res.extend(self._collect(ex))
        if self.sort:
            res.sort(key=lambda r: r[1])
        for _step, tag, value in res:
            v = np.asarray(value)
            # only scalar numeric stats become gauges; custom stat funcs may
            # return strings/arrays, which stay rows-only
            if v.size == 1 and np.issubdtype(v.dtype, np.number):
                _prof.set_gauge(tag, float(v.reshape(())), domain="monitor")
        self.queue = res
        return res

    def toc_print(self):
        for step, name, value in self.toc():
            logging.info("Batch: %7d %30s %s", step, name, value)
