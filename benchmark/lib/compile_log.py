"""What jax reports about compilation, through jax.monitoring: every
backend compile request with its seconds, and the persistent cache's hits
and misses. (Copied from chip_smoke.py's _CompileLog, which ran on the
chip in PR 22.)"""
import jax


class CompileLog:
    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.setup = None       # the counts when the measured window opened
        self.closed = None      # ... and when it closed
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_secs(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self):
        return {"requests": self.requests, "seconds": self.seconds,
                "hits": self.hits, "misses": self.misses}

    def open_window(self):
        """Everything counted so far was set-up."""
        self.setup = self.snapshot()

    def close_window(self):
        self.closed = self.snapshot()

    def in_window(self):
        """Compile requests between the window's opening and its close."""
        return self.closed["requests"] - self.setup["requests"]
