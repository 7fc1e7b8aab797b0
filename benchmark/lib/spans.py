"""The benchmark's own host spans and compile counter.

`Spans` records (label, start, end) on the wall clock (time.time_ns) around
the benchmark's calls into the program; lib/xplane.py shifts them onto the
profiler's clock to say what the host was doing in each idle gap of the
device. `CompileCounter` is chip_smoke.py's, copied: one event per program jax
asks the backend to compile, persistent-cache hits counted apart.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.items: list = []  # (label, start_ns, end_ns)
        self._lock = threading.Lock()

    @contextmanager
    def span(self, label: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.add(label, t0, time.time_ns())

    def add(self, label: str, start_ns: int, end_ns: int) -> None:
        with self._lock:
            self.items.append((label, start_ns, end_ns))


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.requests: list = []  # (fun_name, seconds)
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests.append((kw.get("fun_name", "?"), seconds))

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple:
        return len(self.requests), self.hits

    def since(self, mark: tuple) -> dict:
        new = self.requests[mark[0]:]
        return {
            "requests": len(new),
            "cache_hits": self.hits - mark[1],
            "seconds": round(sum(s for _, s in new), 2),
            "slowest": [[round(s, 2), n] for s, n in sorted(((s, n) for n, s in new), reverse=True)[:5]],
        }
