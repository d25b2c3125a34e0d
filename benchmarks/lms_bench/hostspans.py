"""Host spans around the stack's layer entry points (traced runs only).

``install`` wraps a few methods of the monitoring stack at class level.
Each call becomes a ``jax.profiler.TraceAnnotation`` of the same name, so
the trace can say what the host was doing while the device sat idle, and
is timed: total and self time (time not covered by a nested wrapped call)
per thread and name, counted only while ``active`` is set.  ``uninstall``
restores the originals.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict

# span name -> (module, class, method)
TARGETS = {
    "instr:host_agent.collect_step": ("repro.core.host_agent", "HostAgent",
                                      "collect_step"),
    "instr:usermetric.metric": ("repro.core.usermetric", "UserMetric",
                                "metric"),
    "instr:usermetric.flush": ("repro.core.usermetric", "UserMetric",
                               "flush"),
    "instr:marker.flush": ("repro.core.marker", "MarkerSession", "flush"),
    "ingest:router.write": ("repro.core.router", "MetricsRouter", "write"),
    "query:engine.query": ("repro.core.query", "QueryEngine", "query"),
}


class Spans:
    def __init__(self):
        self.active = False
        self.self_s = defaultdict(float)        # (thread ident, name) -> s
        self.total_s = defaultdict(float)
        self.durations = defaultdict(list)      # name -> [s]
        self._local = threading.local()
        self._saved = []

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _wrap(self, name, fn):
        import jax
        spans = self

        def wrapped(*a, **kw):
            st = spans._stack()
            st.append(0.0)              # child time of this frame
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **kw)
            finally:
                d = time.perf_counter() - t0
                child = st.pop()
                if st:
                    st[-1] += d
                if spans.active:
                    key = (threading.get_ident(), name)
                    spans.total_s[key] += d
                    spans.self_s[key] += d - child
                    spans.durations[name].append(d)
        wrapped.__wrapped__ = fn
        return wrapped

    def install(self):
        import importlib
        for name, (mod, cls, meth) in TARGETS.items():
            klass = getattr(importlib.import_module(mod), cls)
            orig = klass.__dict__[meth]
            self._saved.append((klass, meth, orig))
            setattr(klass, meth, self._wrap(name, orig))
        return self

    def install_attr(self, obj, attr: str, name: str):
        """Wrap one attribute of an object or module (a jitted step held
        by an instance, a module-level function) the same way."""
        orig = getattr(obj, attr)
        self._saved.append((obj, attr, orig))
        setattr(obj, attr, self._wrap(name, orig))
        return self

    def uninstall(self):
        for klass, meth, orig in reversed(self._saved):
            setattr(klass, meth, orig)
        self._saved.clear()

    def thread_self_s(self, ident: int, prefix: str) -> float:
        return sum(v for (t, n), v in self.self_s.items()
                   if t == ident and n.startswith(prefix))

    def thread_total_s(self, ident: int, prefix: str) -> float:
        return sum(v for (t, n), v in self.total_s.items()
                   if t == ident and n.startswith(prefix))
