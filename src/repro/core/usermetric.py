"""libusermetric — application-level monitoring (paper §IV).

A lightweight library that buffers and sends batched messages in the
InfluxDB line protocol.  Default tags can be specified and are added to each
message; besides metric name, value, default tags and time stamp, arbitrary
tags can be supplied (e.g. a thread identifier).

Sinks: an in-process :class:`~repro.core.router.MetricsRouter` or an HTTP
endpoint (``repro.core.httpd.HttpSink``) — the same code path either way,
mirroring how the paper's libusermetric talks to the router over HTTP.
A command-line tool for batch scripts lives in ``usermetric_cli``.
"""

from __future__ import annotations

import socket
import threading
import time
from functools import partial
from typing import Callable, Optional, Union

from jax.profiler import annotate_function

from repro.core.line_protocol import Point, now_ns


class UserMetric:
    """Buffered, batched metric/event emitter with default tags."""

    def __init__(self, sink, *, default_tags: Optional[dict] = None,
                 batch_size: int = 64, flush_interval_s: float = 5.0,
                 hostname: Optional[str] = None,
                 auto_flush_thread: bool = False,
                 max_buffered_points: int = 65536):
        """sink: callable(list[Point]) or an object with .write(points).

        ``max_buffered_points`` bounds the re-buffer kept while the sink
        is failing (e.g. the router endpoint is down): a dead sink drops
        the *oldest* points past the bound instead of growing memory
        forever.
        """
        self._sink = sink.write if hasattr(sink, "write") else sink
        self.default_tags = dict(default_tags or {})
        self.default_tags.setdefault(
            "hostname", hostname or socket.gethostname())
        self.batch_size = batch_size
        self.flush_interval_s = flush_interval_s
        self.max_buffered_points = int(max_buffered_points)
        self._buf: list = []
        self._lock = threading.Lock()
        self._last_flush = time.monotonic()
        self._sent_points = 0
        self._sent_batches = 0
        self._dropped_points = 0
        self._failed_flushes = 0
        self._join_timeouts = 0
        self._stop = threading.Event()
        self._thread = None
        self._markers = None            # lazy MarkerSession (see .markers)
        if auto_flush_thread:
            self._thread = threading.Thread(target=self._flush_loop,
                                            daemon=True)
            self._thread.start()

    # -- emit -----------------------------------------------------------------

    @partial(annotate_function, name="lms.usermetric.metric")
    def metric(self, name: str, value: Union[float, int, dict],
               tags: Optional[dict] = None, ts: Optional[int] = None):
        """Numeric metric; ``value`` may be a dict of field -> value."""
        fields = value if isinstance(value, dict) else {"value": value}
        fields = {k: (float(v) if not isinstance(v, (bool, int, str))
                      else v) for k, v in fields.items()}
        self._push(Point(name, self._tags(tags), fields,
                         ts if ts is not None else now_ns()))

    def event(self, name: str, text: str, tags: Optional[dict] = None,
              ts: Optional[int] = None):
        """String-valued event (paper Fig. 3 start/end markers)."""
        self._push(Point(name, self._tags(tags), {"event": text},
                         ts if ts is not None else now_ns()))

    @property
    def markers(self):
        """Lazy per-emitter marker session (``repro.core.marker``): exact
        nested/concurrent region accounting emitted through this
        UserMetric as the ``marker`` measurement."""
        with self._lock:
            mk = self._markers
        if mk is None:
            from repro.core.marker import MarkerSession
            mk = MarkerSession(self)
            with self._lock:
                if self._markers is None:
                    self._markers = mk
                mk = self._markers
        return mk

    def region(self, name: str, tags: Optional[dict] = None):
        """Context manager timing a code region.

        Routed through the marker subsystem (exact call counts and
        inclusive/exclusive time under nesting and reentrancy — the old
        inline implementation allocated a throwaway class per call and
        only emitted a duration); the legacy per-call ``<name>_time_s``
        point is still emitted for backward compatibility.
        """
        um = self
        inner = self.markers.region(name)

        class _Region:
            def __enter__(self):
                inner.__enter__()
                return self

            def __exit__(self, *exc):
                inner.__exit__(*exc)
                self.seconds = inner.seconds
                um.metric(f"{name}_time_s", inner.seconds, tags)
                return False
        return _Region()

    # -- buffering --------------------------------------------------------------

    def _tags(self, tags):
        out = dict(self.default_tags)
        if tags:
            out.update(tags)
        return out

    def _push(self, p: Point):
        flush_now = False
        with self._lock:
            self._buf.append(p)
            if len(self._buf) >= self.batch_size or \
                    time.monotonic() - self._last_flush \
                    >= self.flush_interval_s:
                flush_now = True
        if flush_now:
            # implicit flush: a failing sink must never crash the
            # monitored application's metric()/event() call — failures
            # are counted and the points re-buffered (bounded) instead
            self._flush(raise_errors=False)

    @partial(annotate_function, name="lms.usermetric.flush")
    def flush(self):
        """Explicit flush: sink failures re-buffer AND raise, so batch
        scripts that call ``flush()``/``close()`` see the error.  Pending
        marker-region deltas are drained into the buffer first."""
        with self._lock:
            mk = self._markers
        if mk is not None:
            mk.flush()
        self._flush(raise_errors=True)

    def _flush(self, raise_errors: bool):
        with self._lock:
            buf, self._buf = self._buf, []
            self._last_flush = time.monotonic()
        if not buf:
            return
        try:
            self._sink(buf)
        except Exception:
            # re-buffer at the front (bounded) so a transient sink
            # failure loses nothing and a dead sink can't grow memory
            # forever
            with self._lock:
                self._failed_flushes += 1
                self._buf[:0] = buf
                excess = len(self._buf) - self.max_buffered_points
                if excess > 0:
                    del self._buf[:excess]
                    self._dropped_points += excess
            if raise_errors:
                raise
            return
        with self._lock:
            self._sent_points += len(buf)
            self._sent_batches += 1

    def _flush_loop(self):
        while not self._stop.wait(self.flush_interval_s):
            self._flush(raise_errors=False)     # retry next interval

    def close(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2 * self.flush_interval_s)
            if self._thread.is_alive():
                # a flusher stuck in a hung sink outlives us; count it
                # so callers reading .stats can tell
                with self._lock:
                    self._join_timeouts += 1
        self.flush()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @property
    def stats(self) -> dict:
        with self._lock:
            return {"sent_points": self._sent_points,
                    "sent_batches": self._sent_batches,
                    "dropped_points": self._dropped_points,
                    "failed_flushes": self._failed_flushes,
                    "join_timeouts": self._join_timeouts,
                    "buffered": len(self._buf)}
