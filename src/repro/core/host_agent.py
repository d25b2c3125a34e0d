"""Host agent — per-node metric collection (paper §III.A).

Gathers (a) system-level metrics from the OS (CPU load, RSS, I/O counters —
the things Diamond/Ganglia collected in the paper's setup) and (b) the
TPU/XLA-derived HPM events described in DESIGN.md §2 (FLOPs, bytes,
collective traffic per step from the compiled artifact, plus step
wall-times).  Raw events go through the LIKWID-style performance groups to
produce derived metrics, and everything is emitted to the router with the
mandatory ``hostname`` tag.

On a real multi-host pod slice each process runs one agent (hostname =
worker name); single-process simulations can run several agents with
synthetic hostnames — that is what the straggler tests do.
"""

from __future__ import annotations

import os
import resource
import socket
import threading
import time
from functools import partial
from typing import Optional

from jax.profiler import annotate_function

from repro.core.line_protocol import Point, now_ns
from repro.core.perf_groups import derive_all


def _read_proc_io() -> dict:
    try:
        out = {}
        with open("/proc/self/io") as f:
            for line in f:
                k, _, v = line.partition(":")
                out[k.strip()] = int(v)
        return {"read_bytes": out.get("read_bytes", 0),
                "write_bytes": out.get("write_bytes", 0)}
    except OSError:
        return {"read_bytes": 0, "write_bytes": 0}


def _read_net_dev(path: str = "/proc/net/dev") -> dict:
    try:
        rx = tx = 0
        with open(path) as f:
            for line in f.readlines()[2:]:
                name, _, rest = line.partition(":")
                if name.strip() == "lo":
                    continue
                # guard per line: a malformed/truncated row (seen on
                # exotic kernels and in torn sysfs reads) must not kill
                # the whole collection tick — skip it (without partial
                # sums) and keep counting the remaining interfaces
                try:
                    cols = rest.split()
                    row_rx, row_tx = int(cols[0]), int(cols[8])
                except (ValueError, IndexError):
                    continue
                rx += row_rx
                tx += row_tx
        return {"net_rx_bytes": rx, "net_tx_bytes": tx}
    except OSError:
        return {"net_rx_bytes": 0, "net_tx_bytes": 0}


class HostAgent:
    """Collects system + XLA-HPM metrics for one (possibly simulated) host."""

    def __init__(self, router, hostname: Optional[str] = None,
                 device_constants: Optional[dict] = None,
                 batch_size: int = 1,
                 max_pending_points: int = 65536):
        self.router = router
        self.hostname = hostname or socket.gethostname()
        # static per-step facts from the compiled artifact (set once after
        # compile): hlo_flops, hlo_bytes, collective_bytes, model_flops,
        # tokens_per_step, hbm_bytes_in_use
        self.step_constants = dict(device_constants or {})
        # previous cumulative-counter sample + its monotonic clock, for
        # the per-interval rate fields (see RATE_FIELDS)
        self._last_sys: Optional[dict] = None
        self._last_t = time.monotonic()
        # >1: buffer points and hand the router whole batches (paper §III.A
        # batched transmission); 1 keeps the historical emit-per-call path
        # so live analyzers see every point immediately
        self.batch_size = max(int(batch_size), 1)
        # points waiting for the next batch, plus any re-buffered after a
        # failed send (bounded: a dead router drops the oldest points
        # past max_pending_points instead of growing memory forever)
        self.max_pending_points = int(max_pending_points)
        # guards the emit buffer + failure counters: collection ticks,
        # explicit flush() callers and __exit__ may run on different
        # threads (the straggler tests drive several agents at once)
        self._lock = threading.Lock()
        self._pending: list = []
        self._failed_flushes = 0
        self._dropped_points = 0

    # -- compiled-artifact facts ------------------------------------------------

    def set_step_constants(self, **kwargs):
        self.step_constants.update(kwargs)

    # -- system metrics (Diamond/Ganglia analogue) -------------------------------

    # cumulative counter field -> the per-interval rate field derived from
    # consecutive samples; cpu seconds become fractions of the wall
    # interval (1.0 = one core fully busy)
    RATE_FIELDS = {
        "cpu_user_s": "cpu_user_frac",
        "cpu_sys_s": "cpu_sys_frac",
        "read_bytes": "read_bytes_per_s",
        "write_bytes": "write_bytes_per_s",
        "net_rx_bytes": "net_rx_bytes_per_s",
        "net_tx_bytes": "net_tx_bytes_per_s",
    }

    def _rate_fields(self, counters: dict, now_m: float) -> dict:
        """Per-interval rates from consecutive cumulative-counter samples.

        A negative delta means the counter reset underneath us (process
        restart feeding the same hostname, kernel counter wrap): that
        field's rate is skipped for this interval and the new value
        becomes the baseline — a reset must never emit a huge negative
        (or wrapped-positive) rate.
        """
        prev, dt = self._last_sys, now_m - self._last_t
        out = {}
        if prev is not None and dt > 0:
            for k, rate_name in self.RATE_FIELDS.items():
                cur, last = counters.get(k), prev.get(k)
                if cur is None or last is None:
                    continue
                delta = cur - last
                if delta < 0:           # counter reset -> skip, re-baseline
                    continue
                out[rate_name] = delta / dt
        self._last_sys = counters
        self._last_t = now_m
        return out

    def collect_system(self) -> Point:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        try:
            load1, load5, load15 = os.getloadavg()
        except OSError:
            load1 = load5 = load15 = 0.0
        fields = {
            "cpu_load_1m": load1,
            "cpu_user_s": ru.ru_utime,
            "cpu_sys_s": ru.ru_stime,
            "rss_bytes": ru.ru_maxrss * 1024,
            **{k: float(v) for k, v in _read_proc_io().items()},
            **{k: float(v) for k, v in _read_net_dev().items()},
        }
        counters = {k: fields[k] for k in self.RATE_FIELDS if k in fields}
        fields.update(self._rate_fields(counters, time.monotonic()))
        return Point("system", {"hostname": self.hostname}, fields, now_ns())

    # -- per-step HPM ------------------------------------------------------------

    @partial(annotate_function, name="lms.agent.collect_step")
    def collect_step(self, *, step: int, step_time_s: float,
                     extra_events: Optional[dict] = None,
                     emit: bool = True, ts: Optional[int] = None) -> dict:
        """Build raw events for one step, derive groups, emit to router.

        Returns the derived metrics dict (also used by the live analyzers).
        ``ts`` overrides the point timestamp (simulated hosts in tests).
        """
        raw = dict(self.step_constants)
        raw["step_time_s"] = max(step_time_s, 1e-9)
        raw["step"] = step
        if extra_events:
            raw.update(extra_events)
        derived = derive_all(raw)
        if emit:
            fields = {"step": step, "step_time_s": step_time_s}
            fields.update({k: float(v) for k, v in derived.items()})
            if extra_events:
                fields.update({k: float(v) for k, v in extra_events.items()
                               if k not in fields})
            self._emit(Point("hpm", {"hostname": self.hostname},
                             fields, ts if ts is not None
                             else now_ns()))
        return derived

    def emit_system(self):
        self._emit(self.collect_system())

    # -- batched emission --------------------------------------------------------

    def _emit(self, point: Point):
        with self._lock:
            self._pending.append(point)
            full = len(self._pending) >= self.batch_size
        if full:
            # implicit flush: a down router/sink must never crash the
            # collection tick — the failure is counted, the points are
            # re-buffered (bounded) and retried on the next emit
            self._flush(raise_errors=False)

    def flush(self):
        """Send any buffered points as one batch.  Explicit flushes
        re-buffer AND raise on a failing sink."""
        self._flush(raise_errors=True)

    def _flush(self, raise_errors: bool):
        with self._lock:
            if not self._pending:
                return
            pending, self._pending = self._pending, []
        try:
            # sink call outside the lock: a slow router must not stall
            # concurrent collection ticks
            self.router.write(pending)
        except Exception:
            with self._lock:
                self._failed_flushes += 1
                self._pending[:0] = pending
                excess = len(self._pending) - self.max_pending_points
                if excess > 0:
                    del self._pending[:excess]
                    self._dropped_points += excess
            if raise_errors:
                raise

    @property
    def emit_stats(self) -> dict:
        with self._lock:
            return {"pending": len(self._pending),
                    "failed_flushes": self._failed_flushes,
                    "dropped_points": self._dropped_points}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.flush()
        return False
