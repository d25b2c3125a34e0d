"""The serving window's trace, reduced to the intervals the serve readers
need.

``load`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``) into plain
lists, all on the profiler's clock in nanoseconds:

- ``window``: ``[start, end]`` of the harness's traced-window span;
- ``busy``: the union of the first device's op intervals (the first
  ``/device:`` plane, by name, with an "XLA Ops" line, as in
  ``trace_reduce``);
- ``decode_programs``, ``prefill_programs``: ``[start, end]`` of each run
  of the engine's decode and prefill programs on that device: the events
  of its "XLA Modules" line named ``jit_decode(...)`` and
  ``jit_prefill(...)`` (JAX names a jitted function's module
  ``jit_<function>``, and the engine jits its step functions ``decode``
  and ``prefill``).

The reductions below work on those lists alone, so they can be checked on
hand-built lists without JAX.
"""

from __future__ import annotations

from benchmarks.lms_bench.progtrace import overlap
from benchmarks.lms_bench.trace_reduce import OP_LINES, WINDOW, _union

MODULE_LINES = ("XLA Modules",)
PROGRAMS = {"decode_programs": "jit_decode(", "prefill_programs": "jit_prefill("}


def _first_device(planes):
    """The first ``/device:`` plane, by name, with ops."""
    for plane in sorted((p for p in planes if p.name.startswith("/device:")),
                        key=lambda p: p.name):
        if any(ln.name in OP_LINES and list(ln.events) for ln in plane.lines):
            return plane
    return None


def _intervals(events):
    return [[float(e.start_ns), float(e.start_ns) + float(e.duration_ns)]
            for e in events]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = _intervals([e])[0]
    ops, runs = [], {key: [] for key in PROGRAMS}
    device = _first_device(data.planes)
    if device is not None:
        for line in device.lines:
            if line.name in OP_LINES:
                ops += _intervals(line.events)
            elif line.name in MODULE_LINES:
                for key, prefix in PROGRAMS.items():
                    runs[key] += _intervals(e for e in line.events
                                            if e.name.startswith(prefix))
    return {"window": window, "busy": _union(ops),
            **{key: sorted(v) for key, v in runs.items()}}


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _covered(a, b) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    return overlap([(s, e, 0) for s, e in a], b).get(0, 0.0)


def _in_window(st: dict, key: str):
    lo, hi = st["window"]
    return [iv for iv in st[key] if lo <= iv[0] < hi]


def idle_between_decodes(st: dict):
    """``(device-idle ns between consecutive decode program runs of a
    batch, number of decode runs)`` in the window: the host's per-token
    loop (argmax sync, row loop, next dispatch); a gap holding a prefill
    run lies between batches and is left out.  ``None`` without decode
    runs or device ops."""
    if st["window"] is None:
        return None
    runs = _in_window(st, "decode_programs")
    if not runs or not st["busy"]:
        return None
    lo, hi = st["window"]
    starts = [p[0] for p in st["prefill_programs"]]
    gaps = [(a[1], b[0]) for a, b in zip(runs, runs[1:])
            if b[0] > a[1] and not any(a[1] <= p < b[0] for p in starts)]
    gaps = _clip(gaps, lo, hi)
    busy = _union(_clip(st["busy"], lo, hi))
    idle = sum(e - s for s, e in gaps) - _covered(gaps, busy)
    return idle, len(runs)


def decode_busy(st: dict):
    """``(device-busy ns inside the window's decode program runs, number of
    those runs)``, or ``None`` where the trace shows no decode program."""
    if st["window"] is None:
        return None
    runs = _in_window(st, "decode_programs")
    if not runs or not st["busy"]:
        return None
    lo, hi = st["window"]
    busy = _union(_clip(st["busy"], lo, hi))
    return _covered(_union(_clip(runs, lo, hi)), busy), len(runs)
