"""From a profiler trace to device busy time, top device ops and idle gaps.

``load_events`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists: per device, the intervals of its ops (the "XLA Ops" line
of each ``/device:`` plane), and the host spans whose names carry one of
the given prefixes, with their threads.  ``reduce`` works on those lists
alone, so it can be checked on a small recorded trace without JAX.

Busy time is the union of a device's op intervals inside the traced window
(the host span named ``WINDOW``), averaged over the devices.  An idle gap is
a stretch of that window with no op on the first device; each is named by
the host span that overlaps it most, or ``host:other``.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict

WINDOW = "lms_bench:traced_window"
HOST_PREFIXES = ("lms_bench:", "instr:", "ingest:", "query:", "serve:")
OP_LINES = ("XLA Ops",)
TOP = 10
# a stretch between two device ops shorter than this is not an idle gap
MIN_GAP_NS = 1e3


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the trace
    names a device op by its whole HLO text."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str, prefixes=HOST_PREFIXES) -> dict:
    """{"devices": {plane: [[name, start_ns, end_ns], ...]},
        "host": [[name, start_ns, end_ns, thread], ...],
        "lines": {plane: [line names]}}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    for plane in data.planes:
        plane_lines = list(plane.lines)
        lines[plane.name] = [ln.name for ln in plane_lines]
        if plane.name.startswith("/device:"):
            ops = [ln for ln in plane_lines if ln.name in OP_LINES]
            evs = [[op_name(e.name), float(e.start_ns), float(e.start_ns)
                    + float(e.duration_ns)]
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane_lines:
                for e in ln.events:
                    if e.name.startswith(prefixes):
                        host.append([e.name, float(e.start_ns),
                                     float(e.start_ns) + float(e.duration_ns),
                                     ln.name])
    return {"devices": devices, "host": host, "lines": lines}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(events: dict, window: str = WINDOW) -> dict:
    """busy_s, window_s, busy share and the breakdown of the traced window."""
    spans = [h for h in events["host"] if h[0] == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in the trace")
    lo, hi = spans[0][1], spans[0][2]
    if not events["devices"]:
        raise ValueError("no device ops in the trace")
    busy, per_op = [], defaultdict(float)
    first_busy = None
    for plane in sorted(events["devices"]):
        clipped = []
        for name, s, e in events["devices"][plane]:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e))
                per_op[name] += (e - s) / len(events["devices"]) * 1e-9
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    gaps, t = [], lo
    for s, e in first_busy + [[hi, hi]]:
        if s - t >= MIN_GAP_NS:
            gaps.append((t, s))
        t = max(t, e)
    host = [h for h in events["host"] if h[0] != window]
    named = []
    for s, e in gaps:
        best, best_ov = "host:other", 0.0
        for name, hs, he, _ in host:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "busy_share": busy_s / window_s,
            "breakdown": {"device_ops": [[n, s] for n, s in ops],
                          "idle_gaps": named[:TOP]}}


def reduce_dir(trace_dir: str, excerpt_path=None) -> dict:
    events = load_events(find_xplane(trace_dir))
    if excerpt_path is not None:
        save_events(excerpt(events), excerpt_path)
    return reduce(events)


def excerpt(events: dict, span_ns: float = 50e6) -> dict:
    """The events of the first ``span_ns`` of the traced window, with the
    window span cut to that length: a small trace to check ``reduce`` on."""
    win = [h for h in events["host"] if h[0] == WINDOW][0]
    lo, hi = win[1], win[1] + span_ns

    def keep(evs):
        return [e for e in evs if e[2] > lo and e[1] < hi]
    host = [[WINDOW, lo, hi, win[3]]] + [h for h in keep(events["host"])
                                        if h[0] != WINDOW]
    return {"devices": {p: keep(evs) for p, evs in
                        events["devices"].items()},
            "host": host, "lines": events["lines"]}


def save_events(events: dict, path: str):
    with open(path, "w") as f:
        json.dump(events, f)
