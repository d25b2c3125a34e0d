"""The program's own spans and its device ops' scopes, from the traced run.

The program writes spans on the profiler's clock (``jax.profiler``):
``train`` step events (``jax.profiler.StepTraceAnnotation``) holding the
training loop's ``train.loop.*`` phases, the stack's ``lms.*`` layers with
their counts as metadata, and ``marker.*`` regions.  Its step program
carries ``jax.named_scope``s, which reach the device ops' framework op
names.  ``load_events`` reads one ``.xplane.pb`` into plain lists:

- ``window``: ``[start_ns, end_ns]`` of the harness's traced-window span;
- ``spans``: ``[name, start_ns, end_ns, thread, meta]`` of every program
  span (``thread`` is the host line's name and index, ``meta`` a dict);
- ``ops``: ``[name, start_ns, end_ns, scope]`` of the first device's ops
  (the first ``/device:`` plane, by name, with an "XLA Ops" line, as in
  ``trace_reduce``), ``scope`` the outermost of ``SCOPES`` in the op's
  framework op name, or ``None``.

Where the framework op name is: on a TPU v5e trace (jax 0.9) an op event
as ``ProfileData`` gives it carries only ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and its name is the
HLO text without metadata.  The profiler keeps the name as ``tf_op`` in
the op's event metadata, which ``ProfileData`` does not expose; the trace
viewer's ``<host>.trace.json.gz`` that the profiler writes beside the
``.xplane.pb`` carries it as the event's ``tf_op`` argument, keyed by its
``long_name`` (the same HLO text).  ``framework_names`` reads that table.
An executable loaded from JAX's persistent cache carries the op names of
the build that compiled it; the program keys its cache by metadata too
(``repro.launch.compile_cache``), so the names are its own.

The reductions below work on those lists alone, so they can be checked on
hand-built events and on a small recorded trace without JAX.  Device busy
time is given to the innermost op covering each instant (ops nest inside
``while`` loops); idle time is the window less the union of the ops, each
idle instant given to the innermost ``train.loop.*`` phase covering it.

``for_ctx(ctx)`` finds the run's own trace: the newest ``.xplane.pb``
under ``bench.OUT_DIR/*/trace/``, taken only when its traced-window span
is as long as ``ctx["trace"]["window_s"]``; otherwise ``None``.  A trace
of a program without these spans or scopes gives ``None`` from each
reader, never an error.  A program that writes the loop's phases also
scopes its step: in its trace ``for_ctx`` raises when no device op, or no
scoped op, is found (``check``), so the shares cannot drop out of the
result line unseen.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import math
import os
import re

from benchmarks.lms_bench import bench
from benchmarks.lms_bench.trace_reduce import (OP_LINES, WINDOW, _union,
                                                op_name)

PROGRAM_PREFIXES = ("train.", "lms.", "marker.")
STEP = "train"                  # the loop's StepTraceAnnotation
PHASE = "train.loop."
SCOPES = ("embed", "attention", "mlp", "norm", "logits_loss", "optimizer")
_SPLIT = re.compile(r"[/()]")


def scope_of(framework_name):
    """Outermost of ``SCOPES`` among the path parts of a framework op
    name (``jit(f)/transpose(jvp(attention))/dot_general`` -> attention),
    or ``None``."""
    if not framework_name:
        return None
    for part in _SPLIT.split(framework_name):
        if part in SCOPES:
            return part
    return None


def framework_names(xplane_path: str) -> dict:
    """``{HLO text: framework op name}`` of the device ops, from the trace
    viewer's ``<host>.trace.json.gz`` beside ``<host>.xplane.pb`` (``{}``
    where there is none)."""
    path = xplane_path.removesuffix(".xplane.pb") + ".trace.json.gz"
    if not os.path.exists(path):
        return {}
    with gzip.open(path, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    out = {}
    for e in events:
        args = e.get("args") or {}
        if "tf_op" in args and "long_name" in args:
            out[args["long_name"]] = args["tf_op"]
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _meta(stats: dict) -> dict:
    """The span's own metadata: stats without the profiler's internal
    (underscore) keys."""
    return {k: v for k, v in stats.items() if not k.startswith("_")}


def _first_device_ops(planes):
    for plane in sorted((p for p in planes if p.name.startswith("/device:")),
                        key=lambda p: p.name):
        evs = [e for ln in plane.lines if ln.name in OP_LINES
               for e in ln.events]
        if evs:
            return evs
    return []


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, spans, ops = None, [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for e in line.events:
                name = e.name
                s = float(e.start_ns)
                t = s + float(e.duration_ns)
                if name == WINDOW:
                    window = [s, t]
                elif name == STEP or name.startswith(PROGRAM_PREFIXES):
                    spans.append([name, s, t, thread, _meta(_stats(e))])
    names = framework_names(path)
    for e in _first_device_ops(data.planes):
        s = float(e.start_ns)
        ops.append([op_name(e.name), s, s + float(e.duration_ns),
                    scope_of(names.get(e.name))])
    spans.sort(key=lambda x: x[1])
    ops.sort(key=lambda x: x[1])
    return {"window": window, "spans": spans, "ops": ops}


@functools.lru_cache(maxsize=1)
def _load_cached(path: str, mtime: float) -> dict:
    return load_events(path)


def newest_xplane(out_dir=None):
    out_dir = str(out_dir if out_dir is not None else bench.OUT_DIR)
    found = glob.glob(os.path.join(out_dir, "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def for_ctx(ctx, out_dir=None):
    """The events of this run's own trace, or ``None``."""
    trace = ctx.get("trace")
    if not trace:
        return None
    path = newest_xplane(out_dir)
    if path is None:
        return None
    ev = _load_cached(path, os.path.getmtime(path))
    if not window_matches(ev, trace["window_s"]):
        return None
    check(ev)
    return ev


def check(events: dict):
    """Refuse a trace of the program with loop phases in which no device
    op, or no op with a named scope, was found: the readers would then
    give nothing, and their metrics drop out of the result line unseen."""
    if not any(sp[0].startswith(PHASE) for sp in events["spans"]):
        return
    ops = events["ops"]
    if not ops:
        raise ValueError(f"the trace has the loop's {PHASE}* spans but no "
                         f"device op on an {OP_LINES} line")
    if not any(op[3] for op in ops):
        raise ValueError(
            f"the trace has the loop's {PHASE}* spans but none of its "
            f"{len(ops)} device ops carries a named scope in its framework "
            "op name (the trace viewer's tf_op)")


def window_matches(events: dict, window_s: float) -> bool:
    w = events.get("window")
    return w is not None and math.isclose((w[1] - w[0]) * 1e-9, window_s,
                                          rel_tol=1e-9)


# --------------------------------------------------------------------------
# intervals
# --------------------------------------------------------------------------


def innermost(intervals, lo: float, hi: float):
    """``[(start, end, key)]`` that may nest -> non-overlapping pieces
    ``[(start, end, key)]`` inside ``[lo, hi]``, each instant given to the
    innermost interval covering it (the latest started)."""
    out = []

    def emit(a, b, key):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            return
        if out and out[-1][2] == key and out[-1][1] == a:
            out[-1] = (out[-1][0], b, key)
        else:
            out.append((a, b, key))

    stack, t = [], lo           # open intervals as (end, key); time done
    for s, e, key in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, k = stack.pop()
            emit(t, end, k)
            t = max(t, end)
        if stack:
            emit(t, s, stack[-1][1])
        t = max(t, s)
        stack.append((e, key))
    while stack:
        end, k = stack.pop()
        emit(t, end, k)
        t = max(t, end)
    return out


def gaps(busy, lo: float, hi: float):
    out, t = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def overlap(pieces, spans):
    """Sum, per key of ``pieces`` ([(s, e, key)], sorted, disjoint), of
    their overlap with ``spans`` ([(s, e)], sorted, disjoint)."""
    out, j = {}, 0
    for s, e, key in pieces:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            ov = min(e, spans[k][1]) - max(s, spans[k][0])
            if ov > 0:
                out[key] = out.get(key, 0.0) + ov
            k += 1
    return out


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------


def steps_in_window(events: dict) -> int:
    lo, hi = events["window"]
    return sum(1 for n, s, *_ in events["spans"] if n == STEP
               and lo <= s < hi)


def idle_by_phase(events: dict):
    """``({phase or None: idle ns}, steps)`` over the traced window, or
    ``None`` when the trace has no loop phases or no device ops."""
    lo, hi = events["window"]
    phases = [(s, e, n) for n, s, e, *_ in events["spans"]
              if n.startswith(PHASE)]
    steps = steps_in_window(events)
    if not phases or not steps or not events["ops"]:
        return None
    busy = _union([(max(s, lo), min(e, hi)) for _, s, e, _ in events["ops"]
                   if e > lo and s < hi])
    idle = gaps(busy, lo, hi)
    pieces = innermost(phases, lo, hi)
    by_phase = overlap(pieces, idle)
    total = sum(e - s for s, e in idle)
    by_phase[None] = total - sum(by_phase.values())
    return by_phase, steps


def busy_by(events: dict, key):
    """``{key(op): busy ns}``: each busy instant of the window given to
    the innermost op covering it."""
    lo, hi = events["window"]
    ops = events["ops"]
    pieces = innermost([(s, e, i) for i, (_, s, e, _) in enumerate(ops)],
                       lo, hi)
    out = {}
    for s, e, i in pieces:
        k = key(ops[i])
        out[k] = out.get(k, 0.0) + (e - s)
    return out


def busy_by_scope(events: dict):
    """``{scope or None: busy ns}`` (``busy_by`` the op's scope); ``None``
    when no op carries a scope (a program without scopes)."""
    if not any(op[3] for op in events["ops"]):
        return None
    return busy_by(events, lambda op: op[3])


def spans_in_window(events: dict, name: str):
    lo, hi = events["window"]
    return [sp for sp in events["spans"] if sp[0] == name
            and lo <= sp[1] < hi]


# --------------------------------------------------------------------------
# what the per-layer readers share
# --------------------------------------------------------------------------


def idle_ms_per_step(ctx, phases) -> float | None:
    ev = for_ctx(ctx)
    r = ev and idle_by_phase(ev)
    if not r:
        return None
    by_phase, steps = r
    return sum(by_phase.get(p, 0.0) for p in phases) * 1e-6 / steps


def scope_frac(ctx, scope) -> float | None:
    ev = for_ctx(ctx)
    r = ev and busy_by_scope(ev)
    if not r:
        return None
    return r.get(scope, 0.0) / sum(r.values())
