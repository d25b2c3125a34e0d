"""Dashboard viewers: open-loop readers of a live job's board over HTTP.

A board is the panel set the stack's dashboard agent lays out for a job
(``DashboardAgent.build_dashboard``), rebuilt here from the job's own
points: one windowed query per time-series panel whose measurement and
field exist, one per field of each application measurement, and the
per-region roofline panel when marker regions exist.  Each viewer is a
thread that refreshes its board every ``board_refresh_s``, spreading the
board's panel queries evenly over the period; viewers are staggered.  A
query is timed from when it was due, so a late viewer's wait counts.

``expected`` recomputes a panel from raw points with nothing of the
stack: the plain reference for the answers' check.
"""

from __future__ import annotations

import math
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

# DashboardAgent's DEFAULT_ROWS time-series panels, (measurement, field)
TIMESERIES_PANELS = (("hpm", "mfu"), ("hpm", "mem_gb_per_s"),
                     ("hpm", "ici_gb_per_s"), ("hpm", "step_time_s"),
                     ("usermetric", "value"), ("system", "cpu_load_1m"),
                     ("system", "rss_bytes"), ("system", "net_tx_bytes"),
                     ("system", "write_bytes"))
# measurements the agent renders otherwise than as app panels
NOT_APP = {"hpm", "system", "job_event", "marker", "analysis"}
MARKER = "marker"
ROOFLINE_METRICS = ("time_s", "calls", "@ROOFLINE.intensity",
                    "@ROOFLINE.achieved_gflops", "@ROOFLINE.roofline_frac")
# the roofline panel's passthrough columns, which a plain sum recomputes
ROOFLINE_PLAIN = ("time_s", "calls")


@dataclass(frozen=True)
class Panel:
    measurement: str
    fields: tuple               # what the query asks for
    plain: tuple                # fields the reference recomputes
    window_ns: int
    agg: str
    group_by: Optional[str] = None

    def spec_dict(self, jobid: str) -> dict:
        return {"measurement": self.measurement,
                "metrics": [[f, None] for f in self.fields],
                "tags": {"jobid": jobid}, "window_ns": self.window_ns,
                "group_by": self.group_by, "agg": self.agg}


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def build_board(points, *, panel_window_s: int,
                roofline_window_s: int) -> list:
    """Panels for the measurements and fields present in ``points``."""
    fields: dict = {}
    for p in points:
        fields.setdefault(p.measurement, set()).update(
            k for k, v in p.fields.items() if _numeric(v))
    w = int(panel_window_s * 1e9)
    board = [Panel(m, (f,), (f,), w, "mean")
             for m, f in TIMESERIES_PANELS if f in fields.get(m, ())]
    for m in sorted(set(fields) - NOT_APP):
        board += [Panel(m, (f,), (f,), w, "mean")
                  for f in sorted(fields[m])]
    if MARKER in fields:
        board.append(Panel(MARKER, ROOFLINE_METRICS, ROOFLINE_PLAIN,
                           int(roofline_window_s * 1e9), "sum", "region"))
    return board


# --------------------------------------------------------------------------
# viewers
# --------------------------------------------------------------------------


@dataclass
class Answer:
    reader: int
    panel: int
    due: float                  # monotonic
    sent_wall_ns: int
    latency_s: Optional[float] = None
    result: Optional[dict] = None
    error: Optional[str] = None


@dataclass
class Readers:
    """``count`` viewer threads over one board until ``stop``."""

    url: str
    jobid: str
    board: list
    count: int
    refresh_s: float
    seed: int
    answers: list = field(default_factory=list)

    def __post_init__(self):
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._threads: list = []
        self.deadline: Optional[float] = None

    def start(self, t0: float):
        from repro.core import HttpQueryClient, QuerySpec
        specs = [QuerySpec.from_dict(p.spec_dict(self.jobid))
                 for p in self.board]
        gap = self.refresh_s / len(self.board)
        rng = random.Random(self.seed)
        for i in range(self.count):
            order = list(range(len(self.board)))
            rng.shuffle(order)
            offset = i * self.refresh_s / self.count
            t = threading.Thread(
                target=self._run, name=f"lms-bench-reader-{i}",
                args=(i, HttpQueryClient(self.url, timeout_s=60.0), specs,
                      order, t0 + offset, gap), daemon=True)
            self._threads.append(t)
        for t in self._threads:
            t.start()

    def _run(self, i, client, specs, order, first_due, gap):
        k = 0
        while True:
            due = first_due + k * gap
            if self.deadline is not None and due >= self.deadline:
                return
            now = time.monotonic()
            if due > now and self._stop.wait(due - now) and \
                    due > time.monotonic():
                return              # stopped before this query was due
            panel = order[k % len(order)]
            a = Answer(i, panel, due, time.time_ns())
            try:
                a.result = client.query(specs[panel]).to_dict()
                a.latency_s = time.monotonic() - due
            except Exception as e:      # noqa: BLE001 - counted as failed
                a.error = f"{type(e).__name__}: {e}"
            with self._lock:
                self.answers.append(a)
            k += 1

    def stop(self, deadline: float, grace_s: float):
        """No query due at or after ``deadline`` is sent; waits for the
        ones in flight."""
        self.deadline = deadline
        self._stop.set()
        end = time.monotonic() + grace_s
        for t in self._threads:
            t.join(max(0.0, end - time.monotonic()))
        return [t.name for t in self._threads if t.is_alive()]


# --------------------------------------------------------------------------
# plain recomputation
# --------------------------------------------------------------------------


def expected(points, panel: Panel, upto_ns: int) -> dict:
    """{group: {field: {window_start: value}}} over windows that ended by
    ``upto_ns``, from raw points alone."""
    w = panel.window_ns
    acc: dict = {}
    for p in points:
        if p.measurement != panel.measurement or p.timestamp is None:
            continue
        w0 = p.timestamp - p.timestamp % w
        if w0 + w > upto_ns:
            continue
        g = p.tags.get(panel.group_by, "") if panel.group_by else ""
        for f in panel.plain:
            v = p.fields.get(f)
            if _numeric(v):
                s = acc.setdefault(g, {}).setdefault(f, {}).setdefault(
                    w0, [0.0, 0])
                s[0] += float(v)
                s[1] += 1
    out: dict = {}
    for g, fs in acc.items():
        for f, wins in fs.items():
            out.setdefault(g, {})[f] = {
                w0: (s / n if panel.agg == "mean" else s)
                for w0, (s, n) in wins.items()}
    return out


def answer_gap(points, panel: Panel, answer: Answer, margin_ns: int) -> float:
    """Largest relative gap between the answer and the recomputation over
    the windows that had closed ``margin_ns`` before the query was sent;
    a closed window that one side has and the other lacks reads inf."""
    upto = answer.sent_wall_ns - margin_ns
    want = expected(points, panel, upto)
    got = answer.result["groups"]
    worst = 0.0
    for g in set(want) | {g for g in got if panel.group_by or g == ""}:
        for f in panel.plain:
            exp = want.get(g, {}).get(f, {})
            col = got.get(g, {}).get(f, {"times": [], "values": []})
            have = {t: v for t, v in zip(col["times"], col["values"])
                    if t + panel.window_ns <= upto}
            if set(have) != set(exp):
                return math.inf
            for w0, e in exp.items():
                gap = abs(have[w0] - e) / max(abs(e), 1e-300)
                worst = max(worst, gap)
    return worst
