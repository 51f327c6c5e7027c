"""The program's own spans in a profiler trace: what the host was doing
while the chip sat idle.

The program opens ``dedup.<layer>.<step>`` spans (``src/repro/obs.py``) on
the thread that does the work: the front end's event loop and engine
thread, the cluster's coordinator and its shard workers.  Every Python
thread has its own line in the trace's ``/host:CPU`` plane, and all of
them are named ``python``: ``load`` keeps each apart (``python``,
``python/1``, ...) where ``trace.load_xplane`` pools lines of one name.

``summarize`` reduces the planes (events ``(name, start_ns, duration_ns)``,
with an optional fourth element, the event's stats) to ``ProgramSpans``:

* per span name, the time its spans cover in the window (``total_s``) and
  their self time (``self_s``: the part no child span on the same thread
  covers), summed over threads; the number, whole durations and summed
  stats of the spans that start in the window;
* the device's idle gaps (as ``trace.reduce`` finds them), each labelled by
  the program span whose self time overlaps it most, summed over threads.
  A ``*.wait`` span is chosen only when no other span overlaps the gap;
  where no program span does, the gap keeps ``trace.reduce``'s label;
* ``idle_by_span``: idle seconds by gap label, and
  ``idle_unattributed_s``: idle time no program span on any thread covers.

``of(ctx)`` is what a per-layer reader calls: the summary of the run's own
trace (the harness's ``.bench_trace`` in the checkout), or None where the
trace holds no program span, as a program without them gives.

    python3 bench/spans.py [<trace dir or .xplane.pb>]

prints the summary of a trace as JSON (default: the last run's).
"""

from __future__ import annotations

import glob
import json
import math
import os
import sys
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PREFIX = "dedup."
WAIT_SUFFIX = ".wait"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         ".bench_trace")
_DEVICE_LINES = (trace.OPS_LINE, trace.MODULES_LINE)

Interval = Tuple[float, float]


def _keep(plane: str, line: str, event: str) -> bool:
    if plane.startswith("/host:"):
        return event.startswith(PREFIX) or event.startswith("bench.")
    return line in _DEVICE_LINES


def load(path: str) -> dict:
    """Planes of an ``.xplane.pb``: the device's op and module lines, and on
    the host one line per thread holding its ``bench.`` and ``dedup.``
    events with their stats."""
    from jax.profiler import ProfileData

    out: dict = {}
    with warnings.catch_warnings():
        # the first read of an event's stats builds its type, with a warning
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            host = plane.name.startswith("/host:")
            lines = out.setdefault(plane.name, {})
            for line in plane.lines:
                name = line.name
                if host and name in lines:
                    k = 1
                    while f"{name}/{k}" in lines:
                        k += 1
                    name = f"{name}/{k}"
                if host:
                    evs = [(e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats))
                           for e in line.events if _keep(plane.name, line.name, e.name)]
                else:
                    evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events if _keep(plane.name, line.name, e.name)]
                if evs or not host:
                    lines.setdefault(name, []).extend(evs)
    return out


def newest_trace(trace_dir: Optional[str] = None) -> Optional[str]:
    """The last ``.xplane.pb`` the profiler wrote under ``trace_dir``
    (default ``TRACE_DIR``), as ``common.Profiler.planes`` picks it."""
    paths = sorted(glob.glob(os.path.join(trace_dir or TRACE_DIR, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


@dataclass
class ProgramSpans:
    window_s: float
    busy_s: float
    threads: int = 0
    total_s: Dict[str, float] = field(default_factory=dict)
    self_s: Dict[str, float] = field(default_factory=dict)
    # spans that start in the window: their number, whole durations and stats
    count: Dict[str, int] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    stats: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # (label, seconds) per idle gap of the window, longest first; with
    # several devices each gap counts its share of one device's idle time
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_unattributed_s: float = 0.0

    @property
    def idle_s(self) -> float:
        return sum(g for _, g in self.gaps)

    @property
    def idle_by_span(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for label, g in self.gaps:
            out[label] = out.get(label, 0.0) + g
        return out

    def stat(self, name: str, key: str) -> float:
        """Summed stat ``key`` of the ``name`` spans that start in the window."""
        return self.stats.get(PREFIX + name, {}).get(key, 0.0)

    def mean_s(self, name: str) -> Optional[float]:
        """Mean duration of the ``name`` spans that start in the window."""
        n = self.count.get(PREFIX + name, 0)
        return self.span_s[PREFIX + name] / n if n else None

    def self_of(self, *names: str) -> float:
        return sum(self.self_s.get(PREFIX + n, 0.0) for n in names)

    def breakdown(self, top: int = 10) -> dict:
        return {"idle_gaps": [[n, g] for n, g in self.gaps[:top]],
                "idle_by_span": dict(sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])),
                "idle_unattributed_s": self.idle_unattributed_s,
                "self_s": dict(sorted(self.self_s.items(), key=lambda kv: -kv[1])),
                "total_s": self.total_s, "count": self.count, "stats": self.stats}


def _self_intervals(spans) -> List[Tuple[str, float, float]]:
    """One thread's spans cut into their self parts: each instant belongs to
    the innermost span open there.  Spans of one thread nest; a child that
    outlives its parent is cut at the parent's end."""
    out = []
    stack: List[list] = []
    t = 0.0
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            if end > t:
                out.append((top, t, end))
            t = max(t, end)
        if stack:
            if a > t:
                out.append((stack[-1][0], t, a))
            b = min(b, stack[-1][1])
        stack.append([name, b])
        t = a
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((top, t, end))
        t = max(t, end)
    return out


def _overlaps(gaps: List[Interval], pieces) -> List[Tuple[int, str, float]]:
    """(gap index, name, overlap) of sorted disjoint gaps with sorted
    disjoint named pieces."""
    out = []
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        ga, gb = gaps[i]
        name, a, b = pieces[j]
        lo, hi = max(ga, a), min(gb, b)
        if hi > lo:
            out.append((i, name, hi - lo))
        if b <= gb:
            j += 1
        else:
            i += 1
    return out


def _window(plain: dict) -> Interval:
    """The window as ``trace.reduce`` takes it."""
    win = [(s, s + d) for p, lines in plain.items() if p.startswith("/host:")
           for evs in lines.values() for n, s, d in evs if n == trace.WINDOW_SPAN]
    if win:
        return min(s for s, _ in win), max(e for _, e in win)
    ops = [(s, s + d) for p, lines in plain.items() if p.startswith("/device:")
           for _, s, d in lines.get(trace.OPS_LINE, [])]
    return (min(a for a, _ in ops), max(b for _, b in ops)) if ops else (0.0, 1.0)


def _device_gaps(plain: dict, lo: float, hi: float) -> List[List[Interval]]:
    """Each device's idle gaps in the window, in time order (as
    ``trace.reduce`` finds them)."""
    out = []
    for p, lines in plain.items():
        if p.startswith("/device:") and lines.get(trace.OPS_LINE):
            busy = trace._union([c for _, s, d in lines[trace.OPS_LINE]
                                 if (c := trace._clip(s, s + d, lo, hi))])
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            out.append([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a])
    return out


def summarize(planes: dict) -> ProgramSpans:
    plain = {p: {ln: [e[:3] for e in evs] for ln, evs in lines.items()}
             for p, lines in planes.items()}
    base = trace.reduce(plain)
    lo, hi = _window(plain)
    out = ProgramSpans(window_s=base.window_s, busy_s=base.busy_s)
    devices = _device_gaps(plain, lo, hi)
    by_gap = [[{} for _ in gaps] for gaps in devices]
    covered: List[Interval] = []
    for p, lines in planes.items():
        if not p.startswith("/host:"):
            continue
        for evs in lines.values():
            spans = [(n, s, s + d) for n, s, d, *_ in evs if n.startswith(PREFIX)]
            if not spans:
                continue
            out.threads += 1
            for n, s, d, *st in evs:
                if not n.startswith(PREFIX):
                    continue
                if (c := trace._clip(s, s + d, lo, hi)):
                    out.total_s[n] = out.total_s.get(n, 0.0) + (c[1] - c[0]) * 1e-9
                    covered.append(c)
                if lo <= s < hi:
                    out.count[n] = out.count.get(n, 0) + 1
                    out.span_s[n] = out.span_s.get(n, 0.0) + d * 1e-9
                    acc = out.stats.setdefault(n, {})
                    for k, v in (st[0] if st else {}).items():
                        if isinstance(v, (int, float)):
                            acc[k] = acc.get(k, 0.0) + v
            pieces = [(n, *c) for n, a, b in _self_intervals(spans)
                      if (c := trace._clip(a, b, lo, hi))]
            for n, a, b in pieces:
                out.self_s[n] = out.self_s.get(n, 0.0) + (b - a) * 1e-9
            for gaps, acc in zip(devices, by_gap):
                for i, n, x in _overlaps(gaps, pieces):
                    acc[i][n] = acc[i].get(n, 0.0) + x
    covered = [("", a, b) for a, b in trace._union(covered)]
    n_dev = max(1, len(devices))
    idle_covered = 0.0
    for gaps, acc in zip(devices, by_gap):
        for (a, b), over in zip(gaps, acc):
            busy = {n: x for n, x in over.items() if not n.endswith(WAIT_SUFFIX)} or over
            label = max(busy, key=lambda n: (busy[n], n)) if busy else \
                trace._label(base.host_spans, (a + b) / 2)
            out.gaps.append((label, (b - a) * 1e-9 / n_dev))
        idle_covered += sum(x for _, _, x in _overlaps(gaps, covered)) * 1e-9 / n_dev
    out.gaps.sort(key=lambda g: -g[1])
    out.idle_unattributed_s = out.idle_s - idle_covered
    return out


def of(ctx: dict) -> Optional[ProgramSpans]:
    """The program spans of the run a per-layer reader reads: ``ctx["spans"]``
    where it is set, else the summary of the newest trace under ``TRACE_DIR``
    if it is this run's (same window and busy time as ``ctx["trace"]``),
    kept in ``ctx["spans"]`` for the run's other readers.  None without a
    trace or without program spans."""
    if "spans" not in ctx:
        ctx["spans"] = _run_spans(ctx.get("trace"))
    return ctx["spans"]


def _run_spans(tr) -> Optional[ProgramSpans]:
    path = newest_trace() if tr is not None else None
    if path is None:
        return None
    s = summarize(load(path))
    if not (math.isclose(s.window_s, tr.window_s, rel_tol=1e-9)
            and math.isclose(s.busy_s, tr.busy_s, rel_tol=1e-9, abs_tol=1e-12)):
        return None  # another run's trace
    return s if s.threads else None


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    where = args[0] if args else TRACE_DIR
    path = where if where.endswith(".pb") else newest_trace(where)
    if path is None:
        print(f"no trace under {where}", file=sys.stderr)
        return 1
    s = summarize(load(path))
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s, "threads": s.threads,
                      **s.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
