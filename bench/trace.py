"""Reduce a profiler trace to the benchmark's device numbers.

``load_xplane`` turns the JAX profiler's ``.xplane.pb`` into plain planes
(name -> line name -> ``(name, start_ns, duration_ns)`` events); ``reduce``
works on that form only, so a recorded trace can be replayed in the tests.

From the planes:

* the window is the host span ``bench.window`` the benchmark opened and
  closed around its measured window (the whole trace if it is missing);
* busy time is the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:`` plane), clipped to the
  window and averaged over the devices that ran anything;
* module seconds sum each XLA program's executions (line ``XLA Modules``),
  keyed by the program name without its trailing ``(id)``;
* idle gaps are the stretches of the window in which no operation ran; each
  is labelled by the benchmark's host span that covers its midpoint: a
  span of ``SPAN_LABELS`` first, else any other ``bench.<name>`` span (as
  ``<name>``).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# host spans the benchmark opens, most specific first: a gap is labelled by
# the first of these that covers its midpoint
SPAN_LABELS = (
    ("bench.write_batch", "ShardedCluster.write_batch"),
    ("bench.frontend", "front-end submit"),
)
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an operation's name is its whole HLO instruction; the breakdown keys it by
# the instruction name, result type and opcode at its head
OP_NAME_CHARS = 120

Planes = Dict[str, Dict[str, List[Tuple[str, float, float]]]]


def load_xplane(path: str) -> Planes:
    from jax.profiler import ProfileData

    out: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
    return out


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    module_s: Dict[str, float] = field(default_factory=dict)
    op_s: Dict[str, float] = field(default_factory=dict)
    gaps_ns: List[Tuple[float, float]] = field(default_factory=list)
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)
    devices: int = 0

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps_ns, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[_label(self.host_spans, (a + b) / 2), (b - a) * 1e-9]
                              for a, b in gaps]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


_ID = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    return _ID.sub("", name)


def reduce(planes: Planes) -> TraceSummary:
    host = [(n, s, s + d) for p, lines in planes.items() if p.startswith("/host:")
            for evs in lines.values() for n, s, d in evs if n.startswith("bench.")]
    devices = {p: lines for p, lines in planes.items() if p.startswith("/device:")
               and lines.get(OPS_LINE)}
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        spans = [(s, s + d) for lines in devices.values() for _, s, d in lines[OPS_LINE]]
        lo, hi = (min(a for a, _ in spans), max(b for _, b in spans)) if spans else (0.0, 1.0)
    window_ns = hi - lo
    busy_ns = 0.0
    module_ns: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for lines in devices.values():
        busy = _union([c for _, s, d in lines[OPS_LINE] if (c := _clip(s, s + d, lo, hi))])
        busy_ns += sum(b - a for a, b in busy)
        for n, s, d in lines[OPS_LINE]:
            if (c := _clip(s, s + d, lo, hi)):
                key = n[:OP_NAME_CHARS]
                op_ns[key] = op_ns.get(key, 0.0) + c[1] - c[0]
        for n, s, d in lines.get(MODULES_LINE, []):
            if (c := _clip(s, s + d, lo, hi)):
                key = module_name(n)
                module_ns[key] = module_ns.get(key, 0.0) + c[1] - c[0]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n = max(1, len(devices))
    return TraceSummary(
        window_s=window_ns * 1e-9, busy_s=busy_ns * 1e-9 / n,
        module_s={k: v * 1e-9 / n for k, v in module_ns.items()},
        op_s={k: v * 1e-9 / n for k, v in op_ns.items()},
        gaps_ns=gaps, host_spans=host, devices=len(devices))


def _label(host, t: float) -> str:
    for span, label in SPAN_LABELS:
        if any(n == span and s <= t <= e for n, s, e in host):
            return label
    for n, s, e in host:
        if n != WINDOW_SPAN and s <= t <= e:
            return n[len("bench."):]
    return "other host work"
