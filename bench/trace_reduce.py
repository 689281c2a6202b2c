"""Reduction of a JAX profiler trace to the numbers the metrics read.

What this file knows is the profiler's own layout: each TPU is a plane
named ``/device:TPU:<n>``, whose ``XLA Ops`` line holds one event per
device operation and whose ``XLA Modules`` line holds one event per
execution of a compiled program; host threads live on ``/host:CPU``,
where ``jax.profiler.TraceAnnotation`` spans appear under their names.
The program's own names (its jitted functions, its Pallas kernel) are
kept in the metric files under ``bench/metrics/`` that look for them.

All times here are seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(prof) -> list:
    planes = [p for p in prof.planes if DEVICE_PLANE.match(p.name)]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def line_events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    """(name, start, end) of every event on ``plane``'s line."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                       for e in line.events)
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(prof, n_devices: Optional[int] = None) -> float:
    """Seconds in which some operation ran, averaged over the first
    ``n_devices`` TPU planes (all by default): the union of the ``XLA Ops``
    intervals of each device."""
    planes = device_planes(prof)[:n_devices]
    if not planes:
        return 0.0
    tot = 0.0
    for p in planes:
        tot += sum(e - s for s, e in merge(
            (s, e) for _, s, e in line_events(p, OPS_LINE)))
    return tot / len(planes)


def _matching(prof, line: str, pattern: str) -> List[Tuple[str, float, float]]:
    rx = re.compile(pattern)
    return [ev for p in device_planes(prof) for ev in line_events(p, line)
            if rx.search(ev[0])]


def module_seconds(prof, pattern: str) -> List[float]:
    """Durations of every execution of a program whose module name
    matches ``pattern``, over all TPU planes."""
    return [e - s for _, s, e in _matching(prof, MODULES_LINE, pattern)]


def op_seconds(prof, pattern: str) -> List[float]:
    """Durations of every device operation whose name matches."""
    return [e - s for _, s, e in _matching(prof, OPS_LINE, pattern)]


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,1,2048]{...} fusion(...)`` -> the op's name and
    the start of its result type."""
    name, _, rest = hlo.partition(" = ")
    return f"{name} {rest.split('{')[0].split(' ')[0]}".strip()[:100]


def self_seconds(events: Sequence[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Device seconds per op name, each op's time less the ops nested in
    it (a while loop's event spans its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[list] = []             # [name, start, end, child secs]

    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0.0) + (e - s) - child

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def top_ops(prof, k: int = 10, n_devices: Optional[int] = None
            ) -> List[Tuple[str, float]]:
    """The ``k`` operations with the most device self-seconds (summed over
    their executions), averaged over the first ``n_devices`` TPU planes."""
    planes = device_planes(prof)[:n_devices]
    tot: Dict[str, float] = {}
    for p in planes:
        for name, sec in self_seconds(line_events(p, OPS_LINE)).items():
            key = short_name(name)
            tot[key] = tot.get(key, 0.0) + sec
    n = max(len(planes), 1)
    return sorted(((a, b / n) for a, b in tot.items()),
                  key=lambda x: -x[1])[:k]


def host_spans(prof, names: Sequence[str]) -> List[Tuple[str, float, float]]:
    """TraceAnnotation spans with one of ``names``, on any host line."""
    want = set(names)
    return [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
            for p in prof.planes if p.name == HOST_PLANE
            for line in p.lines for e in line.events if e.name in want]


def idle_gaps(prof, spans: Sequence[Tuple[str, float, float]],
              k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps of the first TPU plane, between the
    first and the last operation it ran, each named by the innermost host
    span that covers the gap's middle ("host" where none does)."""
    planes = device_planes(prof)
    if not planes:
        return []
    busy = merge((s, e) for _, s, e in line_events(planes[0], OPS_LINE))
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "host"
        out.append((name, e - s))
    return out
