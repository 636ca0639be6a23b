"""Reduction of a profiler trace to device busy time, idle gaps and
per-program device time.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
three lists of ``[name, start_ns, duration_ns]``:

  ops       events on each TPU device plane's "XLA Ops" line
  modules   events on its "XLA Modules" line (one per program run)
  host      the harness's own spans (names starting ``bench.``)

``reduce`` works on those lists alone, so a trimmed recording of them is
enough to test it.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Sequence          # [name, start_ns, duration_ns]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def short_name(name: str) -> str:
    """An HLO op's name and result type: ``%copy.150 = bf16[12,8192,...]``
    (the trace names each op by its whole instruction)."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    return f"{head} = {rest.split('{', 1)[0][:80]}"


def load(path: str) -> Dict[str, list]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, list] = {"ops": [], "modules": [], "host": [], "planes": []}
    for plane in pd.planes:
        lines = list(plane.lines)
        out["planes"].append([plane.name, [ln.name for ln in lines]])
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(ln.name)
                if key:
                    out[key].extend([short_name(e.name), e.start_ns, e.duration_ns]
                                    for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events
                                   if e.name.startswith("bench."))
    return out


def window_of(events: Dict[str, list], span: str = "bench.window") -> Tuple[float, float]:
    """[start, end) in ns of the host span that marks the traced window."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == span]
    if not spans:
        raise ValueError(f"no {span!r} span in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _clip(evs: List[Event], lo: float, hi: float):
    for name, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class _Labeller:
    """Splits a time interval along the harness spans it crosses; those
    spans (other than ``bench.window``) run one after another on one
    thread, so they do not overlap."""

    OUTSIDE = "outside bench spans"

    def __init__(self, host: List[Event]):
        self.spans = sorted((s, s + d, n) for n, s, d in host
                            if n != "bench.window")
        self.starts = [s for s, _, _ in self.spans]

    def __call__(self, a: float, b: float) -> List[Tuple[str, float]]:
        out, t = [], a
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        while t < b and i < len(self.spans):
            s, e, name = self.spans[i]
            if e <= t:
                i += 1
                continue
            if s > t:
                out.append((self.OUTSIDE, min(s, b) - t))
                t = min(s, b)
                continue
            out.append((name, min(e, b) - t))
            t = min(e, b)
            i += 1
        if t < b:
            out.append((self.OUTSIDE, b - t))
        return out


def self_time(ops) -> Dict[str, float]:
    """Device time of each op name less the time of the ops nested inside
    it (a ``while`` holds its loop body's ops on the same line)."""
    own: Dict[str, float] = defaultdict(float)
    stack: list = []                   # [name, end]
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1][0]] -= min(b, stack[-1][1]) - a
        own[name] += b - a
        stack.append([name, b])
    return dict(own)


def reduce(events: Dict[str, list], lo: float, hi: float,
           n_devices: int = 1) -> dict:
    """Busy and idle time of the device(s) in [lo, hi) ns; the idle gaps,
    cut where a harness span begins or ends and labelled with that span,
    longest first; device time by program, and by op less the ops nested
    inside it."""
    ops = list(_clip(events["ops"], lo, hi))
    busy_iv = union((a, b) for _, a, b in ops)
    busy = sum(b - a for a, b in busy_iv) / n_devices
    gaps, t = [], lo
    for a, b in busy_iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    split = _Labeller(events["host"])
    labelled = sorted((piece for a, b in gaps for piece in split(a, b)),
                      key=lambda x: -x[1])
    by_label: Dict[str, float] = defaultdict(float)
    for name, d in labelled:
        by_label[name] += d
    by_op = self_time(ops)
    by_module: Dict[str, float] = defaultdict(float)
    n_module: Dict[str, int] = defaultdict(int)
    for name, a, b in _clip(events["modules"], lo, hi):
        by_module[name] += b - a
        n_module[name] += 1
    window = hi - lo
    return {
        "window_ns": window,
        "busy_ns": busy,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "gaps": labelled,
        "idle_by_span": dict(by_label),
        "ops": dict(by_op),
        "modules": dict(by_module),
        "module_calls": dict(n_module),
    }


def program_time(red: dict, key: str) -> Tuple[float, int]:
    """(device ns, runs) of the programs whose name contains ``key``."""
    ns = sum(v for k, v in red["modules"].items() if key in k)
    n = sum(v for k, v in red["module_calls"].items() if key in k)
    return ns, n


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
