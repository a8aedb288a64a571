"""Reduction of a profiler trace to device busy time, per-op time and
idle gaps attributed to the benchmark's host spans.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small list of plain records; ``reduce`` works on those records only, so
the arithmetic is checked on a recorded fixture without a chip.

A record is ``{"kind": "op" | "span", "dev": int, "name": str,
"start_ns": float, "dur_ns": float}``: ``op`` records are the device's
XLA operations (one per line entry of a device plane's "XLA Ops" line,
``dev`` the device ordinal); ``span`` records are the benchmark's own
host annotations, whose names start with ``bench/``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
_OPS_LINE = "XLA Ops"


def load(trace_dir: str, raw: Optional[Dict[str, str]] = None
         ) -> List[dict]:
    """Records of the newest ``.xplane.pb`` under ``trace_dir``.  With a
    dict ``raw``, the full HLO text of each kernel op is kept in it
    under its short name (for reading a trace by hand)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: List[dict] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    name = op_name(ev.name)
                    if raw is not None and "[" in name:
                        raw.setdefault(name, ev.name)
                    out.append({"kind": "op", "dev": dev, "name": name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.append({"kind": "span", "dev": -1,
                                    "name": ev.name,
                                    "start_ns": float(ev.start_ns),
                                    "dur_ns": float(ev.duration_ns)})
    return out


_KERNEL = re.compile(r'kernel_name="?([A-Za-z0-9_.]+)|"name":\s*"([A-Za-z0-9_.]+)"')


def op_name(text: str) -> str:
    """Short name of a device operation from the HLO text the TPU trace
    gives it: the instruction's name, and for a Pallas kernel
    (``tpu_custom_call``) the kernel's name where the text holds one."""
    if not text.startswith("%"):
        return text
    name = text[1:].split(" = ", 1)[0]
    if "tpu_custom_call" in text:
        m = _KERNEL.search(text)
        name += "[" + (next(g for g in m.groups() if g) if m
                       else "tpu_custom_call") + "]"
    return name


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _length(intervals: Sequence[Sequence[float]]) -> float:
    return sum(b - a for a, b in intervals)


def _subtract(base: Sequence[Sequence[float]],
              cut: Sequence[Sequence[float]]) -> List[List[float]]:
    """Parts of the (merged) ``base`` intervals not covered by ``cut``."""
    out: List[List[float]] = []
    j = 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append([cur, cut[k][0]])
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


@dataclass
class Summary:
    window_s: float
    devices: List[int]
    busy_s: Dict[int, float]                   # per device
    op_s: Dict[str, float]                     # op name -> self seconds
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda kv: -kv[1])[:n]]


def reduce(records: Sequence[dict], window: str = WINDOW_SPAN) -> Summary:
    """Busy and per-op seconds and idle gaps inside the last ``window``
    span of ``records``."""
    wins = [r for r in records if r["kind"] == "span" and r["name"] == window]
    if not wins:
        raise ValueError(f"trace holds no {window!r} span")
    w = wins[-1]
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]
    spans = [r for r in records if r["kind"] == "span"
             and r["name"] != window]
    per_dev: Dict[int, List[Tuple[float, float]]] = {}
    op_s: Dict[str, float] = {}
    ops = sorted((r for r in records if r["kind"] == "op"),
                 key=lambda r: (r["dev"], r["start_ns"], -r["dur_ns"]))
    stack: List[list] = []     # open ops of one device: [end, name, self]
    dev_of_stack = None

    def close(item):
        op_s[item[1]] = op_s.get(item[1], 0.0) + item[2] * 1e-9

    for r in ops:
        iv = _clip(r["start_ns"], r["start_ns"] + r["dur_ns"], lo, hi)
        if iv is None:
            continue
        if r["dev"] != dev_of_stack:
            while stack:
                close(stack.pop())
            dev_of_stack = r["dev"]
        while stack and stack[-1][0] <= iv[0]:
            close(stack.pop())
        # an op nested in another (a loop and its body) is the parent's
        # time: charge each op its self time only
        if stack:
            stack[-1][2] -= min(iv[1], stack[-1][0]) - iv[0]
        stack.append([iv[1], r["name"], iv[1] - iv[0]])
        per_dev.setdefault(r["dev"], []).append(iv)
    while stack:
        close(stack.pop())
    busy = {}
    gaps: Dict[str, float] = {}
    for dev, ivs in per_dev.items():
        merged = _union(ivs)
        busy[dev] = _length(merged) * 1e-9
        for a, b in _subtract([[lo, hi]], merged):
            gaps_key = _innermost(spans, (a + b) / 2)
            gaps[gaps_key] = gaps.get(gaps_key, 0.0) + (b - a) * 1e-9
    devices = sorted(per_dev)
    n_dev = max(len(devices), 1)
    return Summary(
        window_s=(hi - lo) * 1e-9, devices=devices, busy_s=busy,
        op_s=op_s,
        gaps=[(k, v / n_dev) for k, v in gaps.items()])


def _innermost(spans: Sequence[dict], t: float) -> str:
    """Name of the shortest benchmark span covering time ``t``."""
    best: Optional[dict] = None
    for s in spans:
        if s["start_ns"] <= t <= s["start_ns"] + s["dur_ns"]:
            if best is None or s["dur_ns"] < best["dur_ns"]:
                best = s
    return best["name"] if best is not None else "outside any span"
