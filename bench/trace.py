"""From a profiler trace to device busy time, kernel time and idle gaps.

The traced part of a window is the host span ``bench.window``.  Device
operations are the events of each TPU plane's ``XLA Ops`` line, each with
the ``XLA Modules`` event (the jitted program) that holds it.  Busy time is
the union of the operations' intervals; idle gaps are the rest of the
window, each named by the innermost benchmark span the host was in.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

HOST_PREFIXES = ("bench.", "embed.", "s1.", "s2.", "s3.", "s4.")


@dataclasses.dataclass
class Trace:
    ops: List[List[Tuple[float, float, str, str]]]  # per device: s, e, op, module
    host: List[Tuple[float, float, str]]            # benchmark spans

    def spans(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for s, e, n in self.host if n == name]

    @property
    def window(self) -> Tuple[float, float]:
        (w,) = self.spans("bench.window")
        return w


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    return paths[-1]


def _module_of(ops, modules):
    """Attach to each op the module whose interval holds its start."""
    modules = sorted(modules)
    starts = np.array([m[0] for m in modules])
    out = []
    for s, e, name in ops:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        mod = modules[i][2] if i >= 0 and modules[i][1] >= s else ""
        out.append((s, e, name, mod))
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` that holds a TPU's operations; a trace with
    none (no TPU plane with an ``XLA Ops`` line) raises, so that a device
    metric can never read a missing source as 0."""
    trace = read_planes(path)
    if not any(trace.ops):
        raise ValueError(f"{path}: no TPU plane with device operations")
    if len(trace.spans("bench.window")) != 1:
        raise ValueError(f"{path}: no single bench.window span")
    return trace


def read_planes(path: str) -> Trace:
    """The device operations and benchmark spans of an ``.xplane.pb``;
    times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            dev_ops = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                       for e in lines["XLA Ops"].events]
            mods = ([(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                     for e in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [])
            ops.append(_module_of(dev_ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return Trace(ops=ops, host=host)


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a, b) -> float:
    """Total length of the intersection of two unions of intervals."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def busy(trace: Trace, device: int = 0) -> List[Tuple[float, float]]:
    w0, w1 = trace.window
    return union((max(s, w0), min(e, w1)) for s, e, _, _ in trace.ops[device]
                 if e > w0 and s < w1)


def busy_seconds(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the traced chips."""
    if not trace.ops:
        return 0.0
    return float(np.mean([sum(e - s for s, e in busy(trace, d))
                          for d in range(len(trace.ops))]))


def idle_share(trace: Trace, during: str = "bench.batch") -> Optional[float]:
    """Percent of the host spans ``during`` in which no device op ran."""
    w0, w1 = trace.window
    spans = union((max(s, w0), min(e, w1)) for s, e in trace.spans(during)
                  if e > w0 and s < w1)
    total = sum(e - s for s, e in spans)
    if not total or not trace.ops:
        return None
    return 100.0 * (1.0 - overlap(spans, busy(trace)) / total)


def kernel_seconds(trace: Trace, pattern: str) -> float:
    """Device seconds of ops whose name or module matches ``pattern``."""
    w0, w1 = trace.window
    rx = re.compile(pattern)
    return float(sum(min(e, w1) - max(s, w0) for dev in trace.ops
                     for s, e, op, mod in dev
                     if e > w0 and s < w1 and (rx.search(op) or
                                               rx.search(mod))))


def _module_name(mod: str) -> str:
    return re.sub(r"\(\d+\)$", "", mod) or "(no module)"


def _host_segments(trace: Trace):
    """The window cut at every benchmark span's start and end, each piece
    named by the innermost span over it (spans of one thread nest, so the
    innermost is the open one that started last)."""
    w0, w1 = trace.window
    spans = [(s, e, n) for s, e, n in trace.host if n != "bench.window"]
    points = sorted({w0, w1, *(t for s, e, _ in spans for t in (s, e)
                                if w0 < t < w1)})
    by_start = sorted(spans)
    out, open_, k = [], [], 0
    for a, b in zip(points[:-1], points[1:]):
        while k < len(by_start) and by_start[k][0] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        label = (max(open_)[2] if open_ else "waiting for a request")
        out.append((a, b, label))
    return out


def _label_gaps(gaps, segments):
    """Split each idle gap at the host segments' bounds; yield (piece,
    label).  Gaps under 10 us are counted under one label."""
    j = 0
    for s, e in gaps:
        if e - s < 1e-5:
            yield (s, e), "gaps under 10 us"
            continue
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, label = segments[k]
            yield (max(s, a), min(e, b)), label
            k += 1


def breakdown(trace: Trace, n: int = 10) -> Dict[str, list]:
    """The device programs that took most time (the seconds in which any
    of a program's operations ran: an operation nested in another, as a
    loop's body in its ``while``, counts once), and idle time by what the
    host was doing (the innermost benchmark span over each piece of a
    gap)."""
    w0, w1 = trace.window
    spans_of: Dict[str, list] = {}
    for s, e, _, mod in (trace.ops[0] if trace.ops else []):
        if e > w0 and s < w1:
            spans_of.setdefault(_module_name(mod), []).append(
                (max(s, w0), min(e, w1)))
    by_mod = {k: sum(e - s for s, e in union(v))
              for k, v in spans_of.items()}
    gaps, last = [], w0
    for s, e in (busy(trace) if trace.ops else []):
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    if last < w1:
        gaps.append((last, w1))
    by_host: Dict[str, float] = {}
    for (s, e), label in _label_gaps(gaps, _host_segments(trace)):
        by_host[label] = by_host.get(label, 0.0) + (e - s)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    return {"device_ops": top(by_mod), "idle_gaps": top(by_host)}
