"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle
time, device time per XLA program, and idle gaps charged to the host
activity around them.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:<KIND>:<n>``; on each, the ``XLA Ops`` line holds every
operation the device ran and the ``XLA Modules`` line each program
execution; only the latter is read.  Host spans (``hooks.HOST_SPANS``)
are on the host plane's thread lines, on the same clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]          # (start, end), seconds


def program_name(event_name: str) -> str:
    """``jit__decode_step(1234)`` -> ``jit__decode_step``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass
class DeviceTrace:
    window: Interval
    busy: Dict[int, List[Interval]]                 # device id -> union
    modules: List[Tuple[float, float, str]]         # (start, end, program)
    host: Dict[str, List[Interval]]                 # host span -> intervals

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds with a program running on the device, mean over
        devices."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in iv) for iv in self.busy.values()
                   ) / len(self.busy)

    def by_span(self) -> Dict[str, List[Tuple[float, float, str]]]:
        """Program executions grouped by the host span around their
        midpoint (``host.other`` where none is)."""
        spans = sorted((s, e, n) for n, iv in self.host.items()
                       for s, e in iv)
        starts = [s for s, _, _ in spans]
        out: Dict[str, list] = defaultdict(list)
        for s, e, name in self.modules:
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            span = spans[i][2] if i >= 0 and mid <= spans[i][1] else (
                "host.other")
            out[span].append((s, e, name))
        return dict(out)

    def span_seconds(self, span: str) -> float:
        """Device seconds of every program run inside the ``span``s: the
        model step and the copies and scatters around it alike, so that
        work moved from one program to another in the span stays counted.
        The steps' programs carry no stable name (both jitted steps appear
        as ``jit__unknown``), so none is picked out by name."""
        return sum(e - s for s, e, _ in self.by_span().get(span, ()))

    def program_seconds(self) -> Dict[str, float]:
        """Device seconds per program name and host span, for the
        breakdown: ``<program>@<span>``."""
        out: Dict[str, float] = defaultdict(float)
        for span, mods in self.by_span().items():
            for s, e, name in mods:
                out[f"{program_name(name)}@{span}"] += e - s
        return dict(out)

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of each device, summed over devices and divided by
        their number, charged to the host span overlapping each gap most
        (``host.other`` where none does)."""
        spans = sorted((s, e, n) for n, iv in self.host.items()
                       for s, e in iv)
        ends = [e for _, e, _ in spans]
        out: Dict[str, float] = defaultdict(float)
        for iv in self.busy.values():
            edges = [self.window[0]] + [x for b in iv for x in b] + [
                self.window[1]]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e <= s:
                    continue
                best, name = 0.0, "host.other"
                for hs, he, hn in spans[bisect.bisect_right(ends, s):]:
                    if hs >= e:
                        break
                    ov = overlap((s, e), (hs, he))
                    if ov > best:
                        best, name = ov, hn
                out[name] += (e - s) / len(self.busy)
        return dict(out)


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce(path: str, host_spans: Sequence[str],
           devices: Optional[Sequence[int]] = None) -> DeviceTrace:
    """Reduce one trace file; ``devices`` limits the device planes read to
    the chips the cell uses.  Busy time is the union of program
    executions (the ``XLA Modules`` line): per-op events are not read,
    which keeps a long window's reduction to seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy: Dict[int, List[Interval]] = {}
    modules: List[Tuple[float, float, str]] = []
    host: Dict[str, List[Interval]] = defaultdict(list)
    lo, hi = float("inf"), float("-inf")
    wanted = set(host_spans)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                iv = []
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    iv.append((s, e))
                    modules.append((s, e, ev.name))
                busy[dev] = union(iv)
                if iv:
                    lo = min(lo, busy[dev][0][0])
                    hi = max(hi, busy[dev][-1][1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        host[ev.name].append((s, e))
                        lo, hi = min(lo, s), max(hi, e)
    if lo > hi:
        lo = hi = 0.0
    modules.sort()
    return DeviceTrace(window=(lo, hi), busy=busy, modules=modules,
                       host=dict(host))
