"""The program's own spans in a traced run's profiler trace, for the
per-layer readers that read them.

The served path writes, beside the harness's spans (``hooks.HOST_SPANS``):
``serve.pace`` and ``serve.wait``, the event loop's sleeps while a
scheduled slot waits for its predicted end and while nothing is
scheduled (``repro.serving.replay``); ``step.prefill``, ``step.admit`` and
``step.decode``, the engine's steps (``repro.serving.engine``).  A program
that has none of them (an older one) gives every reader None.

The run's ``.xplane.pb`` is read a second time: over these names with
``devtrace.reduce`` as it is, and once more for the time the host
enqueued each device program (the host's ``DoEnqueueProgram`` event that
carries the program's ``run_id``).  A program is charged to the span it
was enqueued in: programs queued back to back can start on the device
after the span that launched them has ended, and the device's clock in
a v5e trace reads up to about a millisecond early against the host's,
so neither a program's start nor its midpoint says whose it is.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace

PACE, WAIT = "serve.pace", "serve.wait"
PREFILL, ADMIT, DECODE = "step.prefill", "step.admit", "step.decode"
NAMES = (PACE, WAIT, PREFILL, ADMIT, DECODE)
DECODE_PROGRAM = "jit_decode_step"
ENQUEUE = "DoEnqueueProgram"
Program = Tuple[float, float, float, str]   # (enqueued, start, end, name)


@dataclasses.dataclass
class ProgramSpans:
    trace: devtrace.DeviceTrace     # reduced over NAMES
    programs: List[Program]         # device programs the host enqueued,
    #                                 in the order of their enqueue times

    def spans(self, name: str) -> List[devtrace.Interval]:
        return sorted(self.trace.host.get(name, ()))

    def launched(self, name: str
                 ) -> List[Tuple[devtrace.Interval, List[Program]]]:
        """Each ``name`` span with the programs enqueued inside it."""
        keys = [p[0] for p in self.programs]
        return [((s, e), self.programs[bisect.bisect_left(keys, s):
                                       bisect.bisect_right(keys, e)])
                for s, e in self.spans(name)]

    def idle_inside(self, name: str) -> List[float]:
        """For each ``name`` span, the seconds of it in which the device
        ran no program, mean over devices."""
        busy = self.trace.busy
        if not busy:
            return []
        ends = {dev: [e for _, e in iv] for dev, iv in busy.items()}
        out = []
        for span in self.spans(name):
            idle = 0.0
            for dev, iv in busy.items():
                ran = 0.0
                for b in iv[bisect.bisect_right(ends[dev], span[0]):]:
                    if b[0] >= span[1]:
                        break
                    ran += devtrace.overlap(span, b)
                idle += span[1] - span[0] - ran
            out.append(idle / len(busy))
        return out


def read(path: str, devices: Optional[Sequence[int]] = None
         ) -> ProgramSpans:
    """Reduce one trace file over the program's spans; ``devices`` limits
    the device planes read, as ``devtrace.reduce`` does."""
    from jax.profiler import ProfileData

    trace = devtrace.reduce(path, NAMES, devices=devices)
    enqueued: Dict[int, float] = {}
    ran: List[Tuple[int, float, float, str]] = []
    for plane in ProfileData.from_file(path).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m and (devices is None or int(m.group(1)) in devices):
            for line in plane.lines:
                if line.name != devtrace.MODULES_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ran.append((dict(ev.stats).get("run_id"), s,
                                s + ev.duration_ns * 1e-9, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name != ENQUEUE:
                        continue
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None and rid not in enqueued:
                        enqueued[rid] = ev.start_ns * 1e-9
    programs = sorted((enqueued[rid], s, e, devtrace.program_name(name))
                      for rid, s, e, name in ran if rid in enqueued)
    return ProgramSpans(trace=trace, programs=programs)


@functools.lru_cache(maxsize=1)
def _read_once(path: str, devices: Tuple[int, ...]) -> ProgramSpans:
    return read(path, devices)


def of(rec) -> Optional[ProgramSpans]:
    """The traced run's program spans, read once for all the readers; None
    in an untraced run, and where the trace holds none of them or no
    device."""
    import runner

    if rec.trace is None:
        return None
    path = devtrace.find_xplane(str(runner.TRACE_DIR))
    if path is None:
        return None
    got = _read_once(path, tuple(sorted(rec.trace.busy)))
    return got if got.trace.host and got.trace.busy else None


def median_ms(xs: List[float]) -> Optional[float]:
    return statistics.median(xs) * 1e3 if xs else None
