"""The harness's own probes around the program: a wall clock that logs how
late the event loop woke, an engine-backend wrapper that names the host's
work in traced runs, and a counter of compilations.

In traced runs the host's activity is written into the profiler trace as
``event_loop.sleep``, ``engine.prefill`` and ``engine.decode`` spans, so
an idle stretch of the device can be charged to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

import jax

from repro.serving.replay import WallClock

SLEEP, PREFILL, DECODE = "event_loop.sleep", "engine.prefill", "engine.decode"
HOST_SPANS = (SLEEP, PREFILL, DECODE)


def _span(name: str, on: bool):
    return (jax.profiler.TraceAnnotation(name) if on
            else contextlib.nullcontext())


class BenchClock(WallClock):
    """``WallClock`` that records, for every wait, the timeline time it
    waited for and the time it woke: an arrival's lag is the difference."""

    def __init__(self, annotate: bool = False):
        super().__init__(1.0)
        self.annotate = annotate
        self.started_at = None          # perf_counter at the window's start
        self.wakes = []                 # (target, woke)
        self.on_start = None            # called as the window starts
        self.on_stop = None             # called once at the first wake
        self.stop_at = float("inf")     # past this timeline time

    def start(self) -> None:
        if self.on_start is not None:
            self.on_start()
        super().start()
        self.started_at = self._t0

    def sleep_until(self, t: float) -> None:
        with _span(SLEEP, self.annotate):
            super().sleep_until(t)
        woke = self.now()
        self.wakes.append((t, woke))
        if self.on_stop is not None and woke >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        """Run ``on_stop`` once.  The timeline skips the time it takes (the
        profiler writing its trace, tens of seconds), so that a request
        queued meanwhile is not charged for the harness's own work."""
        if self.on_stop is not None:
            fn, self.on_stop = self.on_stop, None
            t = time.perf_counter()
            fn()
            if self._t0 is not None:
                self._t0 += time.perf_counter() - t


class AnnotatedBackend:
    """Duck-typed wrapper of an instance's engine backend whose slot work
    appears as host spans in the profiler trace.  Each prefill call's
    prompt lengths are appended to ``prefills``, in the order of the
    calls, which is the order of the spans."""

    def __init__(self, inner, prefills: list):
        self._inner = inner
        self._prefills = prefills

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def run_prefill(self, reqs):
        self._prefills.append([len(r.prompt_tokens) for r in reqs])
        with _span(PREFILL, True):
            return self._inner.run_prefill(reqs)

    def run_decode(self, reqs):
        with _span(DECODE, True):
            return self._inner.run_decode(reqs)


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache, while
    armed (``jax.monitoring`` events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOADED = "/jax/compilation_cache/cache_retrieval_time_sec"
    TRACED = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.armed = False
        self.counts = {self.COMPILE: 0, self.LOADED: 0, self.TRACED: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.counts:
            self.counts[event] += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def summary(self) -> dict:
        return {"compiled": self.counts[self.COMPILE],
                "loaded_from_cache": self.counts[self.LOADED],
                "traced": self.counts[self.TRACED]}


def profile_options():
    """Host spans (TraceMe level 2) without the Python function tracer,
    which would record every Python call of the event loop."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    return opts
