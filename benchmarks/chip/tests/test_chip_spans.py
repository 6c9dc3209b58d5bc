"""The readers of the program's spans (``spans.py``, ``metrics/``
``pace_idle_share``, ``decode_host_ms``, ``decode_device_ms``,
``admit_ms``): their arithmetic on a trace built by hand, and their
readings of a small trace recorded on a TPU v5e
(``fixtures/span_trace.xplane.pb``, made by ``record_span_fixture.py``)."""
import os
import shutil

import pytest

import chip_tiny
import devtrace
import hooks
import record_span_fixture as fx
import runner
import spans
from repro.serving.calibration import CalibrationRecorder

FIXTURE = os.path.join(chip_tiny.CHIP, "fixtures", "span_trace.xplane.pb")
READERS = ("pace_idle_share", "decode_host_ms", "decode_device_ms",
           "admit_ms")


def records(trace):
    cell = chip_tiny.load("chatglm3-6b.sharegpt")
    return runner.Records(
        cell=cell, cfg=None, seconds=1.0, due=[], window_s=trace.window_s,
        clock=hooks.BenchClock(), events=[], recorder=CalibrationRecorder(),
        trace=trace, peak=None)


def read_all(rec):
    return {name: runner.load_reader(name)(rec) for name in READERS}


def by_hand(programs):
    """A 10-s window on one device: a decode span whose step runs 1-3 s,
    an admission whose second copy starts after its span ended, and a
    pacing sleep over which the device is idle but for the copy's tail."""
    host = {spans.DECODE: [(0.5, 3.5)], spans.ADMIT: [(4.0, 4.2)],
            spans.PACE: [(4.2, 8.2)], spans.WAIT: [(8.2, 10.0)]}
    mods = [(s, e, n) for _, s, e, n in programs]
    trace = devtrace.DeviceTrace(
        window=(0.0, 10.0),
        busy={0: devtrace.union([(s, e) for s, e, _ in mods])},
        modules=sorted(mods), host=host)
    return spans.ProgramSpans(trace=trace, programs=sorted(programs))


PROGRAMS = [(0.6, 1.0, 3.0, spans.DECODE_PROGRAM),
            (4.05, 4.1, 4.3, "jit_scatter"),     # starts inside the span
            (4.1, 4.3, 4.7, "jit_scatter")]      # starts after it ended


def test_readers_by_hand(monkeypatch):
    ps = by_hand(PROGRAMS)
    monkeypatch.setattr(spans, "of", lambda rec: ps)
    got = read_all(records(ps.trace))
    # pace 4.2-8.2 with the device busy 4.2-4.7: 3.5 s idle of 10 s
    assert got["pace_idle_share"] == pytest.approx(35.0)
    # decode span 3 s, the step 2 s of it
    assert got["decode_host_ms"] == pytest.approx(1000.0)
    assert got["decode_device_ms"] == pytest.approx(2000.0)
    # both copies were enqueued in the admission span: 0.2 + 0.4 s
    assert got["admit_ms"] == pytest.approx(600.0)


def test_a_decode_span_holding_two_steps_reads_none(monkeypatch):
    two = PROGRAMS + [(3.0, 3.1, 3.4, spans.DECODE_PROGRAM)]
    ps = by_hand(two)
    monkeypatch.setattr(spans, "of", lambda rec: ps)
    assert runner.load_reader("decode_device_ms")(records(ps.trace)) is None


def test_a_trace_without_the_programs_spans_reads_none(tmp_path,
                                                       monkeypatch):
    """The harness's own recorded trace holds none of the program's spans,
    as a program older than them gives: every reader returns None."""
    shutil.copy(os.path.join(chip_tiny.CHIP, "fixtures",
                             "small_trace.xplane.pb"), tmp_path)
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path)
    rec = records(devtrace.reduce(str(tmp_path / "small_trace.xplane.pb"),
                                  hooks.HOST_SPANS))
    assert read_all(rec) == dict.fromkeys(READERS)
    rec.trace = None                                 # an untraced run
    assert read_all(rec) == dict.fromkeys(READERS)


def test_recorded_span_trace(tmp_path, monkeypatch):
    """Two rounds, each: an 8192^3 bf16 product (1.1 TFLOP, at least
    5.58 ms at 197 TFLOP/s) then 3 ms of host inside ``step.decode``; two
    512-MiB copies (each 1 GiB moved, at least 1.31 ms at 819 GB/s)
    enqueued inside ``step.admit``; 10 ms of ``serve.pace`` and 20 ms of
    ``serve.wait`` with the device idle."""
    shutil.copy(FIXTURE, tmp_path)
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path)
    path = str(tmp_path / os.path.basename(FIXTURE))
    rec = records(devtrace.reduce(path, hooks.HOST_SPANS + spans.NAMES))
    got = read_all(rec)

    pace_s = got["pace_idle_share"] * rec.trace.window_s / 100
    assert fx.ROUNDS * fx.PACE_S <= pace_s < fx.ROUNDS * (fx.PACE_S + 2e-3)
    assert fx.HOST_S * 1e3 <= got["decode_host_ms"] < fx.HOST_S * 1e3 + 2
    step_ms = 2 * fx.N ** 3 / 197e12 * 1e3
    assert step_ms <= got["decode_device_ms"] < 1.5 * step_ms
    copy_ms = 2 * 2 * fx.COPY_SHAPE[0] * fx.COPY_SHAPE[1] / 819e9 * 1e3
    assert 2 * copy_ms <= got["admit_ms"] < 8 * copy_ms

    ps = spans.of(rec)
    assert len(ps.spans(spans.WAIT)) == fx.ROUNDS
    for (s, e), progs in ps.launched(spans.ADMIT):
        assert [p[3] for p in progs] == ["jit_admit_copy"] * 2
        assert progs[-1][1] > e          # the second copy outlasts the span
    for _, progs in ps.launched(spans.DECODE):
        assert [p[3] for p in progs] == [spans.DECODE_PROGRAM]
