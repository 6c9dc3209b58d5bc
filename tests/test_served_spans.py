"""Spans of the served path (``Tracer.span``): the event loop's sleeps
split into ``serve.pace`` and ``serve.wait``, the engine's
``step.prefill`` / ``step.admit`` / ``step.decode``, their JSONL and
Chrome-trace rendering, the off switch, and the named step programs."""
import dataclasses
import json
import os
import subprocess
import sys
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.core.slo import SLO
from repro.models import init_cache, init_params
from repro.obs.events import NULL_SPAN, NULL_TRACER, Tracer
from repro.obs.export import SCHEMA, chrome_trace, read_jsonl, write_jsonl
from repro.serving.engine import EngineConfig, serving_steps
from repro.serving.padg_server import PaDGServer
from repro.serving.replay import SlotConfig, VirtualClock
from repro.simulator.cost_model import FittedExecutor

SLO_SET = SLO(ttft=5.0, tpot=0.5)
B, S = 4, 128
# the widths the chip harness's CPU tests run at
TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            head_dim=32, d_ff=256, vocab_size=512)


def model() -> FittedExecutor:
    return FittedExecutor(prefill_base=1e-3, prefill_per_token=1e-4,
                          decode_base=5e-3, decode_per_seq=2e-4,
                          kv_capacity=B * S)


class CountingClock(VirtualClock):
    """A virtual clock that adds up how far it was asked to sleep."""

    def __init__(self):
        super().__init__()
        self.slept = 0.0

    def sleep_until(self, t):
        self.slept += max(0.0, t - self.now())
        super().sleep_until(t)


def spans_of(trc, name):
    return [e for e in trc.events if e[0] == "span" and e[2] == name]


def tiny_cfg():
    return dataclasses.replace(get_smoke_config("chatglm3-6b"), **TINY)


def real_server():
    return PaDGServer(tiny_cfg(), n_instances=1, slo=SLO_SET,
                      econf=EngineConfig(max_batch=B, max_seq_len=S,
                                         eos_token=-1),
                      executor=model(), cost_model=model())


def prompted(n=3, gap=0.05, olen=4, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(3, 12))
        out.append(Request(rid=i, arrival_time=gap * i, prompt_len=plen,
                           output_len=olen,
                           prompt_tokens=rng.integers(2, 500, plen).tolist()))
    return out


# --------------------------------------------------------------------- #
def test_null_span_is_shared_and_allocates_nothing():
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b", rid=1) is NULL_SPAN
    with NULL_TRACER.span("warm", rid=0):
        pass
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for i in range(10_000):
            with NULL_TRACER.span("step.decode", batch=i, ctx=i):
                pass
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before < 1_000          # nothing kept per call
    assert NULL_TRACER.events == ()


def test_mirror_only_tracer_keeps_no_spans():
    trc = Tracer(mirror=[], record=False)
    with trc.span("serve.wait"):
        pass
    assert trc.events == []


def test_pace_and_wait_split_every_sleep():
    """Two requests 5 s apart on one fake instance: the loop waits on an
    empty system between the first one's finish and the second arrival,
    and paces every slot in between."""
    server = PaDGServer(None, n_instances=1, slo=SLO_SET,
                        econf=SlotConfig(max_batch=B, max_seq_len=S),
                        backend="fake", executor=model())
    reqs = [Request(rid=0, arrival_time=0.0, prompt_len=8, output_len=4),
            Request(rid=1, arrival_time=5.0, prompt_len=8, output_len=4)]
    trc, clock = Tracer(), CountingClock()
    try:
        server.serve(reqs, clock=clock, tracer=trc)
    finally:
        server.shutdown()
    waits, paces = spans_of(trc, "serve.wait"), spans_of(trc, "serve.pace")
    assert len(waits) == 1
    _, t, _, dur, stats = waits[0]
    assert stats == {}
    assert t == pytest.approx(reqs[0].finish_time)
    assert t + dur == pytest.approx(5.0)
    slots = [e for e in trc.events if e[0] == "slot"]
    # one pacing sleep per slot, ending at the slot's predicted end
    assert len(paces) == len(slots)
    for (_, ts, _, kind, dur_s, *_), (_, tp, _, dp, st) in zip(slots, paces):
        assert st == {"iid": 0, "kind": kind}
        assert tp + dp == pytest.approx(ts + dur_s)
    total = sum(e[3] for e in waits + paces)
    assert total == pytest.approx(clock.slept) and clock.slept > 5.0
    assert [e[2] for e in spans_of(trc, "serve.start")] == ["serve.start"]


def test_engine_steps_are_spans():
    server = real_server()
    reqs = prompted()
    trc = Tracer()
    try:
        server.serve(reqs, clock=VirtualClock(), tracer=trc)
    finally:
        server.shutdown()
    assert trc.annotate is jax.profiler.TraceAnnotation
    pre, adm = spans_of(trc, "step.prefill"), spans_of(trc, "step.admit")
    assert [e[4]["rid"] for e in pre] == [e[4]["rid"] for e in adm]
    assert sorted(e[4]["rid"] for e in pre) == [r.rid for r in reqs]
    assert {e[4]["rid"]: e[4]["tokens"] for e in pre} == {
        r.rid: r.prompt_len for r in reqs}
    assert all(0 <= e[4]["slot"] < B for e in adm)
    decode_slots = [e for e in trc.events if e[0] == "slot"
                    and e[3] == "decode"]
    dec = spans_of(trc, "step.decode")
    assert len(dec) == len(decode_slots) > 0
    assert [e[4]["batch"] for e in dec] == [len(e[5]) for e in decode_slots]
    assert all(e[4]["iid"] == 0 and e[4]["ctx"] > 0 for e in dec)


@pytest.mark.parametrize("backend", ["fake", "real"])
def test_tracing_changes_no_token_and_no_decision(backend):
    def run(traced):
        if backend == "real":
            server = real_server()
        else:
            server = PaDGServer(None, n_instances=1, slo=SLO_SET,
                                econf=SlotConfig(max_batch=B, max_seq_len=S),
                                backend="fake", executor=model())
        reqs = prompted(n=4)
        try:
            stats = server.serve(reqs, clock=VirtualClock(),
                                 record_decisions=True,
                                 tracer=Tracer() if traced else None)
        finally:
            server.shutdown()
        return ({r.rid: list(r.generated) for r in reqs}, stats.decisions)

    assert run(True) == run(False)


def test_step_programs_have_stable_names():
    cfg = tiny_cfg()
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, max_len=S))
    toks = jax.ShapeDtypeStruct((1, 8), np.int32)
    prefill, decode = serving_steps(cfg)
    assert "@jit_prefill_step" in prefill.lower(params, toks).as_text()
    text = decode.lower(params, cache, jax.ShapeDtypeStruct((B, 1), np.int32),
                        jax.ShapeDtypeStruct((B,), np.int32)).as_text()
    assert "@jit_decode_step" in text


def test_span_jsonl_round_trip_and_chrome_trace(tmp_path):
    trc = Tracer()
    ticks = iter([0.0, 0.25, 1.0, 1.5, 2.0, 2.0])
    trc.timeline = lambda: next(ticks)
    with trc.span("serve.pace", iid=1, kind="decode"):
        pass
    with trc.span("serve.wait"):
        pass
    with trc.span("serve.start"):
        pass
    assert trc.events == [
        ("span", 0.0, "serve.pace", 0.25, {"iid": 1, "kind": "decode"}),
        ("span", 1.0, "serve.wait", 0.5, {}),
        ("span", 2.0, "serve.start", 0.0, {})]
    assert all(len(e) == 2 + len(SCHEMA["span"]) for e in trc.events)
    path = tmp_path / "spans.jsonl"
    assert write_jsonl(trc, path) == 3
    events, _ = read_jsonl(path)
    assert events == trc.events
    doc = chrome_trace(events)
    json.dumps(doc)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [(e["name"], e["ts"], e["dur"]) for e in xs] == [
        ("serve.pace", 0.0, 250_000.0), ("serve.wait", 1e6, 500_000.0),
        ("serve.start", 2e6, 0.0)]
    assert xs[0]["tid"] == 1 and xs[0]["args"] == {"iid": 1,
                                                   "kind": "decode"}
    assert xs[1]["tid"] == xs[2]["tid"] != 1     # the control track


def test_span_is_written_even_when_the_body_raises():
    trc = Tracer()
    with pytest.raises(RuntimeError):
        with trc.span("step.decode", batch=1):
            raise RuntimeError("boom")
    assert [e[2] for e in trc.events] == ["step.decode"]


def test_served_path_modules_import_without_jax():
    code = ("import sys\n"
            "import repro.obs, repro.serving.replay\n"
            "import repro.serving.calibration, repro.serving.padg_server\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
