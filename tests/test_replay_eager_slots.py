"""The served event loop runs a slot's work when the slot starts and
completes the slot at the later of its predicted end and the clock's
time when the work returned: it sleeps (a ``serve.pace`` span) only while
the prediction outlasts the work, and never stamps a token before the
host had it.

The clock here is virtual, but a slot's work on it takes a set cost
``w`` against a model that predicts ``P`` for every slot."""
import pytest

from repro.core.request import Request
from repro.core.slo import SLO
from repro.obs.events import Tracer
from repro.serving.padg_server import PaDGServer
from repro.serving.replay import SlotConfig, VirtualClock
from repro.simulator.cost_model import FittedExecutor

SLO_SET = SLO(ttft=5.0, tpot=0.5)
B, S = 4, 128
P = 0.01                  # every slot's predicted duration
LATE = 5.0                # a last arrival, after the first ones are served
# no cost, work inside the prediction, work past it
W_CASES = pytest.mark.parametrize("w", [0.0, P / 2, 2 * P],
                                  ids=["free", "inside", "past"])


def flat_model() -> FittedExecutor:
    return FittedExecutor(prefill_base=P, prefill_per_token=0.0,
                          decode_base=P, decode_per_seq=0.0,
                          kv_capacity=B * S)


class WorkClock(VirtualClock):
    """A virtual clock on which a slot's work takes ``cost`` seconds."""

    def __init__(self, cost: float):
        super().__init__()
        self.cost = cost

    def work(self) -> None:
        self._now += self.cost


class TimedBackend:
    """Runs each slot on the wrapped backend at the clock's cost; notes
    every call's (kind, start, return, events traced before it) and, per
    request, the clock's time when each of its tokens came back to the
    host."""

    def __init__(self, inner, clock, requests, calls, token_times,
                 events=()):
        self._inner, self._clock = inner, clock
        self._requests, self.calls = requests, calls
        self.token_times, self._events = token_times, events

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _run(self, kind, fn, reqs):
        start, mark = self._clock.now(), len(self._events)
        out = fn(reqs)
        self._clock.work()
        end = self._clock.now()
        self.calls.append((kind, start, end, mark))
        for r in self._requests:
            seen = self.token_times.setdefault(r.rid, [])
            seen.extend([end] * (len(r.generated or ()) - len(seen)))
        return out

    def run_prefill(self, reqs):
        return self._run("prefill", self._inner.run_prefill, reqs)

    def run_decode(self, reqs):
        return self._run("decode", self._inner.run_decode, reqs)


def requests():
    """Five requests at once (one of them done at its prefill), then one
    more when the instance has long been idle."""
    lens = [(8, 1), (5, 4), (12, 2), (3, 6), (9, 3)]
    out = [Request(rid=i, arrival_time=0.0, prompt_len=p, output_len=o)
           for i, (p, o) in enumerate(lens)]
    out.append(Request(rid=len(out), arrival_time=LATE, prompt_len=6,
                       output_len=3))
    return out


def serve(reqs, clock, n_instances=1, timed=True, tracer=None,
          record_decisions=False):
    server = PaDGServer(None, n_instances=n_instances, slo=SLO_SET,
                        econf=SlotConfig(max_batch=B, max_seq_len=S),
                        backend="fake", executor=flat_model())
    calls, token_times = [], {}
    if timed:
        for inst in server.instances:
            inst.engine = TimedBackend(
                inst.engine, clock, reqs, calls, token_times,
                tracer.events if tracer is not None else ())
    try:
        stats = server.serve(reqs, clock=clock, tracer=tracer,
                             record_decisions=record_decisions)
    finally:
        server.shutdown()
    return stats, calls, token_times


def traced(w):
    reqs, trc = requests(), Tracer()
    _, calls, token_times = serve(reqs, WorkClock(w), tracer=trc)
    slots = [e for e in trc.events if e[0] == "slot"]
    paces = [e for e in trc.events if e[0] == "span" and e[2] == "serve.pace"]
    return reqs, trc.events, slots, paces, calls, token_times


# --------------------------------------------------------------------- #
@W_CASES
def test_backend_runs_when_its_slot_starts(w):
    reqs, events, slots, _, calls, _ = traced(w)
    assert len(calls) == len(slots) > len(reqs)
    for slot, (c_kind, start, end, mark) in zip(slots, calls):
        _, t_slot, _, kind, *_ = slot
        assert (c_kind, start) == (kind, t_slot)
        assert end == pytest.approx(t_slot + w)
        # the slot's scheduling is the last thing traced before its work:
        # no sleep came between
        assert events[mark - 1] is slot
    # every token the scheduler counted, and no more, is on the request
    assert all(len(r.generated) == r.output_len for r in reqs)


@W_CASES
def test_pacing_sleeps_what_the_prediction_has_left(w):
    _, _, slots, paces, _, _ = traced(w)
    assert all(e[4] == pytest.approx(P) for e in slots)
    if w < P:
        # one sleep per slot, from the work's return to the predicted end
        assert len(paces) == len(slots)
        for (_, t_slot, iid, kind, dur, *_), (_, t, _, d, st) in zip(slots,
                                                                     paces):
            assert st == {"iid": iid, "kind": kind}
            assert t == pytest.approx(t_slot + w)
            assert d == pytest.approx(P - w)
            assert t + d == pytest.approx(t_slot + dur)
    else:
        assert paces == []
    # back to back while there is work: one slot every max(P, w)
    starts = [e[1] for e in slots if e[1] < LATE]
    assert len(starts) > 3
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    assert gaps == pytest.approx([max(P, w)] * len(gaps))


@pytest.mark.parametrize("n_instances", [1, 2])
@W_CASES
def test_token_times_are_never_early(w, n_instances):
    reqs = requests()
    _, _, token_times = serve(reqs, WorkClock(w), n_instances=n_instances)
    for r in reqs:
        got = token_times[r.rid]
        assert len(got) == r.output_len
        assert r.first_token_time >= got[0]
        if r.output_len > 1:
            assert r.second_token_time >= got[1]
        assert r.finish_time >= got[-1]


@pytest.mark.parametrize("n_instances", [1, 2])
def test_free_work_replays_the_plain_virtual_clock(n_instances):
    def run(clock, timed):
        reqs = requests()
        stats, _, _ = serve(reqs, clock, n_instances=n_instances,
                            timed=timed, record_decisions=True)
        return (stats.decisions,
                sorted((r.rid, r.finish_time, r.first_token_time,
                        tuple(r.generated)) for r in reqs))

    assert run(WorkClock(0.0), True) == run(VirtualClock(), False)
