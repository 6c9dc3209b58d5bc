"""Mitosis scaling: expansion/split, contraction/merge (Fig. 7 semantics)
and the serializable InstanceHandler proxy."""
import pickle

import pytest

from repro.core.instance import Instance
from repro.core.mitosis import InstanceHandler, OverallScheduler, \
    StaleHandlerError, register_instance, registry_size, unregister_instance
from repro.core.slo import SLO


class Exec:
    def prefill_time(self, lens):
        return 1e-4 * sum(lens)

    def decode_time(self, b, c):
        return 0.02


def make_inst(i):
    inst = Instance(i, Exec(), kv_capacity_tokens=10_000)
    register_instance(inst)
    return inst


def make_sched(n_l=3, n_u=6):
    return OverallScheduler(SLO(1.0, 0.1), lambda n: 1e-4 * n,
                            n_lower=n_l, n_upper=n_u)


def test_expansion_splits_at_upper_bound():
    """Fig. 7 steps 1-4 with N_l=3, N_u=6."""
    s = make_sched()
    for i in range(6):
        s.add_instance(make_inst(i))
    assert s.sizes() == [6]
    # 7th instance: split off a new macro with N_l instances
    s.add_instance(make_inst(6))
    assert s.sizes() == [3, 4]
    # further instances fill the fullest non-full macro first (step 3)
    s.add_instance(make_inst(7))
    assert s.sizes() == [3, 5]
    for i in range(8, 10):
        s.add_instance(make_inst(i))
    assert s.sizes() == [4, 6]


def test_contraction_merges_at_upper_bound():
    """Fig. 7 steps 5-8: shrink smallest to N_l, then a full one; merge
    when the two smallest jointly hold N_u."""
    s = make_sched()
    for i in range(10):
        s.add_instance(make_inst(i))
    assert s.sizes() == [4, 6]
    removed = s.remove_instance()       # smallest (4) -> 3 == N_l
    assert removed is not None
    assert s.sizes() == [3, 6]
    s.remove_instance()                 # smallest at N_l -> shrink the full
    assert s.sizes() == [3, 5]
    s.remove_instance()                 # 3 + 4 <= N_u == 6? no: 7 > 6
    assert s.sizes() == [3, 4]
    s.remove_instance()                 # now 3+3 = 6 <= N_u -> merge
    assert s.sizes() == [6]
    assert len(s.macros) == 1


def test_total_instances_preserved_through_split_and_merge():
    s = make_sched()
    for i in range(13):
        s.add_instance(make_inst(i))
    assert s.total_instances == 13
    for _ in range(5):
        s.remove_instance()
    assert s.total_instances == 8


def test_instance_handler_pickle_roundtrip_resolves_same_object():
    inst = make_inst(777)
    h = InstanceHandler.for_instance(inst, address="node3:7011", tp=4)
    blob = h.serialize()
    assert isinstance(blob, bytes)
    h2 = InstanceHandler.deserialize(blob)
    assert h2.actor_id == 777
    assert h2.worker_address == "node3:7011"
    assert h2.capabilities == {"tp": 4}
    # logical migration: the proxy resolves to the SAME running instance,
    # no re-initialization
    assert h2.resolve() is inst


def test_migration_records_fast():
    s = make_sched()
    for i in range(7):          # forces one split -> migrations recorded
        s.add_instance(make_inst(100 + i))
    assert s.migrations
    for m in s.migrations:
        assert m.seconds < 0.1   # paper: <100 ms; pickle is microseconds


def test_registry_does_not_leak_through_scale_churn():
    """Regression for the actor-registry leak: contraction/merge used to
    leave retired instances registered forever, so repeated scale churn
    grew ``_ACTOR_REGISTRY`` without bound.  Churn must return the
    registry exactly to its pre-churn size."""
    baseline = registry_size()
    s = make_sched()
    for cycle in range(3):
        for i in range(7):          # crosses the split threshold
            s.add_instance(make_inst(1000 + cycle * 10 + i))
        assert registry_size() == baseline + 7
        for _ in range(7):          # crosses the merge threshold back
            assert s.remove_instance() is not None
        assert registry_size() == baseline, f"leak on cycle {cycle}"
    assert s.total_instances == 0


def test_discard_instance_unregisters_named_victim():
    """Fault teardown removes a *specific* instance (not the contraction
    heuristic's pick) and must unregister it too."""
    baseline = registry_size()
    s = make_sched()
    insts = [make_inst(2000 + i) for i in range(4)]
    for inst in insts:
        s.add_instance(inst)
    victim = insts[2]
    assert s.discard_instance(victim)
    assert registry_size() == baseline + 3
    assert s.total_instances == 3
    assert not s.discard_instance(victim)    # already gone: no double-pop


def test_stale_handler_resolve_raises_clear_error():
    s = make_sched()
    inst = make_inst(3000)
    s.add_instance(inst)
    h = InstanceHandler.for_instance(inst)
    blob = h.serialize()
    s.discard_instance(inst)                 # unregisters the actor
    with pytest.raises(StaleHandlerError, match="3000"):
        InstanceHandler.deserialize(blob).resolve()


def test_dead_instance_handler_resolve_raises():
    """A handler to a registered-but-dead instance (crashed mid-decode)
    must not resolve: migrating work onto a corpse corrupts state."""
    inst = make_inst(3001)
    h = InstanceHandler.for_instance(inst)
    inst.alive = False
    try:
        with pytest.raises(StaleHandlerError):
            h.resolve()
    finally:
        # the registry is process-global: a dead entry left here fails
        # the fault tests' no-dead-actor check in the same process
        unregister_instance(inst)


def test_migration_does_not_interrupt_execution():
    """An instance keeps its in-flight work across a handler migration."""
    from repro.core.request import Request
    s = make_sched()
    insts = [make_inst(200 + i) for i in range(6)]
    for inst in insts:
        s.add_instance(inst)
    victim = insts[0]
    victim.admit(Request(rid=1, arrival_time=0, prompt_len=50,
                         output_len=5), 0.0)
    kind, dur, batch = victim.next_slot(0.0)
    assert kind == "prefill"
    s.add_instance(make_inst(299))          # triggers split + migration
    # the in-flight slot completes untouched
    victim.complete_slot(kind, batch, dur)
    assert victim.decoding or victim._finished
