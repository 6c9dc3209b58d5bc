"""Scheduler: 90th percentile, over the window's requests, of the time
from the due time to the start of the request's prefill slot (Tracer
``slot`` events), in ms."""
from shared_reads import p90, slot_events


def read(rec):
    due = {r.rid: r.arrival_time for r in rec.due}
    start = {}
    for t, rids in slot_events(rec, "prefill"):
        for rid in rids:
            if rid in due and rid not in start:
                start[rid] = t
    return p90([(t - due[rid]) * 1e3 for rid, t in start.items()])
