"""Event loop: 90th percentile of how late the loop took each due arrival
in (the harness clock's wake time minus the due time), in ms."""
from shared_reads import p90


def read(rec):
    due = {r.arrival_time for r in rec.due}
    seen, lags = set(), []
    for t, woke in rec.clock.wakes:
        if t in due and t not in seen:
            seen.add(t)
            lags.append(max(0.0, woke - t) * 1e3)
    return p90(lags)
