"""Engine: median, over admissions, of the device time of every program
enqueued inside a ``step.admit`` span (the prefill cache grown and copied
into the request's slot of the batched cache, the first token written),
in ms.  Charged by enqueue time: the copy runs as several programs queued
back to back, and the later ones start after the span has ended."""
import spans


def read(rec):
    ps = spans.of(rec)
    if ps is None:
        return None
    return spans.median_ms([sum(e - s for _, s, e, _ in progs)
                            for _, progs in ps.launched(spans.ADMIT)
                            if progs])
