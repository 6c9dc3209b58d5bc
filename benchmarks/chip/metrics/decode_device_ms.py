"""Model step: median device time of the decode program
(``jit_decode_step``, matched by name), one per ``step.decode`` span that
enqueued it, in ms; None where a span enqueued two."""
import spans


def read(rec):
    ps = spans.of(rec)
    if ps is None:
        return None
    times = []
    for _, progs in ps.launched(spans.DECODE):
        steps = [e - s for _, s, e, n in progs if n == spans.DECODE_PROGRAM]
        if len(steps) > 1:
            return None
        times += steps
    return spans.median_ms(times)
