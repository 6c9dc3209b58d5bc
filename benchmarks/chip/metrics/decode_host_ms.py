"""Engine: median, over the program's ``step.decode`` spans, of the span's
wall time in which the device ran no program (the host's share of a
decode step: inputs, dispatch, the token sync, the token feedback), in
ms."""
import spans


def read(rec):
    ps = spans.of(rec)
    return None if ps is None else spans.median_ms(
        ps.idle_inside(spans.DECODE))
