"""Event loop: device-idle seconds inside the program's ``serve.pace``
spans (the loop sleeping until a scheduled slot's predicted end, before
the slot's work runs) over the traced window, in %."""
import spans


def read(rec):
    ps = spans.of(rec)
    if ps is None or not ps.spans(spans.PACE):
        return None
    return 100.0 * sum(ps.idle_inside(spans.PACE)) / rec.trace.window_s
