"""Shared arithmetic of the per-layer readers.  Each reader is a file
``metrics/<metric>.py`` with ``read(rec) -> float | None`` (``rec`` is a
``runner.Records``); None leaves the metric out of the result line."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import hooks  # noqa: E402


def p90(xs):
    return float(np.percentile(np.asarray(xs, float), 90)) if len(xs) else None


def slot_events(rec, kind):
    """(t, request ids) of the Tracer's slot events of one kind."""
    out = []
    for e in rec.events:
        if e[0] == "slot" and e[3] == kind:
            out.append((e[1], [getattr(r, "rid", r) for r in e[5]]))
    return out


def prefill_steps(rec):
    """Device seconds of every program inside the traced prefill spans, and
    the prompt lengths those spans prefilled (the trace starts with the
    window and may stop before it ends, so the spans it holds are the first
    calls).  None where the trace holds no prefill."""
    if rec.trace is None:
        return None
    n = len(rec.trace.host.get(hooks.PREFILL, ()))
    lens = [t for call in rec.prefills[:n] for t in call]
    dev = rec.trace.span_seconds(hooks.PREFILL)
    if not lens or dev <= 0:
        return None
    return dev, lens
