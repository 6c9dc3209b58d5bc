"""Model step: the prefills' share of peak compute: operations the traced
prefills need (the architecture module's ``prefill_flops``) over 197
TFLOP/s, divided by the device time of every program inside the traced
prefill spans (the prefill program and the admission copy into the
batched cache), in %."""
from shared_reads import prefill_steps


def read(rec):
    got = prefill_steps(rec)
    if got is None or rec.peak is None:
        return None
    dev, lens = got
    flops = sum(rec.arch.prefill_flops(rec.cfg, t) for t in lens)
    return 100.0 * flops / rec.peak["bf16_flops_per_s"] / dev
