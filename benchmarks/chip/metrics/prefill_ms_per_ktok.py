"""Engine: prefill host time per 1000 prompt tokens (CalibrationRecorder).
The recorded time stops before the asynchronous grow_cache/_merge_slot
copy into the batched cache finishes, so it leaves that copy out."""


def read(rec):
    toks = sum(t for t, _ in rec.recorder.prefill)
    if not toks:
        return None
    return sum(dt for _, dt in rec.recorder.prefill) * 1e3 / (toks / 1e3)
