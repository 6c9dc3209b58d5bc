"""Engine: median decode step on the host clock (CalibrationRecorder; the
step ends in a host sync on the sampled tokens), in ms."""
import numpy as np


def read(rec):
    dts = [dt for _, _, dt in rec.recorder.decode]
    return float(np.median(dts)) * 1e3 if dts else None
