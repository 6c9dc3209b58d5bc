"""Poisson-like arrivals: the n stratified quantiles (i + 1/2)/n of the
exponential gap at the offered rate, scaled to sum to the window, in the
order the trace's generator draws.  Takes no parameters."""
import numpy as np


def due_times(n: int, rate: float, seconds: float,
              gen: np.random.Generator, params: dict) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= seconds / gaps.sum()
    gaps = gaps[gen.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
