"""The one traffic generator: reads a mix file and draws a cell's requests.

Every seed replays one trace: the same prompt lengths, output lengths and
due times in the same order (stratified quantiles of the mix's
distributions, paired and ordered by the mix's ``pairing_seed``); the
seed draws the prompt tokens (and, in the runner, the weights).  Tails
over a few tens of requests swing by tens of percent with the order of
arrivals alone, so a seed that reordered them would change the work.

Length statistics are the paper's Table 4 (copied from
``repro.simulator.workload.WORKLOADS``): lognormal fits to (mean, median),
or, for inputs whose mean lies below the median (LongBench), a normal
clipped to [1, max].  Prompts are rounded up to the mix's ladder of
lengths, because the engine compiles one prefill program per distinct
prompt length.  Due times come from the arrival process the mix names
(``arrivals/<kind>.py``).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np

from spec import load_module

WARMUP_RID0 = 1_000_000     # warm-up requests never share a rid with the
#                             measured ones


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); seeds may exceed 32 bits."""
    return np.random.default_rng([seed % 2**64, stream])


def ladder(mix: dict) -> List[int]:
    """Powers of two from ``start`` up to ``double_until``, then steps of
    ``step`` up to ``top``."""
    lad = mix["ladder"]
    rungs, n = [], lad["start"]
    while n <= min(lad["double_until"], lad["top"]):
        rungs.append(n)
        n *= 2
    n = rungs[-1] + lad["step"]
    while n <= lad["top"]:
        rungs.append(n)
        n += lad["step"]
    return rungs


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2)/n of a length distribution,
    unrounded, clipped to [1, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    if dist["kind"] == "lognormal":
        mu = math.log(dist["median"])
        sigma = math.sqrt(max(2.0 * math.log(dist["mean"] / dist["median"]),
                              1e-4))
        x = np.exp(mu + sigma * z)
    elif dist["kind"] == "normal":
        x = dist["mean"] + dist["sd"] * z
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return np.clip(x, 1.0, dist["max"])


def round_up(x: float, rungs: List[int]) -> int:
    for r in rungs:
        if r >= x:
            return r
    return rungs[-1]


def request_count(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def shapes(mix: dict, n: int, positions: int):
    """The fixed multiset of (prompt rung, output length) pairs for n
    requests, before the trace orders them.  Outputs are clipped so the
    prompt plus the output fits a slot of ``positions``."""
    rungs = [r for r in ladder(mix) if r <= positions - 4]
    prompts = [round_up(x, rungs) for x in quantile_lengths(mix["input"], n)]
    outs = quantile_lengths(mix["output"], n)
    outs = outs[rng(mix["pairing_seed"], 0).permutation(n)]
    return [(p, int(min(max(1, round(o)), positions - 2 - p)))
            for p, o in zip(prompts, outs)]


def arrivals(mix: dict, n: int, rate: float, seconds: float,
             gen: np.random.Generator) -> np.ndarray:
    """Due times of n requests in [0, seconds) from the mix's arrival
    process, ``arrivals/<kind>.py`` found by the mix's ``arrivals``: a kind,
    or an object with ``kind`` and the process's parameters.  An unknown
    kind raises ValueError."""
    proc = mix["arrivals"]
    kind, params = (proc, {}) if isinstance(proc, str) else (proc["kind"],
                                                             proc)
    return load_module("arrivals", kind).due_times(n, rate, seconds, gen,
                                                   params)


def measured(mix: dict, *, rate: float, seconds: float, positions: int,
             seed: int, vocab: int):
    """The window's requests: the mix's trace of sizes and due times in
    [0, seconds), with prompt tokens drawn from ``seed``."""
    from repro.core.request import Request

    n = request_count(rate, seconds)
    order = rng(mix["pairing_seed"], 1)
    fixed = shapes(mix, n, positions)
    pairs = [fixed[i] for i in order.permutation(n)]
    due = arrivals(mix, n, rate, seconds, order)
    gen = rng(seed, 0)
    out = []
    for i, ((plen, olen), t) in enumerate(zip(pairs, due)):
        out.append(Request(
            rid=i, arrival_time=float(t), prompt_len=plen, output_len=olen,
            prompt_tokens=gen.integers(2, vocab, plen).tolist()))
    return out


def warmup(rungs: List[int], *, requests: int, output_tokens: int,
           seed: int, vocab: int):
    """A short stream, all due at once, from a seed stream of its own: it
    cycles through the cell's prompt rungs and fills the slots, which
    settles the scheduler's measured step times before the window."""
    from repro.core.request import Request

    gen = rng(seed, 1)
    out = []
    for i in range(requests):
        plen = rungs[i % len(rungs)]
        out.append(Request(
            rid=WARMUP_RID0 + i, arrival_time=0.0, prompt_len=plen,
            output_len=output_tokens,
            prompt_tokens=gen.integers(2, vocab, plen).tolist()))
    return out
