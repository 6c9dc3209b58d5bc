"""The traffic generator and the cell files: every seed replays one
trace of sizes and due times, Table 4 statistics hold before rounding,
every prompt length is a rung the cell warms, and every workload's files
load."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_tiny  # noqa: F401  (puts the harness on sys.path)
import spec
import traffic

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(chip_tiny.CHIP, "mixes", "*.json")))
# paper Table 4: (input mean, input median, output mean, output median)
TABLE4 = {"sharegpt": (343.76, 148.0, 237.20, 152.0)}


def _draw(cell, seed):
    c = chip_tiny.load(cell)
    return traffic.measured(c.mix, rate=c.rate, seconds=BENCH["run_seconds"],
                            positions=c.params["positions"], seed=seed,
                            vocab=c.config["model"]["vocab_size"])


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests(cell):
    a, b = _draw(cell, 2**33 + 5), _draw(cell, 2**33 + 5)
    assert [(r.arrival_time, r.prompt_len, r.output_len, r.prompt_tokens)
            for r in a] == [(r.arrival_time, r.prompt_len, r.output_len,
                             r.prompt_tokens) for r in b]


@pytest.mark.parametrize("cell", CELLS)
def test_seeds_replay_one_trace(cell):
    a, b = _draw(cell, 1), _draw(cell, 2**31 + 3)
    assert [(r.arrival_time, r.prompt_len, r.output_len) for r in a] == [
        (r.arrival_time, r.prompt_len, r.output_len) for r in b]
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    gaps = np.diff([r.arrival_time for r in a] + [BENCH["run_seconds"]])
    assert np.isclose(gaps.sum(), BENCH["run_seconds"])
    due = [r.arrival_time for r in a]
    assert due[0] == 0.0 and max(due) < BENCH["run_seconds"]


@pytest.mark.parametrize("mix", MIXES)
def test_table4_statistics_before_rounding(mix):
    """Before clipping at the mix's maximum, the lognormal fits have the
    Table 4 median and, within the 5 % that 20,000 stratified quantiles
    leave of a heavy tail, its mean; a normal has the Table 4 mean.
    Clipping then removes part of the tail, as the simulator's generator
    does."""
    m = json.load(open(os.path.join(chip_tiny.CHIP, "mixes", mix + ".json")))
    in_mean, in_med, out_mean, out_med = TABLE4[mix]
    unclipped = {k: dict(m[k], max=1e12) for k in ("input", "output")}
    x_in = traffic.quantile_lengths(unclipped["input"], 20000)
    x_out = traffic.quantile_lengths(unclipped["output"], 20000)
    if m["input"]["kind"] == "normal":
        assert abs(x_in.mean() / in_mean - 1) < 0.01
    else:
        assert abs(np.median(x_in) / in_med - 1) < 0.01
        assert abs(x_in.mean() / in_mean - 1) < 0.05
    assert abs(np.median(x_out) / out_med - 1) < 0.01
    assert abs(x_out.mean() / out_mean - 1) < 0.05


def test_normal_lengths_hold_their_mean():
    """The normal kind, for inputs whose mean lies below their median:
    Table 4's LongBench input mean, sd 15 % of it."""
    x = traffic.quantile_lengths(
        {"kind": "normal", "mean": 2686.89, "sd": 403.03, "max": 1e12},
        20000)
    assert abs(x.mean() / 2686.89 - 1) < 0.001
    assert abs(x.std() / 403.03 - 1) < 0.01


def test_arrivals_are_found_by_kind_and_unknown_kinds_raise():
    c = spec.load_cell(CELLS[0])
    gen = traffic.rng(c.mix["pairing_seed"], 1)
    due = traffic.arrivals(c.mix, 20, 0.4, 51.0, gen)
    assert len(due) == 20 and due[0] == 0.0 and due[-1] < 51.0
    assert np.all(np.diff(due) > 0)
    for kind in ("mmpp", {"kind": "bursty", "ratio": 4}, "../poisson"):
        with pytest.raises(ValueError):
            traffic.arrivals(dict(c.mix, arrivals=kind), 20, 0.4, 51.0,
                             traffic.rng(0, 1))


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_are_warmed_rungs_that_fit(cell):
    c = chip_tiny.load(cell)
    rungs = traffic.ladder(c.mix)
    reqs = _draw(cell, 99)
    warmed = sorted({r.prompt_len for r in reqs})   # what runner warms
    for r in reqs:
        assert r.prompt_len in rungs and r.prompt_len in warmed
        assert 1 <= r.output_len
        assert r.prompt_len + r.output_len <= c.params["positions"] - 2


@pytest.mark.parametrize("cell", CELLS)
def test_every_workload_loads_its_files(cell):
    import runner

    c = spec.load_cell(cell)
    assert c.config_name in {x["name"] for x in BENCH["configs"]}
    spec.model_config(c.config)
    assert c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    for m in c.per_layer:
        assert callable(runner.load_reader(m["name"]))


def test_config_files_are_the_listed_ones():
    for c in BENCH["configs"]:
        conf = json.load(open(os.path.join(chip_tiny.CHIP, "..", "..",
                                           c["file"])))
        assert conf["reduced"] == c["reduced"] and conf["source"] == c[
            "source"]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(chip_tiny.CHIP, "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(chip_tiny.CHIP, "metrics", "*.py"))
    if not p.endswith("shared_reads.py")))
def test_metric_reader_reads_nothing_from_an_empty_run(name):
    """A reader that finds nothing returns None, never 0."""
    import hooks
    import peaks
    import runner
    from repro.serving.calibration import CalibrationRecorder

    c = spec.load_cell(CELLS[0])
    rec = runner.Records(
        cell=c, cfg=spec.model_config(c.config), seconds=1.0, due=[],
        window_s=1.0, clock=hooks.BenchClock(), events=[],
        recorder=CalibrationRecorder(), trace=None,
        peak=peaks.peaks("TPU v5 lite"))
    got = runner.load_reader(name)(rec)
    assert got is None
