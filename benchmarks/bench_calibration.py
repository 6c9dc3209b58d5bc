"""Sim-to-real calibration bench: replay the checked-in Azure/BurstGPT
trace excerpts through the PaDG server, record per-op step timings, fit
cost-model constants, and report prediction error before vs after.

Two backends:

* **fake** (default, deterministic): the replay runs on the
  ``FakeEngine`` under a ``VirtualClock``; 'measured' timings come from
  a ``SyntheticTruth`` — an affine warp of the analytic roofline model —
  so the fit has a known target and the resulting
  ``CalibrationReport`` is reproducible enough to pin with the
  tolerance-banded golden at ``tests/golden/calibration_report.json``.
  The bench asserts the acceptance claim: fitted constants reduce the
  median per-op prediction error vs the unfitted analytic model.
* **--real**: the same trace excerpt drives live jax ``ServingEngine``
  instances wall-clock on a tiny CPU config; timings are genuinely
  measured, so this row is NOT golden-pinned (CI runs it non-gating).

The saved report feeds the runner's calibrated-executor axis::

    ExperimentRunner(..., calibration=(None, "path/to/report.json"))

    PYTHONPATH=src python -m benchmarks.bench_calibration --smoke \
        --stream rows.jsonl             # deterministic CI cell
    PYTHONPATH=src python -m benchmarks.bench_calibration --real --smoke
    PYTHONPATH=src python -m benchmarks.bench_calibration --write-golden
"""
from __future__ import annotations

import json
import pathlib
import time

from benchmarks.common import emit
from repro.core.slo import SLO
from repro.serving.calibration import (CalibrationRecorder,
                                       CalibrationReport, SyntheticTruth)
from repro.serving.padg_server import PaDGServer
from repro.serving.replay import (SlotConfig, VirtualClock, WallClock,
                                  requests_from_trace)
from repro.simulator.cost_model import GPU_L20, InstanceCostModel
from repro.traces import load_fixture, normalize_rate

GOLDEN_PATH = (pathlib.Path(__file__).resolve().parent.parent
               / "tests" / "golden" / "calibration_report.json")

# the deterministic golden cell: both excerpts, rate-normalized so the
# replay finishes quickly under the virtual clock
FIXTURE_RATE = 10.0
PER_FIXTURE_LIMIT = 20
MAX_PROMPT, MAX_OUTPUT = 120, 12
SLOT = SlotConfig(max_batch=4, max_seq_len=160)
SERVE_SLO = SLO(ttft=2.0, tpot=0.2)

# the synthetic ground truth the fake backend 'measures': an affine warp
# of the analytic model (faster decode, slower prefill, small offsets)
TRUTH_WARP = dict(prefill_scale=1.4, prefill_offset=3e-4,
                  decode_scale=0.75, decode_offset=2e-4)


def trace_requests():
    records = []
    for name in ("azure", "burstgpt"):
        recs = normalize_rate(load_fixture(name), FIXTURE_RATE)
        records.extend(recs[:PER_FIXTURE_LIMIT])
    return requests_from_trace(records, max_prompt=MAX_PROMPT,
                               max_output=MAX_OUTPUT, seed=0)


def analytic_model() -> InstanceCostModel:
    from repro.configs import get_config
    return InstanceCostModel(cfg=get_config("llama-30b"), hw=GPU_L20, tp=4)


def build_report(backend: str = "fake") -> CalibrationReport:
    model = analytic_model()
    rec = CalibrationRecorder()
    if backend == "fake":
        truth = SyntheticTruth(base=model, **TRUTH_WARP)
        server = PaDGServer(None, n_instances=2, slo=SERVE_SLO, econf=SLOT,
                            backend="fake", executor=model, recorder=rec,
                            true_model=truth)
        reqs = trace_requests()
        stats = server.serve(reqs, clock=VirtualClock())
        server.shutdown()
        meta = {"backend": "fake", "truth": TRUTH_WARP,
                "fixtures": ["azure", "burstgpt"],
                "rate": FIXTURE_RATE, "n_requests": len(reqs),
                "finished": len(stats.finished)}
        return CalibrationReport.build(rec, model, like=model, meta=meta)

    # --real: tiny live engine, wall clock, measured timings
    import dataclasses as dc

    from repro.configs import get_smoke_config
    from repro.serving.engine import EngineConfig
    from repro.simulator.cost_model import TPU_V5E_SIM

    cfg = get_smoke_config("llama3-8b")
    cfg = dc.replace(cfg, num_layers=2, d_model=128, num_heads=2,
                     num_kv_heads=1, head_dim=64, d_ff=256, vocab_size=300)
    seed_model = InstanceCostModel(cfg=cfg, hw=TPU_V5E_SIM)
    econf = EngineConfig(max_batch=4, max_seq_len=160, eos_token=-1)
    server = PaDGServer(cfg, n_instances=1, slo=SLO(ttft=60.0, tpot=10.0),
                        econf=econf, backend="real", cost_model=seed_model)
    records = normalize_rate(load_fixture("azure"), 50.0)[:10]
    reqs = requests_from_trace(records, max_prompt=48, max_output=6,
                               vocab_size=cfg.vocab_size, seed=0)
    # warmup pass over the same prompt lengths, unrecorded: jax compiles
    # one decode kernel per batch shape and one prefill kernel per prompt
    # length, and those one-off compile times would otherwise dominate
    # every measurement
    warm = requests_from_trace(records, max_prompt=48, max_output=6,
                               vocab_size=cfg.vocab_size, seed=1)
    server.serve(warm, clock=WallClock(1.0))
    for inst in server.instances:
        inst.engine.engine.recorder = rec
    stats = server.serve(reqs, clock=WallClock(1.0))
    server.shutdown()
    meta = {"backend": "real", "fixtures": ["azure"],
            "n_requests": len(reqs), "finished": len(stats.finished)}
    return CalibrationReport.build(rec, seed_model, like=seed_model,
                                   meta=meta)


def _stream_row(stream: str, report: CalibrationReport) -> None:
    if not stream:
        return
    with open(stream, "a") as fh:
        fh.write(json.dumps({"bench": "calibration",
                             **report.to_dict()}, sort_keys=True) + "\n")
        fh.flush()


def run(backend: str = "fake", stream: str = None) -> CalibrationReport:
    t0 = time.time()
    report = build_report(backend)
    dt = time.time() - t0
    print(f"\n== sim-to-real calibration ({backend} backend) ==")
    print(f"  samples: {report.n_prefill} prefill ops, "
          f"{report.n_decode} decode ops "
          f"({report.meta.get('finished')} requests finished)")
    print("  per-op relative error (|pred - measured| / measured):")
    print(f"  {'':>10} {'unfitted':>10} {'fitted':>10}")
    for key in ("prefill_median", "prefill_p90", "decode_median",
                "decode_p90", "overall_median"):
        print(f"  {key:>16} {report.unfitted[key]:10.4f} "
              f"{report.fitted[key]:10.4f}")
    if backend == "fake":
        # the acceptance claim — measured constants must beat the
        # roofline model on its own replay (real rows are informational:
        # wall-clock noise on shared CI runners is not assertable)
        assert (report.fitted["overall_median"]
                < report.unfitted["overall_median"]), (
            "fitted constants did not reduce median per-op error: "
            f"{report.fitted} vs {report.unfitted}")
    _stream_row(stream, report)
    emit(f"calibration_{backend}", dt * 1e6,
         f"median_err {report.unfitted['overall_median']:.3f}"
         f"->{report.fitted['overall_median']:.3f}")
    return report


def write_golden() -> None:
    report = build_report("fake")
    GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
    report.save(GOLDEN_PATH)
    print(f"wrote calibration report "
          f"({report.n_prefill}+{report.n_decode} ops) to {GOLDEN_PATH}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--real", action="store_true",
                    help="measure the live jax engine wall-clock "
                    "(non-deterministic; CI runs it non-gating)")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for the default single-cell run (CI)")
    ap.add_argument("--stream", default=None, metavar="PATH",
                    help="append the report as one JSONL row")
    ap.add_argument("--write-golden", action="store_true",
                    help="regenerate tests/golden/calibration_report.json")
    args = ap.parse_args()
    if args.write_golden:
        write_golden()
    else:
        run(backend="real" if args.real else "fake", stream=args.stream)
