"""One run of one cell: build the served system, warm it, drive the
measured window through ``PaDGServer.serve`` on a wall clock, check what
the window produced against the plain reference, and make the result.

Set-up (``setup_s``) is everything from process start to the first due
arrival: weights, compiles, the warm-up stream.  The window then offers
the cell's requests at their due times.  Below the knee, arrivals end at
``--seconds`` and the run drains until every request finished or the
mix's ``drain_s`` ran out; an overload cell stops at ``--seconds``.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
from typing import Dict, Optional

import numpy as np

import devtrace
import hooks
import traffic
import weights
from spec import HERE, Cell, load_module, model_config

TRACE_DIR = HERE / "_trace"


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Records:
    """What the per-layer readers (``metrics/<name>.py``) read."""
    cell: Cell
    cfg: object                 # ModelConfig as run
    seconds: float
    due: list                   # the window's requests, after serving
    window_s: float             # wall seconds from first due to the end
    clock: hooks.BenchClock
    events: list                # repro.obs Tracer events of the window
    recorder: object            # CalibrationRecorder of the window
    trace: Optional[devtrace.DeviceTrace]
    peak: Optional[dict]        # peaks.peaks(device_kind)
    prefills: list = dataclasses.field(default_factory=list)
    #                             prompt lengths of each traced prefill call

    @property
    def arch(self):
        """The configuration's architecture module: its step counts."""
        return self.cell.arch


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def meets_slo(r, slo: dict) -> bool:
    """Both of the mix's limits: TTFT from the due time, and mean TPOT from
    the second token on (paper section 3.3); one-token outputs have no TPOT."""
    if r.finish_time is None or r.ttft is None or r.ttft > slo["ttft_s"]:
        return False
    if r.tokens_generated > 1:
        return r.avg_tpot is not None and r.avg_tpot <= slo["tpot_s"]
    return True


def end_to_end(cell: Cell, due: list, window_s: float) -> Dict[str, float]:
    slo = cell.mix["slo"]
    ttft = [r.ttft for r in due if r.first_token_time is not None]
    tpot = [r.avg_tpot for r in due if r.avg_tpot is not None]
    out = {"ttft_p50_s": percentile(ttft, 50),
           "ttft_p90_s": percentile(ttft, 90),
           "tpot_p50_s": percentile(tpot, 50),
           "tpot_p90_s": percentile(tpot, 90),
           "slo_attainment": sum(meets_slo(r, slo) for r in due) / len(due),
           "output_tokens_per_s":
               sum(len(r.generated or ()) for r in due) / window_s}
    say(f"tails: ttft n={len(ttft)} p50={out['ttft_p50_s']} "
        f"p90={out['ttft_p90_s']}; tpot n={len(tpot)} "
        f"p50={out['tpot_p50_s']} p90={out['tpot_p90_s']}; "
        f"slo_attainment={out['slo_attainment']} "
        f"(ttft<={slo['ttft_s']}s, tpot<={slo['tpot_s']}s, "
        f"over {len(due)} due)")
    return out


def load_reader(name: str):
    if str(HERE / "metrics") not in sys.path:
        sys.path.insert(0, str(HERE / "metrics"))
    return load_module("metrics", name).read


def pick_sample(finished: list, seed: int, target_tokens: int,
                most: int, least: int = 3) -> list:
    """The finished request with the most served tokens, then others drawn
    from the seed until the sample holds ``least`` requests and
    ``target_tokens`` served tokens, or ``most`` requests."""
    if not finished:
        return []
    done = sorted(finished, key=lambda r: (-len(r.generated), r.rid))
    sample, total = [done[0]], len(done[0].generated)
    for i in traffic.rng(seed, 2).permutation(len(done) - 1) + 1:
        if (total >= target_tokens and len(sample) >= least
                or len(sample) >= most):
            break
        sample.append(done[i])
        total += len(done[i].generated)
    return sample


def check(params, cfg, arch, sample: list, control: bool = False):
    """Widest gap, over every served token of the sample, between the
    reference's best logit and the served token's; with ``control`` also
    the widest gap of the tokens the float8 control puts first at the same
    positions, the control's tokens in place of the served ones."""
    import reference

    ref = reference.Reference(params, cfg, arch)
    ctl = reference.Reference(params, cfg, arch, "fp8") if control else None
    widest = widest_ctl = 0.0
    for r in sample:
        g, c = reference.served_gaps(ref, r.prompt_tokens, r.generated, ctl)
        widest = max(widest, float(g.max()))
        if c is not None:
            widest_ctl = max(widest_ctl, float(c.max()))
    return widest, (widest_ctl if control else None)


def judge(widest: float, n_tok: int, limits: dict):
    """The comparison that decides ``correct``: each number compared with
    its limit, and whether all of them hold."""
    checks = {"widest_logit_gap": {"value": widest,
                                   "limit": limits["widest_gap_limit"]},
              "served_tokens_checked": {"value": n_tok,
                                        "limit": limits["least_tokens"]}}
    ok = (widest <= limits["widest_gap_limit"]
          and n_tok >= limits["least_tokens"])
    return checks, ok


def _peak_bytes(devices) -> Dict[int, Optional[int]]:
    return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, cfg_override: Optional[dict] = None,
             cost_model=None, rate: Optional[float] = None,
             drain_s: Optional[float] = None, verify: bool = True,
             control: bool = False) -> dict:
    """One run; returns the result line's object plus, under ``extra``,
    what the control and the sweep read."""
    import jax
    import jax.numpy as jnp

    from repro.core.slo import SLO
    from repro.launch.serve import configure_compile_cache
    from repro.serving.calibration import CalibrationRecorder
    from repro.serving.engine import EngineConfig
    from repro.serving.padg_server import PaDGServer

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cp = cell.params
    rate = cp["rate_rps"] if rate is None else rate
    cfg = model_config(cell.config, **(cfg_override or {}))
    dtype = getattr(jnp, cell.config["dtype"])
    slo = cell.mix["slo"]
    n_inst = cell.instances
    devices = jax.devices()[:n_inst]
    recorder = CalibrationRecorder() if trace else None

    econf = EngineConfig(max_batch=cp["slots"], max_seq_len=cp["positions"],
                         dtype=dtype, eos_token=-1)
    server = PaDGServer(cfg, n_instances=n_inst,
                        slo=SLO(slo["ttft_s"], slo["tpot_s"]), econf=econf,
                        cost_model=cost_model, recorder=recorder)
    engines = [inst.engine.engine for inst in server.instances]
    params = []
    for eng in engines:
        theirs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                              eng.params)
        eng.params = None           # the program's own draw is not served
        ours = weights.make(cell.arch, cfg, seed, dtype, eng.device)
        weights.check_layout(ours, theirs)
        eng.params = ours
        params.append(ours)

    due = traffic.measured(cell.mix, rate=rate, seconds=seconds,
                           positions=cp["positions"], seed=seed,
                           vocab=cfg.vocab_size)
    rungs = sorted({r.prompt_len for r in due})
    for eng in engines:
        eng.warmup(rungs)
    wu = cp["warmup"]
    server.serve(traffic.warmup(rungs, requests=wu["requests"],
                                output_tokens=wu["output_tokens"], seed=seed,
                                vocab=cfg.vocab_size))
    say(f"geometry: instances={n_inst} slots={cp['slots']} "
        f"positions={cp['positions']} prefill_rungs={rungs} "
        f"dtype={cell.config['dtype']} layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads}x"
        f"{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}")

    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer()
        recorder.prefill.clear()
        recorder.decode.clear()
        prefills = []
        for inst in server.instances:
            inst.engine = hooks.AnnotatedBackend(inst.engine, prefills)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    clock = hooks.BenchClock(annotate=trace)
    if trace:
        # the profiler covers the arrivals, not the drain: a long drain
        # would make the trace too large to read within the run
        clock.on_start = lambda: jax.profiler.start_trace(
            str(TRACE_DIR), profiler_options=hooks.profile_options())
        clock.on_stop = jax.profiler.stop_trace
        clock.stop_at = seconds
    counter = hooks.CompileCounter()
    counter.armed = True
    drain_s = cell.mix["drain_s"] if drain_s is None else drain_s
    horizon = seconds + drain_s
    server.serve(due, clock=clock, horizon=horizon, tracer=tracer)
    t_end = time.perf_counter()
    counter.armed = False
    counter.close()
    clock.stop()
    setup_s = clock.started_at - t_process
    window_s = t_end - clock.started_at

    finished = [r for r in due if r.finish_time is not None]
    refused = [r for r in due if r.first_token_time is None
               and r.state.value == "failed"]
    say(f"offered: rate={rate} req/s due={len(due)} over {seconds} s, "
        f"horizon={horizon} s, window_wall_s={window_s}")
    say(f"requests: due={len(due)} finished={len(finished)} "
        f"refused={len(refused)} unfinished="
        f"{len(due) - len(finished) - len(refused)}")
    say(f"compiles_in_window: {counter.summary()}")
    peak = _peak_bytes(devices)
    say(f"peak_bytes_in_use: {peak}")
    say(f"setup_s={setup_s}")
    if trace:
        say(f"prefills (tokens, host ms): "
            f"{[(t, round(dt * 1e3, 2)) for t, dt in recorder.prefill]}")
    e2e = end_to_end(cell, due, window_s)
    e2e["setup_s"] = setup_s

    # free the program's state before the reference runs
    for eng in engines:
        eng.cache = eng.tokens = eng.params = None
    server.shutdown()
    del server, engines
    for p in params[1:]:
        jax.tree.map(lambda a: a.delete(), p)
    gc.collect()

    extra = {"e2e": e2e, "finished": len(finished), "due": len(due)}
    checks, correct = {}, False
    if verify:
        sample = pick_sample(finished, seed, cp["check"]["sample_tokens"],
                             cp["check"]["most_requests"])
        widest, widest_ctl = check(params[0], cfg, cell.arch, sample,
                                   control)
        n_tok = sum(len(r.generated) for r in sample)
        checks, correct = judge(widest, n_tok, cp["check"])
        extra.update(widest=widest, sample=[(r.rid, r.prompt_len,
                                             len(r.generated))
                                            for r in sample])
        if control:
            # the control in the program's place: its tokens are judged
            # as the served ones are, and its verdict is the run's
            extra.update(program_checks=checks, program_correct=correct,
                         widest_control=widest_ctl)
            checks, correct = judge(widest_ctl, n_tok, cp["check"])

    failed = len(refused) + (0 if cell.overload else
                             len(due) - len(finished) - len(refused))
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max((v or 0) for v in peak.values())}
    result = {"correct": correct, "attempted": len(due), "failed": failed}
    if trace:
        import peaks
        path = devtrace.find_xplane(str(TRACE_DIR))
        dt = (devtrace.reduce(path, hooks.HOST_SPANS,
                              devices=[d.id for d in devices])
              if path else None)
        try:
            pk = peaks.peaks(d0.device_kind)
        except KeyError:
            pk = None
        rec = Records(cell=cell, cfg=cfg, seconds=seconds, due=due,
                      window_s=window_s, clock=clock, events=tracer.events,
                      recorder=recorder, trace=dt, peak=pk,
                      prefills=prefills)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if dt is not None:
            device.update(busy_s=dt.busy_s, window_s=dt.window_s)
            progs = sorted(dt.program_seconds().items(),
                           key=lambda x: -x[1])[:10]
            gaps = sorted(dt.idle_gaps().items(), key=lambda x: -x[1])[:10]
            result["breakdown"] = {"device_ops": [list(p) for p in progs],
                                   "idle_gaps": [list(g) for g in gaps]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    result["extra"] = extra
    return result
