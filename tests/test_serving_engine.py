"""Real-execution serving: continuous batching engine + PaDG server on a
tiny model (CPU), and greedy-decoding equivalence with plain forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.core.slo import SLO
from repro.models import forward, init_params
from repro.serving.calibration import CalibrationRecorder
from repro.serving.engine import (EngineConfig, MeasuredExecutor,
                                  ServingEngine)
from repro.serving.padg_server import PaDGServer
from repro.simulator.cost_model import (TPU_V5E_SIM, FittedExecutor,
                                        InstanceCostModel)


def tiny_cfg():
    cfg = get_smoke_config("llama3-8b")
    return dataclasses.replace(cfg, num_layers=2, d_model=128, num_heads=2,
                               num_kv_heads=1, head_dim=64, d_ff=256,
                               vocab_size=300)


def seed_model(cfg):
    """The CPU has no seed profile of its own: pass the v5e one."""
    return InstanceCostModel(cfg=cfg, hw=TPU_V5E_SIM)


def greedy_reference(cfg, params, prompt, n_new):
    """Teacher-forced greedy decoding via repeated full forward."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = forward(params, cfg,
                            {"tokens": jnp.asarray([toks], jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_full_forward_greedy():
    cfg = tiny_cfg()
    eng = ServingEngine(cfg, seed=3, cost_model=seed_model(cfg),
                        econf=EngineConfig(max_batch=2, max_seq_len=64,
                                           eos_token=-1))
    prompt = [5, 9, 17, 4, 33]
    n_new = 6
    want = greedy_reference(cfg, eng.params, prompt, n_new)

    req = Request(rid=0, arrival_time=0.0, prompt_len=len(prompt),
                  output_len=n_new, prompt_tokens=prompt)
    eng.prefill(req)
    while len(req.generated) < n_new:
        eng.decode_step()
    assert req.generated == want


def test_engine_concurrent_requests_isolated():
    """Two interleaved requests must produce the same tokens as served
    alone (KV-slot isolation under continuous batching)."""
    cfg = tiny_cfg()
    eng = ServingEngine(cfg, seed=4, cost_model=seed_model(cfg),
                        econf=EngineConfig(max_batch=2, max_seq_len=64,
                                           eos_token=-1))
    p1, p2 = [7, 3, 11], [21, 9, 2, 40, 8]
    solo1 = greedy_reference(cfg, eng.params, p1, 5)
    solo2 = greedy_reference(cfg, eng.params, p2, 5)

    r1 = Request(rid=1, arrival_time=0, prompt_len=len(p1), output_len=5,
                 prompt_tokens=p1)
    r2 = Request(rid=2, arrival_time=0, prompt_len=len(p2), output_len=5,
                 prompt_tokens=p2)
    eng.prefill(r1)
    eng.decode_step()          # r1 advances alone
    eng.prefill(r2)            # r2 joins mid-flight
    for _ in range(6):
        eng.decode_step()
    assert r1.generated[:5] == solo1
    assert r2.generated[:5] == solo2


def serve_two_instances(arch):
    """Two engine-backed instances, one per device, serve 6 requests."""
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=128,
                              num_heads=2, num_kv_heads=max(1, min(
                                  2, cfg.num_kv_heads)), head_dim=64,
                              d_ff=256, vocab_size=300)
    slo = SLO(ttft=60.0, tpot=10.0)   # wall-clock CPU: loose SLOs
    server = PaDGServer(cfg, n_instances=2, slo=slo,
                        cost_model=seed_model(cfg),
                        econf=EngineConfig(max_batch=2, max_seq_len=48,
                                           eos_token=-1))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(6):
        plen = int(rng.integers(3, 10))
        reqs.append(Request(
            rid=i, arrival_time=0.02 * i, prompt_len=plen, output_len=4,
            prompt_tokens=[int(x) for x in rng.integers(2, 290, plen)]))
    stats = server.serve(reqs)
    s = stats.summary()
    assert s["finished"] == 6
    for r in stats.finished:
        assert len(r.generated) == 4
        assert r.finish_time >= r.first_token_time >= 0
    server.shutdown()


@pytest.mark.parametrize("arch", ["llama3-8b", "rwkv6-3b"])
def test_padg_server_end_to_end(arch, on_host_devices):
    on_host_devices(serve_two_instances, arch, n=2)


def test_padg_server_refuses_two_instances_on_one_device():
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="devices"):
        PaDGServer(cfg, n_instances=len(jax.devices()) + 1,
                   slo=SLO(ttft=60.0, tpot=10.0), cost_model=seed_model(cfg))


def test_engine_without_seed_profile_raises():
    """The CPU's device kind has no seed profile: no silent default."""
    with pytest.raises(ValueError, match="cost_model"):
        ServingEngine(tiny_cfg())


# --------------------------------------------------------------------- #
# MeasuredExecutor: shape-aware predictions
# --------------------------------------------------------------------- #
def test_measured_executor_seeds_from_model_probes():
    """Seeded from an exactly-linear model, the probe-derived constants
    reproduce the model's predictions before any observation."""
    seed = FittedExecutor(prefill_base=2e-3, prefill_per_token=3e-4,
                          decode_base=1e-3, decode_per_seq=4e-4,
                          decode_per_ctx_token=2e-6)
    ex = MeasuredExecutor(seed_model=seed)
    for n in (1, 17, 400):
        assert ex.prefill_time([n]) == pytest.approx(seed.prefill_time([n]))
    assert ex.decode_time(3, ctx_sum=500) == pytest.approx(
        seed.decode_time(3, ctx_sum=500))


def test_measured_executor_decode_shape_aware():
    """decode_time must grow with batch AND with context — the flat EWMA
    regression this replaces predicted one constant for every shape."""
    ex = MeasuredExecutor(seed_model=FittedExecutor(
        decode_base=1e-3, decode_per_seq=4e-4, decode_per_ctx_token=2e-6))
    assert ex.decode_time(0) == 0.0
    assert ex.decode_time(4) > ex.decode_time(2) > ex.decode_time(1)
    assert (ex.decode_time(2, ctx_sum=4096) > ex.decode_time(2, ctx_sum=64)
            > ex.decode_time(2, ctx_sum=0))
    # observations rescale, but never flatten, the shape dependence
    for _ in range(20):
        ex.observe_decode(5e-3, batch=2, ctx_sum=64)
    assert ex.decode_time(4, ctx_sum=128) > ex.decode_time(2, ctx_sum=64)


def test_measured_executor_legacy_fallbacks():
    """Without a model to probe, the documented flat fallbacks apply."""
    ex = MeasuredExecutor()
    assert ex.prefill_time([10]) == pytest.approx(10 * 2e-4)
    assert ex.decode_time(3) == pytest.approx(3 * 5e-2)
    ex = MeasuredExecutor(fallback_prefill=1e-3, fallback_decode=1e-2)
    assert ex.prefill_time([4]) == pytest.approx(4e-3)
    assert ex.decode_time(2) == pytest.approx(2e-2)


def test_engine_recorder_captures_op_shapes():
    cfg = tiny_cfg()
    rec = CalibrationRecorder()
    eng = ServingEngine(cfg, seed=5, recorder=rec, cost_model=seed_model(cfg),
                        econf=EngineConfig(max_batch=2, max_seq_len=64,
                                           eos_token=-1))
    prompt = [5, 9, 17, 4]
    req = Request(rid=0, arrival_time=0.0, prompt_len=len(prompt),
                  output_len=3, prompt_tokens=prompt)
    eng.prefill(req)
    while len(req.generated) < 3:
        eng.decode_step()
    assert [toks for toks, _ in rec.prefill] == [len(prompt)]
    assert len(rec.decode) >= 2
    for batch, ctx_sum, dt in rec.decode:
        assert batch == 1 and ctx_sum >= len(prompt) and dt > 0.0
    for _, dt in rec.prefill:
        assert dt > 0.0
