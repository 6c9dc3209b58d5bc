"""Tests for the beyond-paper scheduler variants (EcoServe-CP) and the
serving API."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.instance import Instance
from repro.core.padg_system import EcoServeSystem
from repro.core.request import Request, RequestState
from repro.core.slo import DATASET_SLOS, SLO
from repro.simulator.cost_model import GPU_L20, InstanceCostModel
from repro.simulator.metrics import run_once
from repro.simulator.workload import WORKLOADS


class Exec:
    def prefill_time(self, lens):
        return 1e-4 * sum(lens)

    def decode_time(self, b, c):
        return 0.02

    def hybrid_time(self, chunk_lens, prefix_lens, batch, ctxs):
        return 0.02 + 1e-4 * sum(chunk_lens)


def test_chunked_fallback_progresses_prefill_during_decode():
    """With thin slack, EcoServe-CP completes a prompt through hybrid
    iterations without a dedicated prefill slot."""
    inst = Instance(0, Exec(), kv_capacity_tokens=10**6,
                    slo_tpot=0.1, slo_ttft=10.0, chunked_fallback=256)
    # a long-running decode with ZERO slack (just started)
    running = Request(rid=1, arrival_time=0.0, prompt_len=10, output_len=400)
    inst.admit(running, 0.0)
    k, d, b = inst.next_slot(0.0)
    now = d
    inst.complete_slot(k, b, now)
    assert running.state == RequestState.DECODING

    newreq = Request(rid=2, arrival_time=now, prompt_len=5000, output_len=5)
    inst.admit(newreq, now)
    # the 0.5s prefill exceeds the running decode's ~0.1s slack -> full
    # prefill slot not allowed; slots must be hybrid until the prompt is
    # done chunk by chunk
    kinds = []
    for _ in range(25):
        k, d, batch = inst.next_slot(now)
        kinds.append(k)
        now += d
        inst.complete_slot(k, batch, now)
        if newreq.state == RequestState.DECODING:
            break
    assert "hybrid" in kinds
    assert "prefill" not in kinds[:4]
    assert newreq.state == RequestState.DECODING
    assert newreq.first_token_time is not None
    # the running decode kept generating every iteration meanwhile
    assert running.tokens_generated >= len(kinds)


def test_ecoserve_cp_system_runs_and_attains():
    cost = InstanceCostModel(cfg=get_config("llama-30b"), hw=GPU_L20, tp=4)
    slo = DATASET_SLOS["sharegpt"]
    m = run_once(
        lambda: EcoServeSystem(cost, 4, slo, plus_plus=True,
                               chunked_fallback=512),
        WORKLOADS["sharegpt"], rate=8.0, slo=slo, duration=45.0)
    assert m["completion"] > 0.95
    assert m["attainment"] > 0.9


def serving_api_generate_streaming():
    from repro.serving.api import EcoServeAPI
    from repro.serving.engine import EngineConfig
    from repro.simulator.cost_model import TPU_V5E_SIM

    cfg = get_smoke_config("llama3-8b")
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=128, num_heads=2,
                              num_kv_heads=1, head_dim=64, d_ff=256,
                              vocab_size=300)
    api = EcoServeAPI(cfg, n_instances=2,
                      econf=EngineConfig(max_batch=2, max_seq_len=64,
                                         eos_token=-1),
                      cost_model=InstanceCostModel(cfg=cfg, hw=TPU_V5E_SIM))
    streamed = []
    res = api.generate(["hello world", "padg serving"],
                       max_new_tokens=4,
                       stream=lambda rid, tok: streamed.append((rid, tok)))
    assert len(res) == 2
    for r in res:
        assert len(r.tokens) == 4
        assert r.ttft_s >= 0
        assert isinstance(r.text, str)
    assert len(streamed) == 8


def test_serving_api_generate_streaming(on_host_devices):
    # two instances need two devices
    on_host_devices(serving_api_generate_streaming, n=2)
