"""End-to-end driver: serve a small model with batched requests through
the full EcoServe stack (real JAX execution, wall-clock scheduling).

Two PaDG instances, one per device, serve a Poisson request trace;
Algorithm 1 routes stickily, Algorithm 2 checks constraints, instances
alternate prefill/decode slots (temporal disaggregation).  On the CPU,
ask XLA for two host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_padg.py
"""
import dataclasses

import numpy as np


def main():
    from repro.configs import get_smoke_config
    from repro.core.request import Request
    from repro.core.slo import SLO
    from repro.data.pipeline import ByteTokenizer
    from repro.serving.engine import EngineConfig
    from repro.serving.padg_server import PaDGServer
    from repro.simulator.cost_model import TPU_V5E_SIM, InstanceCostModel

    cfg = get_smoke_config("llama3-8b")
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=128, num_heads=2,
                              num_kv_heads=1, head_dim=64, d_ff=256,
                              vocab_size=300)
    tok = ByteTokenizer(cfg.vocab_size)
    slo = SLO(ttft=30.0, tpot=5.0)       # loose: CPU wall-clock
    # the CPU has no seed profile of its own: schedule with the v5e one
    server = PaDGServer(cfg, n_instances=2, slo=slo,
                        econf=EngineConfig(max_batch=4, max_seq_len=64,
                                           eos_token=-1),
                        cost_model=InstanceCostModel(cfg=cfg, hw=TPU_V5E_SIM))

    prompts = [
        "the quick brown fox", "ecoserve rolls activation",
        "prefill then decode", "macro instances cooperate",
        "temporal disaggregation", "commodity interconnects win",
        "rolling activation keeps ttft low", "mitosis scales instances",
    ]
    rng = np.random.default_rng(0)
    reqs = []
    t = 0.0
    for i, p in enumerate(prompts):
        ids = tok.encode(p)[:20]
        reqs.append(Request(rid=i, arrival_time=t, prompt_len=len(ids),
                            output_len=6, prompt_tokens=ids))
        t += float(rng.exponential(0.15))

    print(f"serving {len(reqs)} requests on 2 PaDG instances "
          f"({cfg.param_count()/1e6:.1f}M params each, CPU)...")
    stats = server.serve(reqs)
    s = stats.summary()
    print(f"\nfinished={s['finished']}  tokens={s['tokens']}")
    print(f"TTFT  p50={s['ttft_p50']*1e3:.0f}ms  p90={s['ttft_p90']*1e3:.0f}ms")
    print(f"TPOT  p50={s['tpot_p50']*1e3:.0f}ms")
    for r in stats.finished[:4]:
        print(f"  req {r.rid}: instance={r.instance_id} "
              f"ttft={r.ttft*1e3:.0f}ms tokens={r.generated}")


if __name__ == "__main__":
    main()
