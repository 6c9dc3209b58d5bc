"""Serving launcher: real-execution PaDG serving of a model at its
published configuration, in bf16, one instance per device.

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b \
        --instances 1 --requests 8 --max-batch 8 --max-seq-len 4096
"""
import argparse
import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
SERVE_SLO = (60.0, 10.0)        # (ttft, tpot) seconds: admits everything


def configure_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
    ``<repo>/.jax_cache`` (the path is part of the cache key, so it never
    moves).  Returns the directory."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def prompt_lengths(max_seq_len: int):
    """The four prompt lengths requests draw from: 1/8 to 1/2 of the
    slot.  Every distinct length compiles its own prefill."""
    return tuple(max_seq_len * k // 8 for k in (1, 2, 3, 4))


def setup(cfg, *, instances: int, requests: int, out_tokens: int,
          max_batch: int, max_seq_len: int, rate: float = 4.0,
          seed: int = 0, cost_model=None):
    """Build a bf16 ``PaDGServer`` for ``cfg`` (weights drawn from
    ``seed``, instance *i* on device *i*), compile every program the
    requests will run, and draw ``requests`` seeded requests: Poisson
    arrivals at ``rate``/s, prompt lengths from ``prompt_lengths``,
    ``out_tokens`` each.  Returns ``(server, requests)``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.request import Request
    from repro.core.slo import SLO
    from repro.serving.engine import EngineConfig
    from repro.serving.padg_server import PaDGServer

    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode serving")
    lens = prompt_lengths(max_seq_len)
    server = PaDGServer(
        cfg, n_instances=instances, slo=SLO(*SERVE_SLO), seed=seed,
        econf=EngineConfig(max_batch=max_batch, max_seq_len=max_seq_len,
                           dtype=jnp.bfloat16, eos_token=-1),
        cost_model=cost_model)
    for inst in server.instances:
        inst.engine.engine.warmup(lens)

    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    for i in range(requests):
        plen = int(rng.choice(lens))
        reqs.append(Request(
            rid=i, arrival_time=t, prompt_len=plen, output_len=out_tokens,
            prompt_tokens=rng.integers(2, cfg.vocab_size, plen).tolist()))
        t += float(rng.exponential(1.0 / rate))
    return server, reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--instances", type=int, default=1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--out-tokens", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=4096)
    args = ap.parse_args()

    from repro.configs import get_config

    configure_compile_cache()
    cfg = get_config(args.arch)
    server, reqs = setup(cfg, instances=args.instances,
                         requests=args.requests, out_tokens=args.out_tokens,
                         max_batch=args.max_batch,
                         max_seq_len=args.max_seq_len, rate=args.rate)
    print(f"serving {len(reqs)} requests on {args.instances} instances "
          f"({cfg.name}, {cfg.param_count()/1e9:.2f}B params, bf16)")
    with server:
        stats = server.serve(reqs)
    for k, v in stats.summary().items():
        print(f"  {k} = {v}")


if __name__ == "__main__":
    main()
