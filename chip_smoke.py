"""Smoke run of the served path on a TPU: ChatGLM3-6B at its published
configuration (28 layers, d_model 4096, 32/2 heads, d_ff 13696, vocab
65,024) in bf16, with random weights drawn from a seed, behind
``PaDGServer`` (-> ``ReplayEngine`` -> ``ServingEngine``).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one macro instance of four
                                     # one-chip instances

One chip: serve 8 seeded requests (prompts of 512-2,048 tokens from four
lengths, 64 output tokens each, 8 slots x 4,096 positions), then check,
for one request, that decoding through the KV cache agrees with a full
forward pass over the prompt plus the generated prefix.

Four chips: serve the same requests on four instances, one per chip, and
check every request's prefill logits, recomputed on each instance, against
instance 0 on device 0 (same seed weights), and that the instance that
served it reproduces its first token.

The lines before the last are smoke output, not benchmark metrics.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``.  Exits
non-zero, without that line, when JAX finds no TPU or any check fails.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "chatglm3-6b"
SEED = 0
REQUESTS = 8
OUT_TOKENS = 64
MAX_BATCH = 8
MAX_SEQ_LEN = 4096

# Cache-vs-forward gate on the relative RMS error of the logits.  The two
# paths round differently in bf16 (blockwise prefill attention vs one-token
# decode attention, matmuls of other shapes), which leaves errors of a few
# percent after 28 layers.  A wrong cache slot, position or mask instead
# decorrelates the logits, which puts the error near sqrt(2).
CACHE_REL_RMS_TOL = 0.1
# Prefill on another chip runs the same program on the same weights; any
# difference beyond float noise means the instance is not what device 0
# holds.
DEVICE_REL_RMS_TOL = 1e-3


def _rel_rms(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def cache_vs_forward(engine, req):
    """Re-run ``req`` through the engine's own admission path and jitted
    decode program, feeding its generated tokens (teacher forcing), and
    compare the logits at every generated position with one full forward
    pass over prompt + generated prefix.  Returns (rel_rms, max_abs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.request import Request
    from repro.models import forward

    prompt, gen = list(req.prompt_tokens), list(req.generated)
    P = len(prompt)
    probe = Request(rid=-1, arrival_time=0.0, prompt_len=P,
                    output_len=MAX_SEQ_LEN, prompt_tokens=prompt)
    engine.prefill(probe)
    slot = engine.slot_req.index(probe)
    first, _ = engine.prefill_fn(
        engine.params, jax.device_put(np.asarray([prompt], np.int32),
                                      engine.device))
    got = [np.asarray(first[0], np.float32)]
    for tok in gen[:-1]:
        tokens = engine.tokens.at[slot, 0].set(tok)
        logits, engine.cache = engine.decode_fn(
            engine.params, engine.cache, tokens,
            jax.device_put(engine.lengths, engine.device))
        got.append(np.asarray(logits[slot], np.float32))
        engine.lengths[slot] += 1
    engine.release(probe)

    full = jax.jit(lambda p, t: forward(p, engine.cfg, {"tokens": t})[0][
        0, P - 1:].astype(jnp.float32))
    want = np.asarray(full(engine.params, jax.device_put(
        np.asarray([prompt + gen[:-1]], np.int32), engine.device)))
    got = np.stack(got)
    return _rel_rms(got, want), float(np.max(np.abs(got - want)))


def _peak_bytes(devices):
    # None where the backend keeps no memory statistics
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices}


def _serve(cfg, instances):
    from repro.launch.serve import setup

    t0 = time.perf_counter()
    server, reqs = setup(cfg, instances=instances, requests=REQUESTS,
                         out_tokens=OUT_TOKENS, max_batch=MAX_BATCH,
                         max_seq_len=MAX_SEQ_LEN, seed=SEED)
    t_setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    stats = server.serve(reqs)
    t_served = time.perf_counter() - t0
    engines = {inst.iid: inst.engine.engine for inst in server.instances}
    s = stats.summary()
    print(f"smoke: instances={instances} prompt_lens="
          f"{sorted({r.prompt_len for r in reqs})}")
    print(f"smoke: setup_s={t_setup} (weights init + compiles)")
    print(f"smoke: served_s={t_served}")
    print("smoke: prefill_compiles="
          + str({i: e.prefill_fn._cache_size() for i, e in engines.items()}))
    print(f"smoke: requests_finished={s['finished']}/{len(reqs)} "
          f"rejected={s['rejected']} tokens={s['tokens']}")
    print("smoke: peak_bytes_in_use=" + json.dumps(_peak_bytes(
        [e.device for e in engines.values()])))
    done = [r for r in stats.finished if len(r.generated) == OUT_TOKENS]
    if s["rejected"] or len(done) != len(reqs):
        raise SystemExit(f"FAIL: {len(done)} of {len(reqs)} requests "
                         f"finished with {OUT_TOKENS} tokens")
    return server, stats.finished, engines


def one_chip(cfg) -> None:
    server, finished, engines = _serve(cfg, 1)
    req = max(finished, key=lambda r: r.prompt_len)
    rel, max_abs = cache_vs_forward(engines[req.instance_id], req)
    print(f"smoke: cache_vs_forward prompt_len={req.prompt_len} "
          f"positions={len(req.generated)} rel_rms={rel} max_abs={max_abs} "
          f"tol={CACHE_REL_RMS_TOL}")
    server.shutdown()
    if not rel <= CACHE_REL_RMS_TOL:
        raise SystemExit("FAIL: cached decode disagrees with full forward")


def four_chips(cfg) -> None:
    import jax
    import numpy as np

    server, finished, engines = _serve(cfg, 4)
    ref = engines[min(engines)]
    if ref.device != jax.devices()[0]:
        raise SystemExit(f"FAIL: instance 0 is on {ref.device}")
    worst = 0.0
    for r in sorted(finished, key=lambda r: r.rid):
        toks = np.asarray([r.prompt_tokens], np.int32)
        logits = {}
        for iid, eng in engines.items():
            out, _ = eng.prefill_fn(eng.params,
                                    jax.device_put(toks, eng.device))
            logits[iid] = np.asarray(out, np.float32)
        rel = max(_rel_rms(got, logits[min(engines)])
                  for got in logits.values())
        served = engines[r.instance_id]
        same_first = (int(np.argmax(logits[r.instance_id][0]))
                      == r.generated[0])
        print(f"smoke: rid={r.rid} served_on_device={served.device.id} "
              f"prompt_len={r.prompt_len} "
              f"max_prefill_rel_rms_vs_device0={rel} "
              f"first_token_reproduced={same_first}")
        if not (rel <= DEVICE_REL_RMS_TOL and same_first):
            raise SystemExit(f"FAIL: request {r.rid}: an instance "
                             f"disagrees with device 0")
        worst = max(worst, rel)
    used = {engines[r.instance_id].device.id for r in finished}
    print(f"smoke: devices_serving={sorted(used)} worst_rel_rms={worst}")
    server.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many TPUs, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.serve import configure_compile_cache

    print(f"smoke: compile cache at {configure_compile_cache()}")
    cfg = get_config(ARCH)
    (four_chips if args.chips == 4 else one_chip)(cfg)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
