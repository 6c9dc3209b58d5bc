"""The served path as a user launches it, on the CPU: the
``repro.launch.serve`` set-up end to end on a smoke config, one instance
per device, weights built in one pass, and where compiled code is kept."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import REPO_ROOT, prompt_lengths, setup
from repro.models import init_params
from repro.models.model import _init_block, _split_layers
from repro.simulator.cost_model import TPU_V5E_SIM, InstanceCostModel


def _seed_model(cfg):
    return InstanceCostModel(cfg=cfg, hw=TPU_V5E_SIM)


def test_serve_setup_runs_smoke_config_end_to_end():
    cfg = get_smoke_config("chatglm3-6b")
    server, reqs = setup(cfg, instances=1, requests=6, out_tokens=4,
                         max_batch=4, max_seq_len=64, rate=50.0,
                         cost_model=_seed_model(cfg))
    assert {r.prompt_len for r in reqs} <= set(prompt_lengths(64))
    eng = server.instances[0].engine.engine
    assert jax.tree.leaves(eng.params)[0].dtype == jnp.bfloat16
    compiled = eng.prefill_fn._cache_size()
    assert compiled == len(prompt_lengths(64))
    with server:
        stats = server.serve(reqs)
    assert stats.summary()["finished"] == 6
    assert all(len(r.generated) == 4 for r in stats.finished)
    # set-up compiled every program the requests ran
    assert eng.prefill_fn._cache_size() == compiled
    assert eng.decode_fn._cache_size() == 1


def place_four_instances():
    """Engine i's params, cache and token buffer live on device i, before
    and after serving."""
    from repro.core.request import Request
    from repro.core.slo import SLO
    from repro.serving.engine import EngineConfig
    from repro.serving.padg_server import PaDGServer

    cfg = dataclasses.replace(get_smoke_config("chatglm3-6b"), d_model=128,
                              num_heads=2, num_kv_heads=1, d_ff=256)
    devices = jax.devices()
    assert len(devices) == 4
    server = PaDGServer(cfg, n_instances=4, slo=SLO(ttft=60.0, tpot=10.0),
                        econf=EngineConfig(max_batch=2, max_seq_len=32,
                                           eos_token=-1),
                        cost_model=_seed_model(cfg))
    reqs = [Request(rid=i, arrival_time=0.01 * i, prompt_len=5,
                    output_len=3, prompt_tokens=[3 + i, 7, 9, 11, 13])
            for i in range(8)]

    def check():
        for i, inst in enumerate(server.instances):
            eng = inst.engine.engine
            assert eng.device == devices[i]
            for leaf in jax.tree.leaves((eng.params, eng.cache, eng.tokens)):
                assert leaf.devices() == {devices[i]}

    check()
    with server:
        stats = server.serve(reqs)
    assert stats.summary()["finished"] == 8
    check()


def test_padg_server_puts_instance_i_on_device_i(on_host_devices):
    on_host_devices(place_four_instances, n=4)


@pytest.mark.parametrize("arch", ["chatglm3-6b", "recurrentgemma-2b",
                                  "rwkv6-3b", "phi3.5-moe-42b-a6.6b"])
def test_init_params_matches_per_layer_init(arch):
    """The vmapped one-pass init draws the weights a layer-by-layer init
    draws: exact in bf16, within an f32 rounding in f32 (the jitted
    program fuses the scale into the draw)."""
    cfg = get_smoke_config(arch)
    plen = len(cfg.block_pattern)
    cfg = dataclasses.replace(cfg, num_layers=2 * plen + 1)   # + a tail
    n_full, n_tail = _split_layers(cfg)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = init_params(jax.random.key(0), cfg, dtype)
        keys = jax.random.split(jax.random.split(jax.random.key(0), 4)[3],
                                cfg.num_layers)
        want = {f"pos{p}": jax.tree.map(lambda *x: jnp.stack(x), *[
            _init_block(keys[c * plen + p], cfg, cfg.block_pattern[p], dtype)
            for c in range(n_full)]) for p in range(plen)}
        tail = tuple(_init_block(keys[n_full * plen + i], cfg,
                                 cfg.block_pattern[i % plen], dtype)
                     for i in range(n_tail))
        for a, b in zip(jax.tree.leaves((got["layers_scan"],
                                         got["layers_tail"])),
                        jax.tree.leaves((want, tail))):
            assert a.shape == b.shape and a.dtype == b.dtype
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if dtype == jnp.bfloat16:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def compile_cache_in(expected, compile_one):
    import jax
    from repro.launch.serve import configure_compile_cache
    assert configure_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    if compile_one:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3))
        assert os.listdir(expected)


def test_compile_cache_follows_env(on_host_devices, monkeypatch, tmp_path):
    where = str(tmp_path / "jaxcc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
    on_host_devices(compile_cache_in, where, True, n=1)
    # unset: the fixed path in the checkout (nothing compiled, so the
    # test writes nothing there)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    on_host_devices(compile_cache_in, str(REPO_ROOT / ".jax_cache"), False,
                    n=1)
