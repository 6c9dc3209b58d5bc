"""Compile guard for the TPU: the Pallas kernels and the served ChatGLM3-6B
steps, compiled by the installed TPU compiler for a described (not
attached) v5e chip.  Interpret mode hides what Mosaic refuses (unaligned
blocks, primitives it cannot lower); these compiles do not.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Compiles run with the persistent compilation cache off, because an
entry written for a described chip cannot be read back without one.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.models import init_cache, init_params
from repro.serving.engine import serving_steps


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _sds(sharding, s.shape, s.dtype), tree)


BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
# (kernel, argument shapes) at the widths of the configs that use them:
# ChatGLM3-6B (32/2 heads of 128; 8 slots x 4,096 positions; 2,048-token
# prompt), RecurrentGemma-2B (d_model 2,560), RWKV6-3B (40 heads of 64)
KERNELS = {
    "decode_attention": (decode_attention, [
        ((8, 32, 128), BF), ((8, 4096, 2, 128), BF),
        ((8, 4096, 2, 128), BF), ((8,), I32)]),
    "flash_prefill": (flash_prefill, [
        ((1, 2048, 32, 128), BF), ((1, 2048, 2, 128), BF),
        ((1, 2048, 2, 128), BF)]),
    "rglru_scan": (rglru_scan, [
        ((1, 2048, 2560), F32), ((1, 2048, 2560), F32), ((1, 2560), F32)]),
    "rwkv6_scan": (rwkv6_scan, [((1, 2048, 40, 64), F32)] * 4
                   + [((40, 64), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def chatglm_one_layer(one_chip):
    """ChatGLM3-6B at published width with one layer: params and an 8 x
    4,096 bf16 cache as shapes on the described chip."""
    cfg = dataclasses.replace(get_config("chatglm3-6b"), num_layers=1)
    params = _on(one_chip, jax.eval_shape(functools.partial(
        init_params, cfg=cfg, dtype=BF), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(functools.partial(
        init_cache, cfg, 8, max_len=4096, dtype=BF)))
    return cfg, params, cache


def test_served_prefill_compiles_for_v5e(chatglm_one_layer, one_chip):
    cfg, params, _ = chatglm_one_layer
    prefill, _ = serving_steps(cfg)
    compiled = prefill.lower(params, _sds(one_chip, (1, 2048), I32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30


def test_served_decode_compiles_for_v5e(chatglm_one_layer, one_chip):
    cfg, params, cache = chatglm_one_layer
    _, decode = serving_steps(cfg)
    compiled = decode.lower(params, cache, _sds(one_chip, (8, 1), I32),
                            _sds(one_chip, (8,), I32)).compile()
    # the cache is donated: updated in place, not returned beside a copy
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
