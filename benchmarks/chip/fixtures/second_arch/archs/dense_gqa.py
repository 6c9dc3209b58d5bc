"""A test fixture, not an architecture of the benchmark: a dense decoder
with grouped-query attention, rotary embeddings on the whole of each head
and no qkv bias, written as an architecture module that a later change
would add (the names are those listed in ``archs/chatglm3.py``).  The
CPU tests copy it into a copy of the harness to show that a second
architecture is served and judged with no shared file edited.

Its shapes follow the program's whole layout, any block pattern of
``attn`` positions with the layers after the last whole cycle in
``layers_tail``, and ``TINY`` makes the CPU test walk all of it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import blocks, causal_attention, ein, rms, rotary

# three layers as two pattern positions and one tail layer
TINY = dict(num_layers=3, block_pattern=("attn", "attn"), d_model=128,
            num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=512)
# sound runs read 0.032-0.070 and the float8 control 0.65-1.30 over
# three seeds at these widths on the CPU
TINY_GAP_LIMIT = 0.15


def _block(lead, cfg):
    d, F = cfg.d_model, cfg.d_ff
    q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    return {"norm1": {"scale": lead + (d,)},
            "core": {"wq": lead + (d, q), "wk": lead + (d, kv),
                     "wv": lead + (d, kv), "wo": lead + (q, d)},
            "norm2": {"scale": lead + (d,)},
            "ffn": {"w_gate": lead + (d, F), "w_up": lead + (d, F),
                    "w_down": lead + (F, d)}}


def shapes(cfg):
    n = len(cfg.block_pattern)
    cycles = cfg.num_layers // n
    d, V = cfg.d_model, cfg.vocab_size
    return {"embed": (V, d), "final_norm": {"scale": (d,)},
            "lm_head": (d, V),
            "layers_scan": {f"pos{p}": _block((cycles,), cfg)
                            for p in range(n)},
            "layers_tail": tuple(_block((), cfg) for _ in
                                 range(cfg.num_layers - cycles * n))}


def std(path: str, cfg) -> float:
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "embed":
        return 0.02
    if leaf == "lm_head":
        return 1.28 / cfg.d_model ** 0.5
    if leaf == "scale":
        return 0.1
    if leaf == "wo":
        return (cfg.num_heads * cfg.head_dim) ** -0.5
    if leaf == "w_down":
        return cfg.d_ff ** -0.5
    return cfg.d_model ** -0.5


def layer(stack, i, x, *, kind, cfg, fp8):
    if kind != "attn" or cfg.rope != "full" or cfg.qkv_bias or cfg.is_moe:
        raise ValueError("dense_gqa has dense global attention with full "
                         "rotary and no qkv bias only")

    def w(*path):
        a = stack
        for p in path:
            a = a[p]
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False
                                            ).astype(jnp.float32)

    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.arange(T)
    lin = partial(ein, "td,de->te", fp8=fp8, axes=(-1, 0))

    h = rms(x, w("norm1", "scale"), cfg.norm_eps)
    q = rotary(lin(h, w("core", "wq")).reshape(T, Hq, D), pos, D,
               cfg.rope_theta)
    k = rotary(lin(h, w("core", "wk")).reshape(T, Hkv, D), pos, D,
               cfg.rope_theta)
    v = lin(h, w("core", "wv")).reshape(T, Hkv, D)
    x = x + lin(causal_attention(q, k, v, fp8), w("core", "wo"))

    h = rms(x, w("norm2", "scale"), cfg.norm_eps)
    F = cfg.d_ff
    nb = blocks(F, 4096)

    def mlp(acc, j):
        def cols(name, axis):
            a = jax.lax.dynamic_index_in_dim(stack["ffn"][name], i,
                                             keepdims=False)
            return jax.lax.dynamic_slice_in_dim(
                a, j * (F // nb), F // nb, axis=axis).astype(jnp.float32)
        g = jax.nn.silu(lin(h, cols("w_gate", 1))) * lin(h, cols("w_up", 1))
        return acc + lin(g, cols("w_down", 0)), None

    out, _ = jax.lax.scan(mlp, jnp.zeros_like(x), jnp.arange(nb))
    return x + out


def layer_matmul_params(cfg) -> int:
    d, q = cfg.d_model, cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    return 2 * d * q + 2 * d * kv + 3 * d * cfg.d_ff


def weight_bytes(cfg, w: int = 2) -> int:
    d = cfg.d_model
    return w * (cfg.num_layers * (layer_matmul_params(cfg) + 2 * d) + d
                + d * cfg.vocab_size)


def kv_bytes_per_token(cfg, w: int = 2) -> int:
    return w * 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim


def _attention_flops(cfg, pairs: int) -> int:
    return 4 * cfg.num_layers * cfg.num_heads * cfg.head_dim * pairs


def prefill_flops(cfg, tokens: int) -> int:
    return (2 * tokens * cfg.num_layers * layer_matmul_params(cfg)
            + _attention_flops(cfg, tokens * (tokens + 1) // 2)
            + 2 * cfg.d_model * cfg.vocab_size)


def decode_flops(cfg, batch: int, ctx_sum: int) -> int:
    return (2 * batch * (cfg.num_layers * layer_matmul_params(cfg)
                         + cfg.d_model * cfg.vocab_size)
            + _attention_flops(cfg, ctx_sum + batch))


def decode_bytes(cfg, batch: int, ctx_sum: int, w: int = 2) -> int:
    kv = kv_bytes_per_token(cfg, w)
    return (weight_bytes(cfg, w) + kv * (ctx_sum + batch)
            + w * batch * cfg.d_model)
