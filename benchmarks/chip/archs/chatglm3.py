"""The ChatGLM3 family: a dense decoder of pre-norm RMSNorm blocks,
grouped-query attention with qkv bias and rotary embeddings on the first
half of each head, and a SwiGLU MLP, written from the published
description.

A configuration file names its architecture (``"architecture":
"chatglm3"``), and the harness loads ``archs/<architecture>.py`` by that
name.  Such a module holds what differs between architectures, under
these names:

    TINY                    ``ModelConfig`` fields that shrink the
                            configuration for the CPU tests
    TINY_GAP_LIMIT          the widest-gap limit of the CPU tests at those
                            widths, set between sound runs' readings and
                            the float8 control's there
    shapes(cfg)             leaf shapes of the program's parameter tree
    std(path, cfg)          the scale of the leaf at ``path`` ("a/b/c")
    layer(stack, i, x, *, kind, cfg, fp8)
                            the float32 reference of one layer: the
                            ``i``-th of the stacked weights ``stack``, of
                            block kind ``kind``, on the residual stream
                            ``x`` (T, d), T a multiple of
                            ``reference.BLOCK``; every product through
                            ``reference.ein`` so that ``fp8`` rounds it
    layer_matmul_params(cfg), weight_bytes(cfg, w=2),
    kv_bytes_per_token(cfg, w=2), prefill_flops(cfg, tokens),
    decode_flops(cfg, batch, ctx_sum), decode_bytes(cfg, batch, ctx_sum,
    w=2)                    the counts of a served step, by the rules in
                            ``counts.py``

``cfg`` is the program's ``ModelConfig``.  The shared code (``weights``,
``reference``, ``counts``) draws, walks the layers, takes the head and
judges; a module imports its helpers (``reference.rms``, ``rotary``,
``blocks``, ``causal_attention``) rather than copying them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import blocks, causal_attention, ein, rms, rotary

TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            head_dim=32, d_ff=256, vocab_size=512)
# sound runs read 0.005-0.034 and the float8 control 0.31-0.48 over three
# seeds at these widths on the CPU, so the limit lies between, nearer the
# control.  The cell's own limit (1.0) cannot serve here: at the cell's
# size, 28 layers of width 4096, the control's rounding grows to 2.9-7.2
TINY_GAP_LIMIT = 0.15

EMBED_STD = 0.02
HEAD_LOGIT_STD = 1.28      # lm_head std * sqrt(d): unit-order logit spread
NORM_STD = 0.1
BIAS_STD = 0.3


def shapes(cfg):
    """The program's tree (``repro.models``, one scanned block position):

        embed (V, d), lm_head (d, V), final_norm.scale (d,)
        layers_scan.pos0: norm1.scale, norm2.scale (L, d)
                          core: wq (L, d, Hq*D), wk/wv (L, d, Hkv*D),
                                wo (L, Hq*D, d), bq/bk/bv
                          ffn: w_gate/w_up (L, d, F), w_down (L, F, d)
    """
    L, d, F, V = cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    core = {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
            "wo": (L, q, d)}
    if cfg.qkv_bias:
        core.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    return {
        "embed": (V, d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, V),
        "layers_scan": {"pos0": {
            "norm1": {"scale": (L, d)},
            "core": core,
            "norm2": {"scale": (L, d)},
            "ffn": {"w_gate": (L, d, F), "w_up": (L, d, F),
                    "w_down": (L, F, d)},
        }},
        "layers_tail": (),
    }


def std(path: str, cfg) -> float:
    """Norm scales and qkv biases are nonzero so that the correctness
    check sees them."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "embed":
        return EMBED_STD
    if leaf == "lm_head":
        return HEAD_LOGIT_STD / cfg.d_model ** 0.5
    if leaf == "scale":
        return NORM_STD
    if leaf.startswith("b"):
        return BIAS_STD
    if leaf == "wo":
        return (cfg.num_heads * cfg.head_dim) ** -0.5
    if leaf == "w_down":
        return cfg.d_ff ** -0.5
    return cfg.d_model ** -0.5


def layer(stack, i, x, *, kind, cfg, fp8):
    if kind != "attn" or cfg.rope != "half" or cfg.is_moe:
        raise ValueError(f"chatglm3 has dense global attention with half "
                         f"rotary only, not {kind!r} with rope "
                         f"{cfg.rope!r}, {cfg.num_experts} experts")

    def w(*path):
        a = stack
        for p in path:
            a = a[p]
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False
                                            ).astype(jnp.float32)

    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rot = D // 2
    pos = jnp.arange(T)
    lin = partial(ein, "td,de->te", fp8=fp8, axes=(-1, 0))

    h = rms(x, w("norm1", "scale"), cfg.norm_eps)
    q = lin(h, w("core", "wq"))
    k = lin(h, w("core", "wk"))
    v = lin(h, w("core", "wv"))
    if cfg.qkv_bias:
        q = q + w("core", "bq")
        k = k + w("core", "bk")
        v = v + w("core", "bv")
    q = rotary(q.reshape(T, Hq, D), pos, rot, cfg.rope_theta)
    k = rotary(k.reshape(T, Hkv, D), pos, rot, cfg.rope_theta)
    o = causal_attention(q, k, v.reshape(T, Hkv, D), fp8)
    x = x + lin(o, w("core", "wo"))

    h = rms(x, w("norm2", "scale"), cfg.norm_eps)
    F = cfg.d_ff
    nb = blocks(F, 4096)
    ffn = stack["ffn"]

    def mlp(acc, j):
        def cols(a, axis):
            a = jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
            return jax.lax.dynamic_slice_in_dim(
                a, j * (F // nb), F // nb, axis=axis).astype(jnp.float32)
        g = (jax.nn.silu(lin(h, cols(ffn["w_gate"], 1)))
             * lin(h, cols(ffn["w_up"], 1)))
        return acc + lin(g, cols(ffn["w_down"], 0)), None

    out, _ = jax.lax.scan(mlp, jnp.zeros_like(x), jnp.arange(nb))
    return x + out


def layer_matmul_params(cfg) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, f = cfg.d_model, cfg.d_ff
    q = cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    return d * q + 2 * d * kv + q * d + 3 * d * f


def weight_bytes(cfg, w: int = 2) -> int:
    """Bytes of the weights a decode step reads: every layer's matrices,
    biases and norms, the final norm and the output head (the embedding
    rows it gathers are counted per token in ``decode_bytes``)."""
    d = cfg.d_model
    q = cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg.qkv_bias:
        per_layer += q + 2 * kv
    return w * (cfg.num_layers * per_layer + d + d * cfg.vocab_size)


def kv_bytes_per_token(cfg, w: int = 2) -> int:
    return w * 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim


def attention_flops(cfg, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` (query, key) pairs, all
    layers."""
    return 4 * cfg.num_layers * cfg.num_heads * cfg.head_dim * pairs


def prefill_flops(cfg, tokens: int) -> int:
    """One prompt of ``tokens``: every layer on every row, causal attention,
    and the head on the last row (the only logits a prefill uses)."""
    pairs = tokens * (tokens + 1) // 2
    return (2 * tokens * cfg.num_layers * layer_matmul_params(cfg)
            + attention_flops(cfg, pairs)
            + 2 * cfg.d_model * cfg.vocab_size)


def decode_flops(cfg, batch: int, ctx_sum: int) -> int:
    """One decode step over ``batch`` slots whose contexts sum to
    ``ctx_sum`` before the step (each new token attends to ctx + 1)."""
    return (2 * batch * (cfg.num_layers * layer_matmul_params(cfg)
                         + cfg.d_model * cfg.vocab_size)
            + attention_flops(cfg, ctx_sum + batch))


def decode_bytes(cfg, batch: int, ctx_sum: int, w: int = 2) -> int:
    """One decode step: the weights once, each slot's valid cache read,
    one token's keys and values written per slot, the embedding rows."""
    kv = kv_bytes_per_token(cfg, w)
    return (weight_bytes(cfg, w) + kv * ctx_sum + kv * batch
            + w * batch * cfg.d_model)
