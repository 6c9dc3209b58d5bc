"""Operations and bytes a served step needs, from the model's shapes.

Counts are of what the algorithm needs, not of what the program happens
to compute: causal attention over the valid context only, the output head
on the rows whose logits are used, the key/value cache read over each
slot's valid context.  A program that does more (reads the whole padded
cache, projects every prompt row onto the vocabulary) shows as a lower
share of its roofline.  ``cfg`` is a ``repro.configs.base.ModelConfig``;
``w`` is the weight size in bytes (2 for bfloat16).
"""
from __future__ import annotations


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


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
