"""Decode (KV-cache) attention kernel — the serving memory-bound hot spot.

One new token per sequence attends over its cached context.  Grid:
``(batch, kv_head, kv_blocks)`` with the kv dimension innermost and
sequential; online-softmax state for the G grouped query heads lives in
VMEM scratch.  The KV cache streams HBM->VMEM exactly once (this is the
traffic the roofline's decode memory term is made of); q is tiny and
stays resident.  Valid-length masking handles ragged batches (continuous
batching) and ring buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_s: int):
    b = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                  # (G, D)
    k = k_ref[0, 0]                  # (block_s, D)
    v = v_ref[0, 0]
    d = q.shape[-1]
    valid_len = len_ref[b]           # scalar-prefetched into SMEM

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (d ** -0.5)   # (G, block_s)
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < valid_len, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,          # (B, Hq, D) one new token per sequence
    k_cache: jnp.ndarray,    # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,    # (B,) valid cache entries per sequence
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv

    block_s = min(block_s, S)
    ns = -(-S // block_s)
    Sp = ns * block_s
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)

    qg = q.reshape(B, Hkv, G, D)
    kg = jnp.moveaxis(k_cache, 2, 1)      # (B, Hkv, Sp, D)
    vg = jnp.moveaxis(v_cache, 2, 1)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    # index maps receive the scalar-prefetched lengths as a trailing arg
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, ns),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, si, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_s, D),
                             lambda b, h, si, _: (b, h, si, 0)),
                pl.BlockSpec((1, 1, block_s, D),
                             lambda b, h, si, _: (b, h, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, si, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        **kwargs,
    )(lengths.astype(jnp.int32), qg, kg, vg)
    return out.reshape(B, Hq, D)
