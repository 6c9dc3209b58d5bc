"""RG-LRU linear-recurrence kernel (RecurrentGemma/Griffin hot spot).

h_t = exp(log_a_t) * h_{t-1} + b_t, elementwise over channels.

TPU-native tiling: grid ``(batch, d_blocks, t_blocks)`` — time innermost
and sequential, carrying the channel-block state h in VMEM scratch; the
channel dimension is lane-aligned (block_d multiple of 128) and each
(log_a, b) tile streams HBM->VMEM once.  The in-block time loop is a
``fori_loop`` over VPU elementwise ops (this recurrence has no matmul, so
the MXU is idle by construction — the kernel exists to keep the scan OFF
the XLA while-loop path, which would round-trip h through HBM every
step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(la_ref, b_ref, h0_ref, o_ref, h_ref, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    # sequential in-VMEM loop over the time block, one (1, block_d) row
    # of VPU elementwise work per step; h stays in vregs
    def step(t, h):
        row = pl.ds(t, 1)
        h = jnp.exp(la_ref[0, row, :]) * h + b_ref[0, row, :]
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t, step, h_ref[...])


def rglru_scan(
    log_a: jnp.ndarray,       # (B, T, d) f32
    b: jnp.ndarray,           # (B, T, d) f32
    h0: jnp.ndarray = None,   # (B, d) initial state
    *,
    block_t: int = 256,
    block_d: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, d = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, d), jnp.float32)

    block_t = min(block_t, T)
    block_d = min(block_d, d)
    nt = -(-T // block_t)
    nd = -(-d // block_d)
    Tp, dp = nt * block_t, nd * block_d
    if (Tp, dp) != (T, d):
        # pad time with identity steps (log_a=0 would scale; use b=0 and
        # log_a=0 -> h unchanged), channels with zeros
        log_a = jnp.pad(log_a, ((0, 0), (0, Tp - T), (0, dp - d)))
        b = jnp.pad(b, ((0, 0), (0, Tp - T), (0, dp - d)))
        h0 = jnp.pad(h0, ((0, 0), (0, dp - d)))

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t),
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, block_t, block_d),
                         lambda bi, di, ti: (bi, ti, di)),
            # h0 as (B, 1, d): the block's last two dims then equal the
            # array's or are lane-aligned, as Mosaic requires
            pl.BlockSpec((1, 1, block_d), lambda bi, di, ti: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d),
                               lambda bi, di, ti: (bi, ti, di)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(log_a, b, h0[:, None, :])
    return out[:, :T, :d]
