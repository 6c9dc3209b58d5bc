"""Composable transformer building blocks (pure functional JAX).

Every function takes an explicit params dict and returns arrays; no
global state.  Blocks come in four kinds (see ``repro.configs.base``):
global attention, sliding-window attention, RG-LRU (Griffin), and RWKV-6.

Attention is computed blockwise over query chunks (flash-style online
softmax) so 32k-token prefills never materialize a (T, T) score matrix.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Dict[str, Any]

Q_CHUNK = 512          # query chunk for blockwise attention
RWKV_CHUNK = 128       # chunk length for the chunked WKV recurrence
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Axis names of the active mesh (None -> single-device execution).

    kv_shard selects the KV-cache layout:
      * "heads":    (B, S, kv->model, hd)  — replicates when kv % model != 0
      * "head_dim": (B, S, kv, hd->model)  — always divides (hd is 128/256);
        QK^T becomes a partial-sum contraction (one small score all-reduce
        per layer) but the cache shards fully (§Perf hillclimb variant)
    """
    mesh: Optional[jax.sharding.Mesh] = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    kv_shard: str = "heads"
    fsdp_params: bool = False   # additionally shard weights over batch axes
    unroll_layers: bool = False  # python loop instead of lax.scan (lets
    #                              FSDP gathers stay per-layer inside)
    remat_group: int = 1         # checkpoint every G cycles (sqrt-L remat)
    #                              instead of every cycle — §Perf H4

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


# --------------------------------------------------------------------------- #
# Small primitives
# --------------------------------------------------------------------------- #
def rms_norm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def _head_rms_norm(scale: jnp.ndarray, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm over the last (head_dim) axis; x: (..., heads, head_dim)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def soft_cap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


# --------------------------------------------------------------------------- #
# Rotary embeddings (full / half / mrope)
# --------------------------------------------------------------------------- #
def _rope_freqs(head_dim: int, theta: float, n_freq: int) -> jnp.ndarray:
    exponent = jnp.arange(0, n_freq, dtype=jnp.float32) / n_freq
    return 1.0 / (theta ** exponent)


def _apply_rotary(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x: (..., 2*n_freq) pairs-first layout; angles: broadcastable (..., n_freq)."""
    n = angles.shape[-1]
    x1, x2 = x[..., :n], x[..., n:2 * n]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
    if x.shape[-1] > 2 * n:  # "half" rope: pass the rest through
        rotated = jnp.concatenate([rotated, x[..., 2 * n:]], axis=-1)
    return rotated


def apply_rope(cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """x: (B, T, heads, head_dim); positions: (B, T) or (B, T, 3) for mrope."""
    hd = x.shape[-1]
    if cfg.rope == "none":
        return x
    if cfg.rope == "half":
        n_freq = hd // 4          # rotary on the first half of head_dim
    else:
        n_freq = hd // 2
    freqs = _rope_freqs(hd, cfg.rope_theta, n_freq)
    if cfg.rope == "mrope":
        # Split frequency slots into (temporal, height, width) sections 2:1:1.
        s1 = n_freq // 2
        s2 = (n_freq - s1) // 2
        s3 = n_freq - s1 - s2
        pos = positions.astype(jnp.float32)           # (B, T, 3)
        ang = jnp.concatenate(
            [
                pos[..., 0:1] * freqs[:s1],
                pos[..., 1:2] * freqs[s1:s1 + s2],
                pos[..., 2:3] * freqs[s1 + s2:],
            ],
            axis=-1,
        )                                             # (B, T, n_freq)
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, T, n_freq)
    return _apply_rotary(x, ang[:, :, None, :])       # broadcast over heads


# --------------------------------------------------------------------------- #
# Blockwise (flash-style) attention — prefill / training path
# --------------------------------------------------------------------------- #
def blockwise_attention(
    q: jnp.ndarray,                # (B, T, Hq, D)
    k: jnp.ndarray,                # (B, S, Hkv, D)
    v: jnp.ndarray,                # (B, S, Hkv, D)
    *,
    causal: bool,
    window: int = 0,               # 0 -> unbounded
    q_offset: int = 0,             # absolute position of q[0] (chunked prefill)
    kv_valid_len: Optional[jnp.ndarray] = None,  # (B,) valid kv length
    q_chunk: int = Q_CHUNK,
) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv                    # query heads per kv head (GQA group)
    scale = D ** -0.5

    q_chunk = min(q_chunk, T)
    n_chunks = -(-T // q_chunk)
    pad = n_chunks * q_chunk - T
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # grouped-query layout: never materialize a repeated KV cache
    qc = q.reshape(B, n_chunks, q_chunk, Hkv, G, D)

    kv_pos = jnp.arange(S)[None, :]                          # (1, S)

    def chunk_fn(carry, inputs):
        idx, q_blk = inputs                            # (B, qc, Hkv, G, D)
        q_pos = q_offset + idx * q_chunk + jnp.arange(q_chunk)  # (qc,)
        s = jnp.einsum("bqhgd,bshd->bhgqs", q_blk, k,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((B, q_chunk, S), dtype=bool)
        if causal:
            mask &= kv_pos[None] <= q_pos[None, :, None]
        if window:
            mask &= kv_pos[None] > q_pos[None, :, None] - window
        if kv_valid_len is not None:
            mask &= kv_pos < kv_valid_len[:, None, None]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        att = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhgqs,bshd->bqhgd", att, v,
                         preferred_element_type=jnp.float32)
        return carry, out.astype(v.dtype)

    _, outs = jax.lax.scan(chunk_fn, None, (jnp.arange(n_chunks),
                                            jnp.swapaxes(qc, 0, 1)))
    out = jnp.swapaxes(outs, 0, 1).reshape(B, n_chunks * q_chunk, Hq, D)
    return out[:, :T]


def decode_attention_jnp(
    q: jnp.ndarray,                # (B, 1, Hq, D)
    k_cache: jnp.ndarray,          # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    kv_valid_len: jnp.ndarray,     # (B,) number of valid cache entries
) -> jnp.ndarray:
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, 1, Hkv, G, D)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_cache,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    mask = jnp.arange(S)[None, :] < kv_valid_len[:, None]    # (B, S)
    s = jnp.where(mask[:, None, None, None], s, NEG_INF)
    att = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgqs,bshd->bqhgd", att, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Attention block (global or sliding-window)
# --------------------------------------------------------------------------- #
def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, hq * hd), dtype) * std,
        "wk": jax.random.normal(k2, (d, hkv * hd), dtype) * std,
        "wv": jax.random.normal(k3, (d, hkv * hd), dtype) * std,
        "wo": jax.random.normal(k4, (hq * hd, d), dtype) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attention_block(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                     # (B, T, d)
    positions: jnp.ndarray,             # (B, T) or (B, T, 3)
    *,
    window: int,                        # 0 for global
    layer_cache: Optional[Params],      # {"k","v"} or None
    cache_len: Optional[jnp.ndarray],   # (B,) tokens already in cache
    mi: MeshInfo,
    return_cache: bool,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    B, T, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, T, hq, hd)
    k = k.reshape(B, T, hkv, hd)
    v = v.reshape(B, T, hkv, hd)
    if cfg.qk_norm:
        q = _head_rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = _head_rms_norm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    if mi.model_axis is not None:
        if mi.kv_shard == "head_dim":
            spec = P(*_bspec(mi), None, None, mi.model_axis)
        else:
            spec = P(*_bspec(mi), None, mi.model_axis, None)
        q = jax.lax.with_sharding_constraint(q, spec)
        k = jax.lax.with_sharding_constraint(k, spec)
        v = jax.lax.with_sharding_constraint(v, spec)

    new_cache = None
    if layer_cache is not None and T == 1:
        # ---- decode: scatter kv into the cache ring and attend over it ----
        S = layer_cache["k"].shape[1]
        idx = (cache_len % S).astype(jnp.int32)              # ring index (B,)
        bidx = jnp.arange(B)
        k_cache = layer_cache["k"].at[bidx, idx].set(k[:, 0])
        v_cache = layer_cache["v"].at[bidx, idx].set(v[:, 0])
        valid = jnp.minimum(cache_len + 1, S)
        out = decode_attention_jnp(q, k_cache, v_cache, valid)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        # ---- prefill / training: blockwise attention over this sequence ----
        causal = not cfg.is_encoder
        out = blockwise_attention(q, k, v, causal=causal, window=window)
        if return_cache:
            if window and window < T:
                # keep only the trailing window in a ring-ordered buffer:
                # position p lives at slot p % window
                tail = jax.lax.dynamic_slice_in_dim(k, T - window, window, axis=1)
                tailv = jax.lax.dynamic_slice_in_dim(v, T - window, window, axis=1)
                shift = T % window
                k_ring = jnp.roll(tail, shift, axis=1)
                v_ring = jnp.roll(tailv, shift, axis=1)
                new_cache = {"k": k_ring, "v": v_ring}
            elif window and window > T:
                # ring buffer sized `window`, slots T..W-1 still empty
                padw = ((0, 0), (0, window - T), (0, 0), (0, 0))
                new_cache = {"k": jnp.pad(k, padw), "v": jnp.pad(v, padw)}
            else:
                new_cache = {"k": k, "v": v}

    out = out.reshape(B, T, hq * hd)
    return out @ params["wo"], new_cache


def _bspec(mi: MeshInfo):
    return (mi.batch_axes,) if mi.batch_axes else (None,)


# --------------------------------------------------------------------------- #
# Gated MLP (SwiGLU)
# --------------------------------------------------------------------------- #
def init_mlp(key, cfg: ModelConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = d ** -0.5
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) * std,
        "w_up": jax.random.normal(k2, (d, f), dtype) * std,
        "w_down": jax.random.normal(k3, (f, d), dtype) * (f ** -0.5),
    }


def mlp_block(params: Params, x: jnp.ndarray, mi: MeshInfo) -> jnp.ndarray:
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    if mi.model_axis is not None:
        h = jax.lax.with_sharding_constraint(
            h, P(*_bspec(mi), None, mi.model_axis))
    return h @ params["w_down"]


# --------------------------------------------------------------------------- #
# Mixture of Experts — expert parallelism over the `model` axis
# --------------------------------------------------------------------------- #
def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    return {
        "router": jax.random.normal(k1, (d, e), dtype) * std,
        "w_gate": jax.random.normal(k2, (e, d, f), dtype) * std,
        "w_up": jax.random.normal(k3, (e, d, f), dtype) * std,
        "w_down": jax.random.normal(k4, (e, f, d), dtype) * (f ** -0.5),
    }


def _moe_local(params: Params, cfg: ModelConfig, x: jnp.ndarray,
               expert_lo: int, n_local: int) -> jnp.ndarray:
    """Capacity-routed MoE over experts [expert_lo, expert_lo+n_local).

    x: (T, d) local tokens.  Returns the partial output contributed by the
    local experts only (caller psums across expert shards).
    """
    T, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    cap = max(1, int(T * k / E * cfg.capacity_factor))

    logits = (x @ params["router"]).astype(jnp.float32)       # (T, E)
    weights, experts = jax.lax.top_k(logits, k)               # (T, k)
    weights = jax.nn.softmax(weights, axis=-1)

    # position of each (token, choice) within its expert's queue
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)      # (T, k, E)
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                     # exclusive cumsum
    pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)          # (T, k)
    keep = pos < cap

    out = jnp.zeros((T, d), jnp.float32)
    for j in range(n_local):
        e = expert_lo + j
        sel = (experts == e) & keep                           # (T, k)
        # slot of each token in expert e's buffer (cap entries)
        slot = jnp.where(sel, pos, cap)                       # cap = dropped
        slot_t = jnp.min(slot, axis=-1)                       # (T,)
        w_t = jnp.sum(jnp.where(sel, weights, 0.0), axis=-1)  # (T,)
        buf = jnp.zeros((cap + 1, d), x.dtype).at[slot_t].add(x)
        buf = buf[:cap]
        h = jax.nn.silu(buf @ params["w_gate"][j]) * (buf @ params["w_up"][j])
        eo = (h @ params["w_down"][j]).astype(jnp.float32)    # (cap, d)
        # gather back: token t reads buffer slot slot_t (if kept)
        gathered = jnp.take(jnp.vstack([eo, jnp.zeros((1, d))]),
                            jnp.minimum(slot_t, cap), axis=0)
        out = out + gathered * w_t[:, None]
    return out


def _moe_local_wtp(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                   expert_lo: int, n_local: int,
                   d_idx, n_d: int, model_axis: str,
                   data_axes) -> jnp.ndarray:
    """Weight-tensor-parallel MoE for the batch-replicated case (batch=1
    long-context decode): each expert's d_model contraction is split over
    the otherwise-idle data axes.  Partial matmuls + psum reconstruct the
    exact math; expert weights shard model*data ways (16x memory).
    Returns the FULL (already psum'ed over model+data) output.
    """
    T, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    f = cfg.d_ff
    cap = max(1, int(T * k / E * cfg.capacity_factor))
    d_loc, f_loc = d // n_d, f // n_d

    logits = (x @ params["router"]).astype(jnp.float32)     # router replicated
    weights, experts = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(weights, axis=-1)
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)
    keep = pos < cap

    x_slice = jax.lax.dynamic_slice_in_dim(x, d_idx * d_loc, d_loc, axis=1)
    out = jnp.zeros((T, d), jnp.float32)
    for j in range(n_local):
        e = expert_lo + j
        sel = (experts == e) & keep
        slot = jnp.where(sel, pos, cap)
        slot_t = jnp.min(slot, axis=-1)
        w_t = jnp.sum(jnp.where(sel, weights, 0.0), axis=-1)
        buf = jnp.zeros((cap + 1, d_loc), x.dtype).at[slot_t].add(x_slice)
        buf = buf[:cap]
        # partial over the d_in contraction -> psum over data axes
        a = jax.lax.psum(buf @ params["w_gate"][j], data_axes)
        b = jax.lax.psum(buf @ params["w_up"][j], data_axes)
        h = jax.nn.silu(a) * b                               # (cap, f) full
        h_slice = jax.lax.dynamic_slice_in_dim(
            h, d_idx * f_loc, f_loc, axis=1)
        eo = (h_slice @ params["w_down"][j]).astype(jnp.float32)  # partial
        gathered = jnp.take(jnp.vstack([eo, jnp.zeros((1, d))]),
                            jnp.minimum(slot_t, cap), axis=0)
        out = out + gathered * w_t[:, None]
    # partial over (f contraction x expert shards)
    return jax.lax.psum(out, (model_axis,) + tuple(data_axes))


def moe_block(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              mi: MeshInfo) -> jnp.ndarray:
    """MoE FFN; experts sharded over the `model` axis via shard_map.

    Activations are replicated across the model axis (Megatron pattern), so
    each model shard routes all its data-shard tokens to *its own* experts
    and the shards' partial outputs are psum'ed — one all-reduce per MoE
    layer, no all-to-all.

    When the batch cannot use the data axes (batch=1 decode) and
    ``mi.fsdp_params`` is set, expert weights additionally split their
    contraction dims over the data axes (weight tensor parallelism) —
    §Perf H3 variant.
    """
    B, T, d = x.shape
    E = cfg.num_experts

    if mi.mesh is None or mi.model_axis is None:
        y = _moe_local(params, cfg, x.reshape(B * T, d), 0, E)
        return y.reshape(B, T, d).astype(x.dtype)

    n_model = mi.model_size
    if E % n_model != 0:
        # experts don't divide the model axis: replicate them and compute
        # the full MoE on every shard (only hit in reduced smoke settings)
        y = _moe_local(params, cfg, x.reshape(B * T, d), 0, E)
        return y.reshape(B, T, d).astype(x.dtype)
    n_local = E // n_model
    batch_ok = bool(mi.batch_axes) and B % _axes_size(mi) == 0
    bspec = mi.batch_axes if batch_ok else None

    data_axes = tuple(a for a in mi.mesh.axis_names if a != mi.model_axis)
    n_d = 1
    for a in data_axes:
        n_d *= mi.mesh.shape[a]
    use_wtp = (mi.fsdp_params and not batch_ok and n_d > 1
               and d % n_d == 0 and cfg.d_ff % n_d == 0)

    def local_fn(p_loc, x_loc):
        lo = jax.lax.axis_index(mi.model_axis) * n_local
        Bl, Tl, _ = x_loc.shape
        if use_wtp:
            d_idx = jnp.zeros((), jnp.int32)
            mult = 1
            for a in reversed(data_axes):
                d_idx = d_idx + jax.lax.axis_index(a) * mult
                mult *= mi.mesh.shape[a]
            y = _moe_local_wtp(p_loc, cfg, x_loc.reshape(Bl * Tl, d),
                               lo, n_local, d_idx, n_d, mi.model_axis,
                               data_axes)
        else:
            y = _moe_local(p_loc, cfg, x_loc.reshape(Bl * Tl, d),
                           lo, n_local)
            y = jax.lax.psum(y, mi.model_axis)
        return y.reshape(Bl, Tl, d).astype(x_loc.dtype)

    pspec = {
        "router": P(),
        "w_gate": P(mi.model_axis, data_axes if use_wtp else None, None),
        "w_up": P(mi.model_axis, data_axes if use_wtp else None, None),
        "w_down": P(mi.model_axis, data_axes if use_wtp else None, None),
    }
    y = jax.shard_map(
        local_fn,
        mesh=mi.mesh,
        in_specs=(
            {k: pspec[k] for k in params},
            P(bspec, None, None),
        ),
        out_specs=P(bspec, None, None),
    )(params, x)
    return y


def _axes_size(mi: MeshInfo) -> int:
    n = 1
    for a in mi.batch_axes:
        n *= mi.mesh.shape[a]
    return n


# --------------------------------------------------------------------------- #
# RG-LRU recurrent block (Griffin / RecurrentGemma)
# --------------------------------------------------------------------------- #
CONV_WIDTH = 4
RGLRU_C = 8.0


def init_rglru(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    std = d ** -0.5
    return {
        "w_x": jax.random.normal(ks[0], (d, d), dtype) * std,
        "w_gate": jax.random.normal(ks[1], (d, d), dtype) * std,
        "w_out": jax.random.normal(ks[2], (d, d), dtype) * std,
        "conv_w": jax.random.normal(ks[3], (CONV_WIDTH, d), dtype) * 0.1,
        "w_in_gate": jax.random.normal(ks[4], (d, d), dtype) * std,
        "w_rec_gate": jax.random.normal(ks[5], (d, d), dtype) * std,
        "lambda": jnp.full((d,), 1.0, dtype),   # softplus(1.0) ~ 1.31
    }


def _rglru_coeffs(params: Params, u: jnp.ndarray):
    """u: (..., d) conv output.  Returns (log_a, gated_input) in f32."""
    i_gate = jax.nn.sigmoid((u @ params["w_in_gate"]).astype(jnp.float32))
    r_gate = jax.nn.sigmoid((u @ params["w_rec_gate"]).astype(jnp.float32))
    log_a = -RGLRU_C * r_gate * jax.nn.softplus(
        params["lambda"].astype(jnp.float32))
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * i_gate * u.astype(jnp.float32)
    return log_a, b


def rglru_scan_jnp(log_a: jnp.ndarray, b: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Associative scan of h_t = exp(log_a_t) * h_{t-1} + b_t over axis 1.

    log_a, b: (B, T, d) float32.  h0: (B, d) initial state or None.
    """
    if h0 is not None:
        log_a = jnp.concatenate([jnp.zeros_like(log_a[:, :1]), log_a], axis=1)
        b = jnp.concatenate([h0[:, None].astype(b.dtype), b], axis=1)

    def op(l, r):
        (la1, b1), (la2, b2) = l, r
        return la1 + la2, b1 * jnp.exp(la2) + b2

    _, h = jax.lax.associative_scan(op, (log_a, b), axis=1)
    return h[:, 1:] if h0 is not None else h


def rglru_block(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                    # (B, T, d)
    layer_cache: Optional[Params],     # {"conv": (B, W-1, d), "h": (B, d)}
    mi: MeshInfo,
    return_cache: bool,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    B, T, d = x.shape
    gate = jax.nn.gelu((x @ params["w_gate"]))
    xin = x @ params["w_x"]

    # temporal conv (width 4, causal)
    if layer_cache is not None and T == 1:
        hist = jnp.concatenate([layer_cache["conv"], xin], axis=1)  # (B, W, d)
        u = jnp.einsum("bwd,wd->bd", hist, params["conv_w"])[:, None]
        new_conv = hist[:, 1:]
    else:
        pad = jnp.zeros((B, CONV_WIDTH - 1, d), xin.dtype)
        hist = jnp.concatenate([pad, xin], axis=1)
        u = jnp.stack(
            [hist[:, i:i + T] for i in range(CONV_WIDTH)], axis=0)
        u = jnp.einsum("wbtd,wd->btd", u, params["conv_w"])
        new_conv = hist[:, -(CONV_WIDTH - 1):]

    log_a, b = _rglru_coeffs(params, u)
    if layer_cache is not None and T == 1:
        h_prev = layer_cache["h"].astype(jnp.float32)
        h = jnp.exp(log_a[:, 0]) * h_prev + b[:, 0]
        y = h[:, None]
        new_cache = {"conv": new_conv, "h": h.astype(x.dtype)}
    else:
        h0 = layer_cache["h"].astype(jnp.float32) if layer_cache else None
        y = rglru_scan_jnp(log_a, b, h0)
        new_cache = (
            {"conv": new_conv, "h": y[:, -1].astype(x.dtype)}
            if return_cache else None
        )
    out = (y.astype(x.dtype) * gate) @ params["w_out"]
    return out, new_cache


# --------------------------------------------------------------------------- #
# RWKV-6 (Finch) time-mix block with data-dependent decay
# --------------------------------------------------------------------------- #
DECAY_LORA = 64


def init_rwkv6(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 10)
    std = d ** -0.5
    return {
        "w_r": jax.random.normal(ks[0], (d, d), dtype) * std,
        "w_k": jax.random.normal(ks[1], (d, d), dtype) * std,
        "w_v": jax.random.normal(ks[2], (d, d), dtype) * std,
        "w_g": jax.random.normal(ks[3], (d, d), dtype) * std,
        "w_o": jax.random.normal(ks[4], (d, d), dtype) * std,
        "mu": jax.random.uniform(ks[5], (4, d), dtype),       # r,k,v,g shifts
        "decay_base": jnp.full((d,), -6.0, dtype),
        "decay_lora_a": jax.random.normal(ks[6], (d, DECAY_LORA), dtype) * std,
        "decay_lora_b": jax.random.normal(
            ks[7], (DECAY_LORA, d), dtype) * (DECAY_LORA ** -0.5),
        "bonus_u": jax.random.normal(ks[8], (cfg.num_heads, hd), dtype) * 0.1,
        "ln_out_scale": jnp.zeros((d,), dtype),
    }


def rwkv6_chunked_jnp(
    r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,   # (B, T, H, D) f32
    w: jnp.ndarray,                                   # (B, T, H, D) decay in (0,1)
    u: jnp.ndarray,                                   # (H, D) bonus
    s0: Optional[jnp.ndarray] = None,                 # (B, H, D, D)
    chunk: int = RWKV_CHUNK,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked linear-attention form of the WKV6 recurrence.

    State S (per head, D_k x D_v):  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    Output: o_t = r_t^T (diag(u) k_t v_t^T + S_{t-1}).
    Returns (o: (B,T,H,D), final state).
    """
    B, T, H, D = r.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    if pad:
        zp = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zp(r), zp(k), zp(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)

    rc = r.reshape(B, n, chunk, H, D)
    kc = k.reshape(B, n, chunk, H, D)
    vc = v.reshape(B, n, chunk, H, D)
    logw = jnp.log(jnp.maximum(w, 1e-12)).reshape(B, n, chunk, H, D)

    s_init = (jnp.zeros((B, H, D, D), jnp.float32) if s0 is None
              else s0.astype(jnp.float32))

    def chunk_fn(S, inp):
        rb, kb, vb, lwb = inp          # (B, c, H, D)
        cum = jnp.cumsum(lwb, axis=1)                  # inclusive decay sums
        # decay from chunk start to just BEFORE step t:
        dec_in = jnp.exp(cum - lwb)                    # (B, c, H, D)
        # contribution of carried-in state: o_intra_state = r_t . (decayed S)
        r_dec = rb * dec_in
        o_state = jnp.einsum("bchd,bhde->bche", r_dec, S)
        # within-chunk token-to-token: A[t,s] = r_t . diag(decay s+1..t-1... )
        # k_s effective: k_s * exp(cum_t - cum_s)  for s < t
        kin = kb * jnp.exp(-(cum))                     # k_s / prod decay <= s
        att = jnp.einsum("bchd,bshd->bhcs", r_dec, kin)
        c_idx = jnp.arange(rb.shape[1])
        causal_mask = c_idx[:, None] > c_idx[None, :]  # strictly lower
        att = jnp.where(causal_mask[None, None], att, 0.0)
        o_intra = jnp.einsum("bhcs,bshd->bchd", att, vb)
        # bonus diagonal term
        o_diag = jnp.einsum("bchd,hd,bchd->bch", rb, u.astype(jnp.float32),
                            kb)[..., None] * vb
        # update state to end of chunk
        dec_all = jnp.exp(cum[:, -1])                  # (B, H, D)
        k_end = kb * jnp.exp(cum[:, -1][:, None] - cum)
        S_new = S * dec_all[..., None] + jnp.einsum(
            "bchd,bche->bhde", k_end, vb)
        return S_new, o_state + o_intra + o_diag

    xs = (jnp.swapaxes(rc, 0, 1), jnp.swapaxes(kc, 0, 1),
          jnp.swapaxes(vc, 0, 1), jnp.swapaxes(logw, 0, 1))
    S_fin, outs = jax.lax.scan(chunk_fn, s_init, xs)
    o = jnp.swapaxes(outs, 0, 1).reshape(B, n * chunk, H, D)[:, :T]
    return o, S_fin


def rwkv6_block(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                   # (B, T, d)
    layer_cache: Optional[Params],    # {"shift": (B, d), "state": (B,H,D,D)}
    mi: MeshInfo,
    return_cache: bool,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    B, T, d = x.shape
    H, D = cfg.num_heads, cfg.head_dim

    if layer_cache is not None and T == 1:
        x_prev = layer_cache["shift"][:, None]
    else:
        first = (layer_cache["shift"][:, None] if layer_cache
                 else jnp.zeros((B, 1, d), x.dtype))
        x_prev = jnp.concatenate([first, x[:, :-1]], axis=1)

    mu = params["mu"]
    mix = lambda i: x * mu[i] + x_prev * (1.0 - mu[i])
    r = (mix(0) @ params["w_r"]).reshape(B, T, H, D).astype(jnp.float32)
    k = (mix(1) @ params["w_k"]).reshape(B, T, H, D).astype(jnp.float32)
    v = (mix(2) @ params["w_v"]).reshape(B, T, H, D).astype(jnp.float32)
    g = jax.nn.silu(mix(3) @ params["w_g"])

    # data-dependent decay (the Finch signature)
    dd = (x @ params["decay_lora_a"]) @ params["decay_lora_b"]
    logit = params["decay_base"].astype(jnp.float32) + dd.astype(jnp.float32)
    w = jnp.exp(-jnp.exp(logit)).reshape(B, T, H, D)          # in (0, 1)

    s0 = layer_cache["state"] if layer_cache is not None else None
    if layer_cache is not None and T == 1:
        # single-step recurrence
        S = s0.astype(jnp.float32)
        o = jnp.einsum("bhd,hd,bhd->bh", r[:, 0], params["bonus_u"].astype(
            jnp.float32), k[:, 0])[..., None] * v[:, 0]
        o = o + jnp.einsum("bhd,bhde->bhe", r[:, 0], S)
        S_new = S * w[:, 0][..., None] + jnp.einsum(
            "bhd,bhe->bhde", k[:, 0], v[:, 0])
        o = o[:, None]
        new_state = S_new
    else:
        o, new_state = rwkv6_chunked_jnp(r, k, v, w, params["bonus_u"])

    o = o.reshape(B, T, d).astype(x.dtype)
    # group norm over heads ~ rms per head group, simplified to rms over d
    o = rms_norm({"scale": params["ln_out_scale"]}, o, cfg.norm_eps)
    out = (o * g) @ params["w_o"]

    new_cache = None
    if return_cache or (layer_cache is not None and T == 1):
        new_cache = {"shift": x[:, -1], "state": new_state.astype(jnp.float32)}
    return out, new_cache


# --------------------------------------------------------------------------- #
# RWKV channel mix (used as the FFN for rwkv blocks)
# --------------------------------------------------------------------------- #
def init_channel_mix(key, cfg: ModelConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_in": jax.random.normal(k1, (d, f), dtype) * d ** -0.5,
        "w_out": jax.random.normal(k2, (f, d), dtype) * f ** -0.5,
    }


def channel_mix(params: Params, x: jnp.ndarray, mi: MeshInfo) -> jnp.ndarray:
    h = jnp.square(jax.nn.relu(x @ params["w_in"]))
    if mi.model_axis is not None:
        h = jax.lax.with_sharding_constraint(
            h, P(*_bspec(mi), None, mi.model_axis))
    return h @ params["w_out"]
