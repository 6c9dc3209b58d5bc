"""Composable model definition: init / forward / cache for all families.

Layers are stacked per cycle-position of ``cfg.block_pattern`` and executed
with ``lax.scan`` over full pattern cycles (remainder layers are unrolled),
with ``jax.checkpoint`` on the cycle body — this keeps 64-layer 512-device
lowering tractable and bounds activation memory.

Forward modes:
  * training / encoder forward:  full sequence, no cache
  * prefill:                     full sequence, returns a decode cache
  * decode:                      T == 1 step against an existing cache
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ATTN, LOCAL_ATTN, RGLRU, RWKV6, ModelConfig
from repro.models import layers as L
from repro.models.layers import MeshInfo

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# Init
# --------------------------------------------------------------------------- #
def _init_block(key, cfg: ModelConfig, kind: str, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in (ATTN, LOCAL_ATTN):
        core = L.init_attention(k1, cfg, dtype)
    elif kind == RGLRU:
        core = L.init_rglru(k1, cfg, dtype)
    elif kind == RWKV6:
        core = L.init_rwkv6(k1, cfg, dtype)
    else:  # pragma: no cover
        raise ValueError(kind)
    if kind == RWKV6:
        ffn = L.init_channel_mix(k2, cfg, dtype)
    elif cfg.is_moe:
        ffn = L.init_moe(k2, cfg, dtype)
    else:
        ffn = L.init_mlp(k2, cfg, dtype)
    return {
        "norm1": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "core": core,
        "norm2": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "ffn": ffn,
    }


def _split_layers(cfg: ModelConfig) -> Tuple[int, int]:
    """(number of full pattern cycles scanned, number of tail layers)."""
    plen = len(cfg.block_pattern)
    n_full = cfg.num_layers // plen
    n_tail = cfg.num_layers - n_full * plen
    return n_full, n_tail


def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                device: Optional[jax.Device] = None) -> Params:
    """Random weights for ``cfg``, built in one jitted program.

    The layers of each scanned pattern position are made by vmapping the
    block init over their keys, so the stacked leaves are written once:
    no per-layer copies are held beside the stack.  ``device`` places the
    output there directly (the default device when None).
    """
    out = SingleDeviceSharding(device) if device is not None else None
    return jax.jit(functools.partial(_init_params, cfg=cfg, dtype=dtype),
                   out_shardings=out)(key)


def _init_params(key, cfg: ModelConfig, dtype) -> Params:
    n_full, n_tail = _split_layers(cfg)
    plen = len(cfg.block_pattern)
    keys = jax.random.split(key, 4)

    params: Params = {
        "embed": jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), dtype) * 0.02,
        "final_norm": {"scale": jnp.zeros((cfg.d_model,), dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size), dtype) * 0.02
    if cfg.frontend_dim:
        params["frontend"] = jax.random.normal(
            keys[2], (cfg.frontend_dim, cfg.d_model), dtype) * 0.02

    # layer l = c * plen + pos for cycle c, then the tail layers
    layer_keys = jax.random.split(keys[3], cfg.num_layers)
    params["layers_scan"] = {
        f"pos{pos}": jax.vmap(functools.partial(
            _init_block, cfg=cfg, kind=cfg.block_pattern[pos], dtype=dtype))(
                layer_keys[pos:n_full * plen:plen])
        for pos in range(plen)
    }
    params["layers_tail"] = tuple(
        _init_block(layer_keys[n_full * plen + i], cfg,
                    cfg.block_pattern[i % plen], dtype)
        for i in range(n_tail)
    )
    return params


# --------------------------------------------------------------------------- #
# Cache
# --------------------------------------------------------------------------- #
def _block_cache(cfg: ModelConfig, kind: str, lead: Tuple[int, ...],
                 batch: int, max_len: int, dtype, device) -> Params:
    """Zero cache of one block kind; ``lead`` prefixes every leaf's shape
    (the layer axis of scanned blocks)."""
    def zeros(shape, dt=dtype):
        return jnp.zeros(lead + shape, dt, device=device)

    if kind in (ATTN, LOCAL_ATTN):
        s = max_len if kind == ATTN else cfg.sliding_window
        shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
        return {"k": zeros(shape), "v": zeros(shape)}
    if kind == RGLRU:
        return {
            "conv": zeros((batch, L.CONV_WIDTH - 1, cfg.d_model)),
            "h": zeros((batch, cfg.d_model)),
        }
    if kind == RWKV6:
        return {
            "shift": zeros((batch, cfg.d_model)),
            "state": zeros((batch, cfg.num_heads, cfg.head_dim, cfg.head_dim),
                           jnp.float32),
        }
    raise ValueError(kind)  # pragma: no cover


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32, device: Optional[jax.Device] = None
               ) -> Params:
    """Zero decode cache, allocated on ``device`` (default device if None)."""
    n_full, n_tail = _split_layers(cfg)
    plen = len(cfg.block_pattern)
    scan_cache = {
        f"pos{pos}": _block_cache(cfg, cfg.block_pattern[pos], (n_full,),
                                  batch, max_len, dtype, device)
        for pos in range(plen)
    }
    tail_cache = tuple(
        _block_cache(cfg, cfg.block_pattern[i % plen], (), batch, max_len,
                     dtype, device)
        for i in range(n_tail)
    )
    return {"scan": scan_cache, "tail": tail_cache}


def grow_cache(cfg: ModelConfig, cache: Params, max_len: int) -> Params:
    """Pad a prefill-returned cache so global-attention blocks have room for
    ``max_len`` total positions (local/ring + recurrent caches are fixed)."""
    plen = len(cfg.block_pattern)

    def pad_kv(kind, c, stacked):
        if kind != ATTN or c is None:
            return c
        axis = 2 if stacked else 1
        cur = c["k"].shape[axis]
        if cur >= max_len:
            return c
        pad = [(0, 0)] * c["k"].ndim
        pad[axis] = (0, max_len - cur)
        return {"k": jnp.pad(c["k"], pad), "v": jnp.pad(c["v"], pad)}

    scan = {
        f"pos{p}": pad_kv(cfg.block_pattern[p], cache["scan"][f"pos{p}"], True)
        for p in range(plen)
    } if cache["scan"] is not None else None
    tail = tuple(
        pad_kv(cfg.block_pattern[i % plen], c, False)
        for i, c in enumerate(cache["tail"]))
    return {"scan": scan, "tail": tail}


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _apply_block(
    kind: str,
    cfg: ModelConfig,
    bp: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    layer_cache: Optional[Params],
    cache_len: Optional[jnp.ndarray],
    mi: MeshInfo,
    return_cache: bool,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    h = L.rms_norm(bp["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL_ATTN):
        window = cfg.sliding_window if kind == LOCAL_ATTN else 0
        core, new_cache = L.attention_block(
            bp["core"], cfg, h, positions, window=window,
            layer_cache=layer_cache, cache_len=cache_len, mi=mi,
            return_cache=return_cache)
    elif kind == RGLRU:
        core, new_cache = L.rglru_block(
            bp["core"], cfg, h, layer_cache, mi, return_cache)
    elif kind == RWKV6:
        core, new_cache = L.rwkv6_block(
            bp["core"], cfg, h, layer_cache, mi, return_cache)
    else:  # pragma: no cover
        raise ValueError(kind)
    x = x + core

    h = L.rms_norm(bp["norm2"], x, cfg.norm_eps)
    if kind == RWKV6:
        ffn = L.channel_mix(bp["ffn"], h, mi)
    elif cfg.is_moe:
        ffn = L.moe_block(bp["ffn"], cfg, h, mi)
    else:
        ffn = L.mlp_block(bp["ffn"], h, mi)
    return x + ffn, new_cache


def _default_positions(cfg: ModelConfig, batch: int, seqlen: int,
                       num_patches: int = 0) -> jnp.ndarray:
    if cfg.rope == "mrope":
        if num_patches:
            g = max(1, int(num_patches ** 0.5))
            pi = jnp.arange(num_patches)
            patch_pos = jnp.stack([jnp.zeros_like(pi), pi // g, pi % g], -1)
            tj = jnp.arange(seqlen - num_patches) + g
            text_pos = jnp.stack([tj, tj, tj], -1)
            pos = jnp.concatenate([patch_pos, text_pos], axis=0)
        else:
            t = jnp.arange(seqlen)
            pos = jnp.stack([t, t, t], -1)
        return jnp.broadcast_to(pos, (batch,) + pos.shape)
    return jnp.broadcast_to(jnp.arange(seqlen), (batch, seqlen))


def _embed_inputs(params: Params, cfg: ModelConfig,
                  batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    if cfg.modality == "audio":
        return batch["frames"] @ params["frontend"]
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.modality == "vision" and "patches" in batch:
        patch_emb = batch["patches"] @ params["frontend"]
        x = jnp.concatenate([patch_emb, x], axis=1)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    *,
    mi: MeshInfo = MeshInfo(),
    cache: Optional[Params] = None,
    cache_len: Optional[jnp.ndarray] = None,   # (B,) context length so far
    return_cache: bool = False,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Returns (logits, new_cache).

    decode:  batch["tokens"] has T == 1 and ``cache``/``cache_len`` given.
    prefill: full sequence + return_cache=True.
    train:   full sequence, no cache.
    """
    x = _embed_inputs(params, cfg, batch)
    B, T = x.shape[0], x.shape[1]
    decoding = cache is not None and T == 1

    if "positions" in batch:
        positions = batch["positions"]
    elif decoding:
        pos = cache_len[:, None]
        positions = (jnp.repeat(pos[..., None], 3, axis=-1)
                     if cfg.rope == "mrope" else pos)
    else:
        positions = _default_positions(
            cfg, B, T, batch.get("patches", jnp.zeros((1, 0))).shape[1]
            if cfg.modality == "vision" else 0)

    n_full, n_tail = _split_layers(cfg)
    plen = len(cfg.block_pattern)
    want_cache = return_cache or decoding

    def cycle_body(carry, xs):
        xcur = carry
        cyc_params, cyc_cache = xs
        new_caches = {}
        for pos in range(plen):
            kind = cfg.block_pattern[pos]
            lc = cyc_cache[f"pos{pos}"] if cyc_cache is not None else None
            xcur, nc = _apply_block(
                kind, cfg, cyc_params[f"pos{pos}"], xcur, positions,
                lc, cache_len, mi, want_cache)
            new_caches[f"pos{pos}"] = nc if nc is not None else 0
        return xcur, new_caches if want_cache else None

    scan_cache = cache["scan"] if cache is not None else None
    G = mi.remat_group
    if (n_full > 0 and G > 1 and n_full % G == 0 and cache is None
            and not want_cache):
        # sqrt-L remat: checkpoint every G cycles; activation checkpoints
        # drop from n_full to n_full/G at the cost of one extra forward of
        # each G-block during backward (§Perf H4)
        n_outer = n_full // G
        stacked = jax.tree.map(
            lambda a: a.reshape((n_outer, G) + a.shape[1:]),
            params["layers_scan"])

        def outer_body(xcur, xs_outer):
            # NESTED remat: the inner cycles must checkpoint too, else the
            # outer block's backward holds every cycle's internals live
            def inner(x2, xs):
                x2, _ = cycle_body(x2, (xs, None))
                return x2, None
            x2, _ = jax.lax.scan(jax.checkpoint(inner), xcur, xs_outer)
            return x2, None

        x, _ = jax.lax.scan(jax.checkpoint(outer_body), x, stacked)
        new_scan_cache = None
    elif n_full > 0:
        body = jax.checkpoint(cycle_body)
        if mi.unroll_layers:
            # python loop: per-layer FSDP all-gathers stay inside the step
            # (XLA hoists them out of a lax.scan, defeating the sharding)
            caches_per_cycle = []
            for c in range(n_full):
                cyc_p = jax.tree.map(lambda a: a[c], params["layers_scan"])
                cyc_c = (jax.tree.map(lambda a: a[c], scan_cache)
                         if scan_cache is not None else None)
                x, nc = body(x, (cyc_p, cyc_c))
                caches_per_cycle.append(nc)
            new_scan_cache = (
                jax.tree.map(lambda *xs: jnp.stack(xs), *caches_per_cycle)
                if want_cache else None)
        else:
            x, new_scan_cache = jax.lax.scan(
                body, x, (params["layers_scan"], scan_cache))
    else:
        new_scan_cache = None

    new_tail = []
    for i in range(n_tail):
        kind = cfg.block_pattern[i % plen]
        lc = cache["tail"][i] if cache is not None else None
        x, nc = _apply_block(kind, cfg, params["layers_tail"][i], x,
                             positions, lc, cache_len, mi, want_cache)
        new_tail.append(nc)

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head
    logits = L.soft_cap(logits, cfg.logit_soft_cap)

    new_cache = None
    if want_cache:
        new_cache = {"scan": new_scan_cache, "tail": tuple(new_tail)}
    return logits, new_cache


# --------------------------------------------------------------------------- #
# Loss
# --------------------------------------------------------------------------- #
def make_loss_fn(cfg: ModelConfig, mi: MeshInfo = MeshInfo()):
    """Next-token CE for decoders; per-frame label CE for encoders."""

    def loss_fn(params, batch):
        logits, _ = forward(params, cfg, batch, mi=mi)
        labels = batch["labels"]
        if not cfg.is_encoder:
            logits = logits[:, :-1]
            labels = labels[:, 1:]
        if logits.shape[1] != labels.shape[1]:
            # vlm: patches were prepended; score only the text positions
            logits = logits[:, -labels.shape[1]:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)

    return loss_fn
