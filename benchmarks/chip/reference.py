"""Plain float32 reference of the served decoder (the ChatGLM3 family):
pre-norm RMSNorm blocks, grouped-query attention with qkv bias and rotary
embeddings on the first half of each head, and a SwiGLU MLP.  Written from
the published description and sharing no code with the program; it reads
the benchmark's own weights (``weights.py``), upcast layer by layer so the
model never needs to fit in float32 at once.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes.  ``mode="fp8"`` is the control:
the same computation with every product's operands rounded to float8
(e4m3, one scale per row or column along the contraction), the precision
below the configuration's bfloat16.

Sequences are padded to a multiple of ``BLOCK`` with causal attention, so
padding never reaches the rows that are read, and one program serves each
padded length.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512              # query block and sequence padding
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fq(x, axis):
    """Round x to float8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _ein(spec, a, b, fp8, axes):
    if fp8:
        a, b = _fq(a, axes[0]), _fq(b, axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rotary(x, pos, rot_dim, theta):
    """Rotate-half rotary embedding on the first ``rot_dim`` features of
    each head; x (T, H, D), pos (T,)."""
    half = rot_dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:rot_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot_dim:]], axis=-1)


def _blocks(n: int, most: int) -> int:
    """The fewest blocks of at most ``most`` that divide n evenly, so no
    whole float32 copy of a large weight is ever held."""
    k = -(-n // most)
    while n % k:
        k += 1
    return k


def _layer(stack, l, x, *, cfg, fp8):
    def w(*path):
        a = stack
        for p in path:
            a = a[p]
        return jax.lax.dynamic_index_in_dim(a, l, keepdims=False
                                            ).astype(jnp.float32)

    T = x.shape[0]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = Hq // Hkv
    rot = D // 2
    pos = jnp.arange(T)
    lin = partial(_ein, "td,de->te", fp8=fp8, axes=(-1, 0))

    h = _rms(x, w("norm1", "scale"), cfg.norm_eps)
    q = lin(h, w("core", "wq"))
    k = lin(h, w("core", "wk"))
    v = lin(h, w("core", "wv"))
    if cfg.qkv_bias:
        q = q + w("core", "bq")
        k = k + w("core", "bk")
        v = v + w("core", "bv")
    q = _rotary(q.reshape(T, Hq, D), pos, rot, cfg.rope_theta)
    k = _rotary(k.reshape(T, Hkv, D), pos, rot, cfg.rope_theta)
    v = v.reshape(T, Hkv, D)
    qb = q.reshape(T // BLOCK, BLOCK, Hkv, G, D)

    def attend(i):
        s = _ein("qhgd,shd->hgqs", qb[i], k, fp8, (-1, -1)) * D ** -0.5
        qpos = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hgqs,shd->qhgd", p, v, fp8, (-1, 0))

    o = jax.lax.map(attend, jnp.arange(T // BLOCK)).reshape(T, Hq * D)
    x = x + lin(o, w("core", "wo"))

    h = _rms(x, w("norm2", "scale"), cfg.norm_eps)
    F = cfg.d_ff
    nb = _blocks(F, 4096)
    ffn = stack["ffn"]

    def mlp(acc, j):
        def cols(a, axis):
            a = jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
            return jax.lax.dynamic_slice_in_dim(
                a, j * (F // nb), F // nb, axis=axis).astype(jnp.float32)
        g = (jax.nn.silu(lin(h, cols(ffn["w_gate"], 1)))
             * lin(h, cols(ffn["w_up"], 1)))
        return acc + lin(g, cols(ffn["w_down"], 0)), None

    out, _ = jax.lax.scan(mlp, jnp.zeros_like(x), jnp.arange(nb))
    return x + out


def _head(final_scale, head, h, read, *, cfg, fp8):
    """Per row of h: the largest logit, its token, and the logits of the
    tokens in ``read`` (k, n); the vocabulary is taken in column blocks."""
    h = _rms(h, final_scale.astype(jnp.float32), cfg.norm_eps)
    V = head.shape[1]
    vb = V // _blocks(V, 8192)

    def block(carry, j):
        best, arg, got = carry
        w = jax.lax.dynamic_slice_in_dim(head, j * vb, vb, axis=1)
        logits = _ein("td,dv->tv", h, w.astype(jnp.float32), fp8, (-1, 0))
        top = jnp.max(logits, -1)
        local = read - j * vb
        inside = (local >= 0) & (local < vb)
        val = jnp.take_along_axis(logits[None], jnp.clip(local, 0, vb - 1)
                                  [..., None], axis=-1)[..., 0]
        better = top > best
        return (jnp.where(better, top, best),
                jnp.where(better, jnp.argmax(logits, -1) + j * vb, arg),
                jnp.where(inside, val, got)), None

    n = h.shape[0]
    init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,), jnp.int32),
            jnp.zeros(read.shape, jnp.float32))
    out, _ = jax.lax.scan(block, init, jnp.arange(V // vb))
    return out


class Reference:
    """One forward pass of ``cfg`` over a token sequence, layer by layer."""

    def __init__(self, params, cfg, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        if cfg.rope != "half":
            raise ValueError(f"the reference has half rotary only, not "
                             f"{cfg.rope!r}")
        fp8 = mode == "fp8"
        self.params, self.cfg = params, cfg
        self.device = params["embed"].devices().pop()
        self._layer = jax.jit(partial(_layer, cfg=cfg, fp8=fp8))
        self._head = jax.jit(partial(_head, cfg=cfg, fp8=fp8))

    def hidden(self, tokens) -> jax.Array:
        """Residual stream after the last layer, (T padded to BLOCK, d)."""
        T = len(tokens)
        pad = -(-T // BLOCK) * BLOCK
        toks = np.zeros(pad, np.int32)
        toks[:T] = tokens
        toks = jax.device_put(toks, self.device)
        x = jnp.take(self.params["embed"], toks, axis=0).astype(jnp.float32)
        stack = self.params["layers_scan"]["pos0"]
        for layer in range(self.cfg.num_layers):
            x = self._layer(stack, layer, x)
        return x

    def head(self, h, rows, read):
        """(max logit, argmax, logits of ``read`` (k, n)) at ``rows`` of h;
        rows are padded to a multiple of BLOCK to bound the programs."""
        n = len(rows)
        pad = -(-n // BLOCK) * BLOCK
        r = np.zeros(pad, np.int32)
        r[:n] = rows
        rd = np.zeros((len(read), pad), np.int32)
        rd[:, :n] = read
        hs = jnp.take(h, jax.device_put(r, self.device), axis=0)
        out = self._head(self.params["final_norm"]["scale"],
                         self.params["lm_head"], hs,
                         jax.device_put(rd, self.device))
        top, arg, got = (np.asarray(a) for a in out)
        return top[:n], arg[:n], got[:, :n]


def served_gaps(ref: Reference, prompt, served, control: "Reference" = None):
    """For one served request: at each served token, how far its logit lies
    below the reference's best (0 where the reference agrees).  With a
    ``control``, also the same gap for the token the control puts first at
    each of those positions.  Returns (gaps, control_gaps or None)."""
    prompt, served = list(prompt), list(served)
    toks = prompt + served[:-1]
    rows = np.arange(len(prompt) - 1, len(toks))
    h = ref.hidden(toks)
    reads = [served]
    ctrl_tok = None
    if control is not None:
        _, ctrl_tok, _ = control.head(control.hidden(toks), rows, [served])
        reads.append(ctrl_tok)
    top, _, got = ref.head(h, rows, np.asarray(reads, np.int32))
    gaps = top - got[0]
    return gaps, (top - got[1] if control is not None else None)
