"""Plain float32 reference of the served decoder: the embedding, each
layer in the program's order by its architecture's module
(``archs/<architecture>.py``, ``layer``), the final norm and the output
head.  Written from the published description and sharing no code with
the program; it reads the benchmark's own weights (``weights.py``),
upcast layer by layer so the model never needs to fit in float32 at once.
The helpers here (``ein``, ``rms``, ``rotary``, ``blocks``,
``causal_attention``) are for the architecture modules to share.

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


def ein(spec, a, b, fp8, axes):
    if fp8:
        a, b = _fq(a, axes[0]), _fq(b, axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def rotary(x, pos, rot_dim, theta):
    """Rotate-half rotary embedding on the first ``rot_dim`` features of
    each head; x (T, H, D), pos (T,)."""
    half = rot_dim // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:rot_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot_dim:]], axis=-1)


def blocks(n: int, most: int) -> int:
    """The fewest blocks of at most ``most`` that divide n evenly, so no
    whole float32 copy of a large weight is ever held."""
    k = -(-n // most)
    while n % k:
        k += 1
    return k


def causal_attention(q, k, v, fp8):
    """Causal attention of q (T, Hq, D) over k, v (T, Hkv, D), each group of
    Hq / Hkv query heads on one key/value head, one BLOCK of queries at a
    time; (T, Hq * D)."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    pos = jnp.arange(T)
    qb = q.reshape(T // BLOCK, BLOCK, Hkv, Hq // Hkv, D)

    def attend(i):
        s = ein("qhgd,shd->hgqs", qb[i], k, fp8, (-1, -1)) * D ** -0.5
        qpos = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("hgqs,shd->qhgd", p, v, fp8, (-1, 0))

    return jax.lax.map(attend, jnp.arange(T // BLOCK)).reshape(T, Hq * D)


def _head(final_scale, head, h, read, *, cfg, fp8):
    """Per row of h: the largest logit, its token, and the logits of the
    tokens in ``read`` (k, n); the vocabulary is taken in column blocks."""
    h = rms(h, final_scale.astype(jnp.float32), cfg.norm_eps)
    V = head.shape[1]
    vb = V // blocks(V, 8192)

    def block(carry, j):
        best, arg, got = carry
        w = jax.lax.dynamic_slice_in_dim(head, j * vb, vb, axis=1)
        logits = ein("td,dv->tv", h, w.astype(jnp.float32), fp8, (-1, 0))
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


def layer_stacks(params, cfg):
    """Each layer in order as (stacked weights, index, block kind), where
    the program keeps it: pattern position p of cycle c at
    ``layers_scan.pos<p>``, index c; the layers after the last whole cycle
    in ``layers_tail``, given a layer axis of one here."""
    pattern = cfg.block_pattern
    n = len(pattern)
    scanned = cfg.num_layers // n * n
    for layer in range(cfg.num_layers):
        kind = pattern[layer % n]
        if layer < scanned:
            yield (params["layers_scan"][f"pos{layer % n}"], layer // n,
                   kind)
        else:
            tail = params["layers_tail"][layer - scanned]
            yield jax.tree.map(lambda a: a[None], tail), 0, kind


class Reference:
    """One forward pass of ``cfg`` over a token sequence, layer by layer;
    ``arch`` is the configuration's architecture module."""

    def __init__(self, params, cfg, arch, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        fp8 = mode == "fp8"
        self.params, self.cfg = params, cfg
        self.device = params["embed"].devices().pop()
        self._layer = jax.jit(partial(arch.layer, cfg=cfg, fp8=fp8),
                              static_argnames="kind")
        self._head = jax.jit(partial(_head, cfg=cfg, fp8=fp8))

    def hidden(self, tokens) -> jax.Array:
        """Residual stream after the last layer, (T padded to BLOCK, d)."""
        T = len(tokens)
        pad = -(-T // BLOCK) * BLOCK
        toks = np.zeros(pad, np.int32)
        toks[:T] = tokens
        toks = jax.device_put(toks, self.device)
        x = jnp.take(self.params["embed"], toks, axis=0).astype(jnp.float32)
        for stack, i, kind in layer_stacks(self.params, self.cfg):
            x = self._layer(stack, i, x, kind=kind)
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
