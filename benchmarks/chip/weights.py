"""The benchmark's own weights, drawn on the device from ``--seed`` in one
jitted call, in the type they are served in, laid out as the program's
parameter tree (``repro.models`` with one scanned block position):

    embed (V, d), lm_head (d, V), final_norm.scale (d,)
    layers_scan.pos0: norm1.scale, norm2.scale (L, d)
                      core: wq (L, d, Hq*D), wk/wv (L, d, Hkv*D),
                            wo (L, Hq*D, d), bq/bk/bv
                      ffn: w_gate/w_up (L, d, F), w_down (L, F, d)

The program serves these in place of the ones it draws itself, and the
plain reference reads the same arrays, so the reference takes nothing the
program made.  Norm scales and qkv biases are nonzero so that the
correctness check sees them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
HEAD_LOGIT_STD = 1.28      # lm_head std * sqrt(d): unit-order logit spread
NORM_STD = 0.1
BIAS_STD = 0.3


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole number, which may exceed 32 bits."""
    import numpy as np
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])


def shapes(cfg):
    """Leaf shapes of the parameter tree for a ``ModelConfig``."""
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


def _std(path: str, cfg) -> float:
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


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and bool(x) and isinstance(x[0], int)


def _draw(key, cfg, dtype):
    tree = shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        leaves.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                        dtype) * jnp.asarray(
                                            _std(name, cfg), dtype))
    treedef = jax.tree_util.tree_structure(tree, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make(cfg, seed: int, dtype, device):
    """All weights for ``cfg`` on ``device``, from ``seed``, in one call."""
    from functools import partial
    from jax.sharding import SingleDeviceSharding

    fn = jax.jit(partial(_draw, cfg=cfg, dtype=dtype),
                 out_shardings=SingleDeviceSharding(device))
    return fn(jax.random.key(jax_seed(seed)))


def check_layout(ours, theirs) -> None:
    """Refuse to serve weights whose tree, shapes or types differ from the
    program's own parameter tree."""
    a = jax.tree.map(lambda x: (x.shape, x.dtype), ours)
    b = jax.tree.map(lambda x: (x.shape, x.dtype), theirs)
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or a != b:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"{a}\nvs\n{b}")
