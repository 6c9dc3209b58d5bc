"""The benchmark's own weights, drawn on the device from ``--seed`` in one
jitted call, in the type they are served in, laid out as the program's
parameter tree.  The configuration's architecture module
(``archs/<architecture>.py``) gives each leaf's shape (``shapes``) and
scale (``std``); each leaf is drawn from its own ``fold_in`` of the key,
in tree order.

The program serves these in place of the ones it draws itself, and the
plain reference reads the same arrays, so the reference takes nothing the
program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole number, which may exceed 32 bits."""
    import numpy as np
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and bool(x) and isinstance(x[0], int)


def _draw(key, arch, cfg, dtype):
    tree = arch.shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        leaves.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                        dtype) * jnp.asarray(
                                            arch.std(name, cfg), dtype))
    treedef = jax.tree_util.tree_structure(tree, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make(arch, cfg, seed: int, dtype, device):
    """All weights for ``cfg`` of architecture module ``arch`` on
    ``device``, from ``seed``, in one call."""
    from functools import partial
    from jax.sharding import SingleDeviceSharding

    fn = jax.jit(partial(_draw, arch=arch, cfg=cfg, dtype=dtype),
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
