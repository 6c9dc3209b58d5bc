"""Pins of what the one cell's configuration draws and computes, taken
before the harness's architecture-specific code moved into its own
module: the weights (a checksum per leaf) and the reference's logits at
fixed rows of a fixed prompt, for ChatGLM3 at the CPU tests' widths.
A change that moves or shares harness code must leave both as they
are."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

import chip_tiny

SEED = 2**33 + 5
# leaf path: (sum in float64, first 16 hex digits of the sha256 of its
# float32 bytes)
LEAVES = {
    "embed": (8.655813395432318, "a2b402d92d261168"),
    "final_norm/scale": (0.653992679392104, "ac48002ee8f37968"),
    "layers_scan/pos0/core/bk": (6.858383066777606, "fcd2edd52d307fef"),
    "layers_scan/pos0/core/bq": (-2.1205976124620065, "352dfb506f6bbf1d"),
    "layers_scan/pos0/core/bv": (-1.0655804706038907, "5adefe907ddddbac"),
    "layers_scan/pos0/core/wk": (2.33434148401102, "289d5cc801decc81"),
    "layers_scan/pos0/core/wo": (-0.45975182987103835, "38779faf2a809b69"),
    "layers_scan/pos0/core/wq": (13.116380065478097, "343e0332bc1da4bc"),
    "layers_scan/pos0/core/wv": (20.62661945638432, "cefdf96994b80488"),
    "layers_scan/pos0/ffn/w_down": (-8.810883473639706, "979ecbc1c92a5c7e"),
    "layers_scan/pos0/ffn/w_gate": (22.859781213975758, "7999150c5b42e6ee"),
    "layers_scan/pos0/ffn/w_up": (-39.21674975798103, "6c5a3a29dae12bca"),
    "layers_scan/pos0/norm1/scale": (-0.0068655817740364, "20f759cacb836689"),
    "layers_scan/pos0/norm2/scale": (1.1285259512951598, "96179d8650d6441b"),
    "lm_head": (-29.89512634291509, "6c035942cbf306d5"),
}
ROWS = [3, 100, 197, 294, 391, 488, 585, 682]
TOP = [3.783677577972412, 4.698156833648682, 4.353097438812256,
       4.838695526123047, 4.6681904792785645, 4.791590690612793,
       4.577181816101074, 4.468367576599121]
ARGMAX = [490, 509, 490, 509, 509, 509, 85, 509]


def _cell_and_config():
    cell = chip_tiny.load("chatglm3-6b.sharegpt")
    return cell, chip_tiny.tiny_config(cell)


def test_weights_are_pinned():
    cell, cfg = _cell_and_config()
    params = chip_tiny.draw(cell, cfg, SEED, jnp.float32)
    got = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(a)
        got["/".join(str(getattr(p, "key", p)) for p in path)] = (
            float(np.sum(a, dtype=np.float64)),
            hashlib.sha256(a.tobytes()).hexdigest()[:16])
    assert got == LEAVES


def test_reference_logits_are_pinned():
    cell, cfg = _cell_and_config()
    params = chip_tiny.draw(cell, cfg, SEED, jnp.float32)
    toks = np.random.default_rng(5).integers(2, cfg.vocab_size, 700)
    ref = chip_tiny.reference(cell, params, cfg)
    top, arg, _ = ref.head(ref.hidden(toks), np.asarray(ROWS),
                           np.asarray([toks[ROWS]], np.int32))
    assert arg.tolist() == ARGMAX
    # float32 on the CPU: the same code on the same machine gives the same
    # bits; the room is for another CPU's order of summation in the
    # products, far below what a change to the layer's arithmetic moves
    # (the top two logits of these rows lie 0.09-1.0 apart)
    np.testing.assert_allclose(top, TOP, rtol=0, atol=2e-5)
