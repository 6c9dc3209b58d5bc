"""The plain float32 reference against the program, on the CPU at small
widths: the same logits as the program's float32 forward pass (half
rotary, GQA, qkv bias), and, through a whole harness run (prefill,
admission into the batched cache, decode through the cache, greedy
feedback, all via ``PaDGServer``), served tokens judged correct while the
float8 control, judged by the same comparison in their place, is not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_tiny
import reference
import spec
import weights

CONFIGS = {"chatglm3-6b": "chatglm3-6b.sharegpt"}
# The widest-gap limit at this small size (2 layers, d_model 128), set as
# the cells' limits are: sound runs read 0.005-0.034 and the float8
# control 0.31-0.48 over three seeds on the CPU, so the limit lies
# between, nearer the control.  The cell's own limit (1.0) cannot serve
# here: at the cell's size, 28 layers of width 4096, the control's
# rounding grows to 2.9-7.2, and ``control.py`` shows it failing there.
SMALL_SIZE_LIMIT = 0.15


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_reference_matches_program_forward(config):
    from repro.models import forward

    cell = chip_tiny.load(CONFIGS[config])
    cfg = spec.model_config(cell.config, **chip_tiny.TINY)
    params = weights.make(cfg, 2**32 + 11, jnp.float32, jax.devices()[0])
    toks = np.random.default_rng(0).integers(2, cfg.vocab_size, 700)
    want, _ = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})
    want = np.asarray(want[0])
    ref = reference.Reference(params, cfg)
    rows = np.arange(0, 700, 7)
    top, arg, got = ref.head(ref.hidden(toks), rows,
                             np.asarray([toks[rows]], np.int32))
    assert np.max(np.abs(top - want[rows].max(-1))) < 1e-4
    assert np.array_equal(arg, want[rows].argmax(-1))
    assert np.max(np.abs(got[0] - want[rows, toks[rows]])) < 1e-4


@pytest.mark.parametrize("name", sorted(CONFIGS.values()))
def test_served_tokens_agree_and_control_fails(name):
    cell = chip_tiny.tiny_cell(name, limit=SMALL_SIZE_LIMIT, rate_rps=4.0)
    res = chip_tiny.run_tiny(cell, control=True)
    x = res["extra"]
    assert x["finished"] > 0
    assert x["program_correct"] is True, x["program_checks"]
    # the run's verdict is the control's, by the comparison that judges
    # every run
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] == x["widest_control"]
    assert x["widest_control"] > SMALL_SIZE_LIMIT
