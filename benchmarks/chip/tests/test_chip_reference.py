"""The plain float32 reference against the program, on the CPU at small
widths, for every configuration in ``BENCHMARK.json`` with the first cell
that runs it: the same logits as the program's float32 forward pass, and,
through a whole harness run (prefill, admission into the batched cache,
decode through the cache, greedy feedback, all via ``PaDGServer``),
served tokens judged correct while the float8 control, judged by the same
comparison in their place, is not.  The small widths and the limit at
them (``TINY``, ``TINY_GAP_LIMIT``) are the configuration's architecture
module's."""
import jax.numpy as jnp
import numpy as np
import pytest

import chip_tiny
import spec

BENCH = spec.benchmark()
CONFIGS = {c["name"]: next(w["name"] for w in BENCH["workloads"]
                           if w["config"] == c["name"])
           for c in BENCH["configs"]}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_reference_matches_program_forward(config):
    from repro.models import forward

    cell = chip_tiny.load(CONFIGS[config])
    cfg = chip_tiny.tiny_config(cell)
    params = chip_tiny.draw(cell, cfg, 2**32 + 11, jnp.float32)
    toks = np.random.default_rng(0).integers(2, cfg.vocab_size, 700)
    want, _ = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})
    want = np.asarray(want[0])
    ref = chip_tiny.reference(cell, params, cfg)
    rows = np.arange(0, 700, 7)
    top, arg, got = ref.head(ref.hidden(toks), rows,
                             np.asarray([toks[rows]], np.int32))
    assert np.max(np.abs(top - want[rows].max(-1))) < 1e-4
    assert np.array_equal(arg, want[rows].argmax(-1))
    assert np.max(np.abs(got[0] - want[rows, toks[rows]])) < 1e-4


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_served_tokens_agree_and_control_fails(config):
    limit = chip_tiny.load(CONFIGS[config]).arch.TINY_GAP_LIMIT
    cell = chip_tiny.tiny_cell(CONFIGS[config], limit=limit, rate_rps=4.0)
    res = chip_tiny.run_tiny(cell, control=True)
    x = res["extra"]
    assert x["finished"] > 0
    assert x["program_correct"] is True, x["program_checks"]
    # the run's verdict is the control's, by the comparison that judges
    # every run
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] == x["widest_control"]
    assert x["widest_control"] > limit
