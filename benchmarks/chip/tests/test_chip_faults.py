"""A run with the timed path broken underneath must come out not correct:
for each fault a served one-chip cell can have, the harness runs on the
CPU at a small size (its look for a chip skipped) with the fault planted
in the program's decode step, and ``correct`` must be false.  The fault
of a missing exchange between chips has no place in these cells: their
instances exchange nothing.

Each faulty run checks every request it finished, and the half of the
batch left out is the slots' first half, which every run fills (a request
that finds the engine idle lands in slot 0): which slots a run fills
beyond that, and which requests a sample holds, follow the host's
timing, and a fault that only those would show came and went with it."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import chip_tiny
import repro.serving.engine as engine_mod

SERVING_STEPS = engine_mod.serving_steps


def _faulty_steps(fault):
    def steps(cfg):
        prefill, _ = SERVING_STEPS(cfg)

        def decode(params, cache, toks, lengths):
            logits, new = engine_mod._decode_step(params, cache, toks,
                                                  lengths, cfg=cfg)
            if fault == "state_unchanged":
                return logits, cache
            if fault == "half_batch":
                b = logits.shape[0]
                return logits.at[:b // 2].set(logits[b - b // 2:]), new
            if fault == "token_altered":
                return jnp.roll(logits, 1, axis=-1), new
            raise ValueError(fault)

        donate = () if fault == "state_unchanged" else (1,)
        return prefill, jax.jit(decode, donate_argnums=donate)
    return steps


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_makes_run_not_correct(fault, monkeypatch):
    cell = chip_tiny.tiny_cell(rate_rps=4.0)
    every = dict(cell.params["check"], most_requests=10**6,
                 sample_tokens=10**9)
    cell = dataclasses.replace(cell, params=dict(cell.params, check=every))
    limit = every["widest_gap_limit"]
    monkeypatch.setattr(engine_mod, "serving_steps", _faulty_steps(fault))
    res = chip_tiny.run_tiny(cell)
    assert res["extra"]["finished"] > 0
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] > limit


def test_sound_run_is_correct():
    cell = chip_tiny.tiny_cell(rate_rps=4.0)
    res = chip_tiny.run_tiny(cell)
    assert res["correct"] is True, res["checks"]
