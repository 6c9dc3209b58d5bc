"""Small-size runs of the harness on the CPU for the tests: a cell's own
files, with every width shrunk and a short window, driven through
``runner.run_cell`` (the harness's look for a chip is skipped)."""
import dataclasses
import os
import sys
import time

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(CHIP, "..", "..", "src")
for p in (SRC, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

SEED = 2**31 + 7


def load(name):
    """A cell by its name in BENCHMARK.json."""
    return spec.load_cell(name)


def tiny_config(cell, **override):
    """The cell's ``ModelConfig`` at its architecture's small widths."""
    return spec.model_config(cell.config, **dict(cell.arch.TINY, **override))


def draw(cell, cfg, seed, dtype):
    """The benchmark's weights for ``cfg`` on the default device."""
    import jax
    import weights

    return weights.make(cell.arch, cfg, seed, dtype, jax.devices()[0])


def reference(cell, params, cfg, mode="f32"):
    import reference as ref

    return ref.Reference(params, cfg, cell.arch, mode)


def tiny_cell(name="chatglm3-6b.sharegpt", limit=None, **params):
    cell = load(name)
    mix = dict(cell.mix, ladder=dict(cell.mix["ladder"], top=256),
               drain_s=min(cell.mix["drain_s"], 30) or 0)
    p = dict(cell.params, slots=4, positions=512,
             warmup={"requests": 4, "output_tokens": 4})
    p["check"] = dict(p["check"], least_tokens=10, sample_tokens=100)
    if limit is not None:
        p["check"]["widest_gap_limit"] = limit
    p.update(params)
    return dataclasses.replace(cell, mix=mix, params=p)


def run_tiny(cell, seed=SEED, seconds=2.0, trace=False, control=False,
             override=None):
    """One run at small widths; the persistent compilation cache the
    runner turns on is turned back off for the rest of the test process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.simulator.cost_model import (HARDWARE_BY_DEVICE_KIND,
                                            InstanceCostModel)
    from runner import run_cell

    tiny = dict(cell.arch.TINY, **(override or {}))
    cfg = spec.model_config(cell.config, **tiny)
    cm = InstanceCostModel(cfg=cfg, hw=HARDWARE_BY_DEVICE_KIND["TPU v5 lite"])
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        return run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                        t_process=time.perf_counter(), cfg_override=tiny,
                        cost_model=cm, control=control)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
