"""Dry-run core: lower + compile one (arch x shape x mesh) combination and
record memory / cost / collective analysis.  Import this ONLY from a
process whose XLA_FLAGS already force the wanted device count (see
``dryrun.py``)."""
from __future__ import annotations

import json
import math
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.input_specs import INPUT_SHAPES, applicable
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.launch.steps import (build_decode_step, build_prefill_step,
                                build_train_step)
from repro.roofline.analysis import TPU_V5E, roofline_terms
from repro.roofline.hlo_costs import analyze_hlo


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode D=batch."""
    n = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens            # forward only
    return 2.0 * n * shape.global_batch    # decode: one token per request


def run_dryrun(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mesh=None,
    dump_hlo_dir: Optional[str] = None,
    variant: str = "baseline",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    n_chips = mesh.devices.size

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": n_chips, "variant": variant,
    }
    skip = applicable(cfg, shape)
    if skip:
        result["status"] = "skipped"
        result["reason"] = skip
        return result

    mi = mesh_info(mesh, global_batch=shape.global_batch)
    # perf-iteration variants (§Perf in EXPERIMENTS.md)
    import dataclasses as _dc
    if "kv_headdim" in variant:
        mi = _dc.replace(mi, kv_shard="head_dim")
    if "fsdp" in variant:
        mi = _dc.replace(mi, fsdp_params=True)
    if "unroll" in variant:
        mi = _dc.replace(mi, unroll_layers=True)
    if "remat8" in variant:
        mi = _dc.replace(mi, remat_group=8)
    try:
        t0 = time.time()
        # f32 on purpose: the CPU backend legalizes bf16 compute by
        # inserting wholesale f32 conversions (copies of params + KV cache)
        # that the TPU target would never materialize.  We lower in f32 and
        # report bf16-projected memory/collective terms (/2) alongside raw.
        dt = jnp.float32
        if shape.kind == "train":
            step, sds, in_sh, out_sh = build_train_step(cfg, mi, shape, dt)
            donate = (0, 1)           # params + optimizer state
        elif shape.kind == "prefill":
            step, sds, in_sh, out_sh = build_prefill_step(cfg, mi, shape, dt)
            donate = ()
        else:
            step, sds, in_sh, out_sh = build_decode_step(cfg, mi, shape, dt)
            donate = (1,)             # KV cache updated in place

        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*sds)
            compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # instruction-level re-derivation: XLA's cost_analysis counts while
        # (layer-scan) bodies once; analyze_hlo multiplies by trip counts
        hc = analyze_hlo(hlo)

        flops_dev = hc.flops
        bytes_dev = hc.hbm_bytes
        wire_bytes = hc.wire_bytes
        # bf16 projection: every tensor in the f32-lowered program is 2 bytes
        # on the bf16 TPU target; compute stays (MXU bf16 rate).  Adam m/v &
        # softmax accumulators would stay f32 (~small undercount, documented)
        terms = roofline_terms(flops_dev, bytes_dev / 2, wire_bytes / 2)
        terms_raw_f32 = roofline_terms(flops_dev, bytes_dev, wire_bytes)
        mf = model_flops(cfg, shape)
        flops_global = flops_dev * n_chips

        result.update({
            "status": "ok",
            "compile_seconds": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": (
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)),
                # f32-lowered; bf16 target halves it (see dtype note above)
                "peak_bytes_bf16_projected": (
                    getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)) / 2,
                "fits_hbm": (getattr(mem, "argument_size_in_bytes", 0)
                             + getattr(mem, "temp_size_in_bytes", 0)) / 2
                            < TPU_V5E.hbm_bytes,
            },
            "cost": {
                "flops_per_device": flops_dev,
                "bytes_per_device": bytes_dev,
                "wire_bytes_per_device": wire_bytes,
                "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
                "xla_cost_analysis_bytes": float(
                    cost.get("bytes accessed", 0.0)),
            },
            "roofline": terms,
            "roofline_raw_f32": terms_raw_f32,
            "model_flops": mf,
            "useful_flops_ratio": (mf / flops_global) if flops_global else 0.0,
            "collective_ops": hc.collectives,
        })
        if dump_hlo_dir:
            os.makedirs(dump_hlo_dir, exist_ok=True)
            fn = os.path.join(
                dump_hlo_dir, f"{arch}_{shape_name}_{mesh_name}.hlo.txt")
            with open(fn, "w") as f:
                f.write(hlo)
            result["hlo_path"] = fn
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    return result


def _summarize_collectives(ops):
    summary: Dict[str, Dict[str, float]] = {}
    for op in ops:
        s = summary.setdefault(op["kind"], {"count": 0, "wire_bytes": 0.0})
        s["count"] += op["trips"]
        s["wire_bytes"] += op["wire_bytes"]
    return summary


def save_result(result: Dict[str, Any], out_dir: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{result['arch']}_{result['shape']}_{result['mesh']}"
            f"_{result.get('variant', 'baseline')}.json")
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return path
