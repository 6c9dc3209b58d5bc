"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; the harness reads

    configs/<config>.json   the model as it is run (every ``ModelConfig``
                            field it sets), its architecture, source, cuts
    archs/<architecture>.py what differs between architectures: the
                            weight tree and scales, the reference of one
                            layer, the step counts, the CPU tests' widths
    mixes/<traffic>.json    lengths, prompt-length ladder, arrivals, SLO
    cells/<cell>.json       rate, instances and engine geometry
    metrics/<metric>.py     one reader per per-layer metric
    arrivals/<kind>.py      one arrival process per kind a mix names

so a later change adds a cell, a mix, a metric, or a configuration of an
architecture already here or a new one (its module and a configuration
file that names it), by adding files and entries, without editing any
file that is here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, as a module; ValueError where
    there is no such file."""
    path = HERE / kind / f"{name}.py"
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name) or (
            not path.is_file()):
        raise ValueError(f"no {kind}/{name}.py for {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"[.-]", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(BENCHMARK_JSON)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict          # configs/<config>.json
    arch: object          # archs/<architecture>.py, as a module
    mix: dict             # mixes/<traffic>.json
    params: dict          # cells/<cell>.json
    end_to_end: tuple     # the metric entries this cell reports
    per_layer: tuple

    @property
    def overload(self) -> bool:
        return self.mix["drain_s"] == 0

    @property
    def instances(self) -> int:
        return self.params["instances"]

    @property
    def rate(self) -> float:
        return self.params["rate_rps"]


def _reports(entry: dict, cell: str) -> bool:
    ws = entry.get("workloads")
    return ws is None or cell in ws


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(by_name)}")
    w = by_name[name]
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if m["moves"] in e2e_names and _reports(m, name))
    config = load_json(HERE / "configs" / f"{w['config']}.json")
    return Cell(
        name=name, config_name=w["config"], traffic=w["traffic"],
        chips=w["chips"], config=config,
        arch=load_module("archs", config["architecture"]),
        mix=load_json(HERE / "mixes" / f"{w['traffic']}.json"),
        params=load_json(HERE / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=layer)


def model_config(config: dict, **override):
    """The program's ``ModelConfig`` from a configuration file's ``model``
    fields (``block_pattern`` a list of block kinds); ``override``
    replaces fields (the CPU tests shrink widths this way)."""
    from repro.configs.base import ModelConfig

    fields = dict(config["model"], **override)
    fields["block_pattern"] = tuple(fields["block_pattern"])
    return ModelConfig(name=fields.pop("name", "bench"),
                       citation=config["paper"], **fields)
