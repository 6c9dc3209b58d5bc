"""Architectures are found by name: a configuration file names its
module ``archs/<architecture>.py``, a name with no such file is refused,
and a second architecture (``fixtures/second_arch/``: a dense GQA decoder
with full rotary and no qkv bias, laid out as two pattern positions and a
tail layer at its small widths) is served from a copy of the harness to
which it was added by new files and entries alone: its served tokens
judged correct against its own reference, the float8 control not, and
no file the copy shares with the harness edited."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_tiny
import spec

FIXTURE = os.path.join(chip_tiny.CHIP, "fixtures", "second_arch")
CELL = "dense-gqa.sharegpt"
SCRIPT = """
import json, sys
sys.path.insert(0, sys.argv[1])
import chip_tiny, spec
cell = chip_tiny.load(sys.argv[2])
limit = cell.arch.TINY_GAP_LIMIT
res = chip_tiny.run_tiny(chip_tiny.tiny_cell(cell.name, limit=limit,
                                             rate_rps=4.0), control=True)
x = res["extra"]
print(json.dumps({"spec": spec.__file__, "arch": cell.arch.__file__,
                  "limit": limit, "finished": x["finished"],
                  "program": x["widest"],
                  "program_correct": x["program_correct"],
                  "control": x["widest_control"], "correct": res["correct"],
                  "metrics": sorted(res["metrics"])}))
"""


def test_unknown_architecture_is_refused(monkeypatch):
    load_json = spec.load_json

    def renamed(path):
        got = load_json(path)
        if path.parent.name == "configs":
            got = dict(got, architecture="no_such_arch")
        return got

    monkeypatch.setattr(spec, "load_json", renamed)
    with pytest.raises(ValueError, match=r"no archs/no_such_arch\.py"):
        spec.load_cell("chatglm3-6b.sharegpt")


def _add_files(fixture, harness):
    """Copy each file under ``fixture`` to the same place under
    ``harness``, where none may be yet."""
    added = []
    for kind in ("archs", "configs", "cells"):
        for name in os.listdir(os.path.join(fixture, kind)):
            dst = os.path.join(harness, kind, name)
            assert not os.path.exists(dst), dst
            shutil.copyfile(os.path.join(fixture, kind, name), dst)
            added.append(dst)
    return added


def _add_entries(bench, entries):
    """The fixture's configuration and cell appended, and the cell added
    to every metric that lists its cells."""
    out = json.loads(json.dumps(bench))
    out["configs"] += entries["configs"]
    out["workloads"] += entries["workloads"]
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [w["name"] for w in entries["workloads"]]
    return out


def _extends(new, old):
    """``new`` is ``old`` with items appended to its lists, if any."""
    if isinstance(old, dict):
        return new.keys() == old.keys() and all(
            _extends(new[k], v) for k, v in old.items())
    if isinstance(old, list):
        return len(new) >= len(old) and all(
            _extends(a, b) for a, b in zip(new, old))
    return new == old


def test_second_architecture_by_files_alone(tmp_path):
    root = os.path.dirname(os.path.dirname(chip_tiny.CHIP))
    harness = tmp_path / "benchmarks" / "chip"
    shutil.copytree(chip_tiny.CHIP, harness, ignore=shutil.ignore_patterns(
        "__pycache__", "_trace"))
    bench = spec.benchmark()
    with open(os.path.join(FIXTURE, "entries.json")) as fh:
        entries = json.load(fh)
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(_add_entries(bench, entries), fh, indent=1)
    added = _add_files(FIXTURE, str(harness))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / ".jax_cache"))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(harness / "tests"), CELL],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])

    assert got["spec"] == str(harness / "spec.py")
    assert got["arch"] == str(harness / "archs" / "dense_gqa.py")
    assert got["finished"] > 0
    assert got["program_correct"] is True, got
    assert got["correct"] is False and got["control"] > got["limit"], got
    assert got["metrics"] == ["setup_s", "tpot_p50_s", "ttft_p50_s"]

    # nothing the copy shares with the harness was edited
    for dirpath, dirnames, files in os.walk(chip_tiny.CHIP):
        dirnames[:] = [d for d in dirnames if d not in ("__pycache__",
                                                        "_trace")]
        rel = os.path.relpath(dirpath, chip_tiny.CHIP)
        for f in files:
            assert filecmp.cmp(os.path.join(dirpath, f),
                               os.path.join(harness, rel, f), shallow=False)
    assert len(added) == 3
    with open(tmp_path / "BENCHMARK.json") as fh:
        assert _extends(json.load(fh), bench)
