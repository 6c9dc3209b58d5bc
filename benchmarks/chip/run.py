"""Chip benchmark of the served path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Runs on the machine it is started on and needs as many TPU chips as the
cell asks for; without them it exits non-zero and prints no result.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness check
compared, with its limit.  The same numbers end standard error.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, whatever the environment says; the program takes its cache
# directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def chips_ok(chips: int) -> bool:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return False
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import spec
    cell = spec.load_cell(args.workload)
    if not chips_ok(cell.chips):
        return 1
    from runner import run_cell
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS)
    result.pop("extra")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
