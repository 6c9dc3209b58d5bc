"""Knee sweep of a cell, run once on the chip when a cell is made.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 0.6,0.8,1.0 \
        --seed <n> [--seconds 51]

Serves the cell's traffic at each offered rate, in one process, without
the correctness check, and prints the end-to-end numbers per rate.  The
knee is the highest rate whose SLO attainment stays at or above 0.9 with
every request finished in the drain; a cell below the knee is set at about
four fifths of it, an overload cell above it.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--drain", type=float, default=None,
                    help="drain limit in seconds (default: the mix's); an "
                    "overload cell's knee is found with one")
    args = ap.parse_args()

    import run
    import spec
    from runner import run_cell

    bench = spec.benchmark()
    cell = spec.load_cell(args.workload, bench)
    if not run.chips_ok(cell.chips):
        return 1
    seconds = args.seconds or bench["run_seconds"]
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        res = run_cell(cell, seed=args.seed, seconds=seconds, trace=False,
                       t_process=t0, rate=rate, drain_s=args.drain,
                       verify=False)
        x = res["extra"]
        print(json.dumps({"rate": rate, "due": x["due"],
                          "finished": x["finished"], "e2e": x["e2e"],
                          "run_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
