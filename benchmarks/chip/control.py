"""Readings that set a cell's correctness limit, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 51]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(same window, same sample), reading the widest logit gap of the served
tokens (the program) and of the tokens a float8 computation of the
reference puts first at the same positions (the control), each judged by
the comparison that decides a run's ``correct``, at the cell's own limit.
Prints one JSON line per seed and a summary line; exits 1 where a control
comes out correct or the program does not.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()

    import run
    import spec
    from runner import run_cell

    bench = spec.benchmark()
    cell = spec.load_cell(args.workload, bench)
    if not run.chips_ok(cell.chips):
        return 1
    seconds = args.seconds or bench["run_seconds"]
    prog, ctl, verdicts = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run_cell(cell, seed=seed, seconds=seconds, trace=False,
                       t_process=t0, control=True)
        x = res["extra"]
        prog.append(x["widest"])
        ctl.append(x["widest_control"])
        verdicts.append((x["program_correct"], res["correct"]))
        print(json.dumps({"seed": seed, "program": x["widest"],
                          "program_correct": x["program_correct"],
                          "control": x["widest_control"],
                          "control_correct": res["correct"],
                          "checks": res["checks"],
                          "sample": x["sample"], "e2e": x["e2e"],
                          "finished": x["finished"], "due": x["due"],
                          "run_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": cell.name, "program_max": max(prog),
                      "control_min": min(ctl), "program": prog,
                      "control": ctl, "verdicts": verdicts}), flush=True)
    return 0 if all(p and not c for p, c in verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
