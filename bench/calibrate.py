"""Readings behind the limits of ``correct``: a cell's timed path and its
bfloat16 control over many seeds, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: a full set-up and a short window at the cell's own load,
then the numbers of ``bench/reference.py`` for the program against the
float32 reference and for the bfloat16 reference against the float32
one, on the same sampled products.  Prints one JSON line per seed and a
summary: the largest program reading (the lower end of a limit) and the
smallest control reading (its upper end).  With ``--fault <name>`` the
timed path runs with that fault of ``bench/faults.py`` planted, and the
program's numbers are the fault's readings.  Needs a TPU, like
``run.py``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "traffic")]

import faults  # noqa: E402
import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default="none")
    args = ap.parse_args()

    cell = harness.load_cell(ROOT, args.workload)
    harness.import_program(ROOT)
    harness.configure_jax(ROOT, cell.config)
    import jax

    if jax.default_backend() != "tpu":
        sys.exit("calibrate: no TPU found")
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        tap, patch = faults.FAULTS[args.fault]
        with patch():
            res = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, time.perf_counter(), tap=tap,
                                   control=True)
        row = {"seed": seed, "fault": args.fault, "correct": res["correct"],
               "program": res["numbers"], "control": res["control"],
               "e2e": res["e2e"]}
        print(json.dumps(row), flush=True)
        for k, v in res["numbers"].items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in res["control"].items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
