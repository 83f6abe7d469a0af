"""The readings that set the limits of ``correct`` (not run by the
benchmark's own runs): for each seed, one short run of the cell as
``run.py`` makes it, which reads the program's numbers, and the control,
the reference computed in float32 (the nearest precision below the
configuration's float64) put in the program's place and reduced by the
same ``run.judge``, which has to find it not correct.

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 --seconds 5

Each seed runs in a process of its own, one after the other: an engine
that stops does not give its device memory back, so a second SF10 table
in one process would not fit on the chip. This parent never touches JAX.
Each seed prints one line; the exit code is not 0 where a program run is
not correct or a control is.
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__" and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = os.path.dirname(HERE)


def one_seed(workload: str, seed: int, seconds: float) -> int:
    import numpy as np

    from benchmark import run

    run._place_compile_cache()
    res = run.run_cell(workload, seed, seconds, control_dtype=np.float32, out=lambda s: None)
    print(json.dumps({"seed": seed, "correct": res["correct"], "attempted": res["attempted"],
                      "checks": res["checks"], "control": res["control"]}), flush=True)
    if res["control"]["correct"] is not False:
        print(f"readings.py: the control came out correct on seed {seed}", file=sys.stderr)
        return 1
    return 0 if res["correct"] else 1


def main(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", help="comma-separated: one process each")
    p.add_argument("--seed", type=int, help="one seed, in this process")
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    if args.seed is not None:
        return one_seed(args.workload, args.seed, args.seconds)
    rc = 0
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--workload", args.workload,
               "--seed", seed, "--seconds", str(args.seconds)]
        rc = max(rc, subprocess.run(cmd).returncode)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
