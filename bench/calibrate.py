"""Readings for the limits of ``correct``: the program's and the float8
control's, over many seeds, in one process (compiled programs are
shared; each seed still makes its own weights and traffic). Each seed
also says whether the program and the control pass the cell's limits:
the control has to fail them.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3

Prints one JSON line per seed: ``{"seed", "served", "control",
"program_correct", "control_correct", ...}``.
Not part of the benchmark's runs.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import run_cell
    for seed in args.seeds:
        buf = io.StringIO()
        res = run_cell(ROOT, args.workload, seed, args.seconds, False,
                       time.perf_counter(), out=buf, control=True)
        sys.stdout.write(buf.getvalue().split("\n")[1] + "\n")
        print(json.dumps({"seed": seed, "served": res["readings"]["served"],
                          "control": res["readings"]["control"],
                          "tokens": res["readings"]["tokens"],
                          "program_correct": res["program_correct"],
                          "control_correct": res["correct"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
