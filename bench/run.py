"""Run one benchmark cell on the machine this starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m bench.run`` works the same.) Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
The last line of standard output is the result, as JSON.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import run_cell
    run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
             T_PROCESS)


if __name__ == "__main__":
    main()
