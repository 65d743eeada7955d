"""Find the knee of an open-loop cell: the highest offered rate whose
backlog does not grow over the window. Runs the cell at each rate in one
process (compiled programs are shared), without the comparison.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1 2 3 4

Prints one JSON line per rate: the offered rate, requests due, admitted
and finished, the mean queue length over the first and the second half
of the window's steps, and the cell's end-to-end metrics. Not part of the
benchmark's runs.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import load_cell, run_cell
    base = load_cell(ROOT, args.workload).traffic
    if base["loop"] != "open":
        raise SystemExit("sweep: the cell's traffic is not an open loop")
    for rate in args.rates:
        buf = io.StringIO()
        res = run_cell(ROOT, args.workload, args.seed, args.seconds, False,
                       time.perf_counter(), out=buf, check=False,
                       traffic=dict(base, rate_per_s=rate))
        text = buf.getvalue()
        sys.stderr.write(text)
        win = re.search(r"requests due (\d+), admitted (\d+), finished "
                        r"(\d+).*queue mean first/second half "
                        r"([\d.]+)/([\d.]+)", text)
        due, adm, fin, q1, q2 = win.groups()
        print(json.dumps({"rate_per_s": rate, "due": int(due),
                          "admitted": int(adm), "finished": int(fin),
                          "queue_first_half": float(q1),
                          "queue_second_half": float(q2),
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
