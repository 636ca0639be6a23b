"""Readings that the limit of ``correct`` is set from.

    python bench/calibrate.py --workload <cell> --seconds 20 --seeds 1 2 3 ...

Runs the cell once per seed in this one process, at the cell's own load
and sizes, with the fp8 control put in the program's place
(``run_cell(control=True)``): the run's verdict is the control's, and the
program's own widest gap is read beside it on the same requests.  For
each seed it prints both gaps, how many compared positions each side
puts off the reference's first choice, and both verdicts at the cell's
limit.  The limit lies between the largest program reading and the
smallest control reading.  Each seed's row goes to
``chiprun_out/calibrate-<cell>.jsonl``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main():
    from harness.main import run_cell
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"))
    args = ap.parse_args()
    Path(args.out).mkdir(exist_ok=True)
    rows = []
    with open(Path(args.out) / f"calibrate-{args.workload}.jsonl", "a") as f:
        for seed in args.seeds:
            t = time.perf_counter()
            lines = []

            def log(msg, **kw):
                lines.append(msg)
                print(msg, **kw)
            r = run_cell(ROOT, args.workload, seed, args.seconds, False,
                         t_start=t, control=True, log=log)
            c = r["checks"]
            off = json.loads(next(x for x in lines if x.startswith("check off_top: "))
                             [len("check off_top: "):])
            program_ok = (c["program_gap"]["value"] <= c["program_gap"]["limit"]
                          and c["wrong_length"]["value"] == 0
                          and c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"])
            row = {"seed": seed, "program": c["program_gap"]["value"],
                   "control": c["widest_gap"]["value"], "limit": c["widest_gap"]["limit"],
                   "program_correct": program_ok, "control_correct": r["correct"],
                   "off_top": off, "wrong_length": c["wrong_length"]["value"],
                   "failed": r["failed"], "attempted": r["attempted"],
                   "metrics": r["metrics"], "seconds": time.perf_counter() - t}
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    prog = max(r["program"] for r in rows)
    ctl = min(r["control"] for r in rows)
    print(f"calibrate {args.workload}: program max {prog} over {len(rows)} seeds; "
          f"control min {ctl}; ratio {ctl / prog if prog else float('inf')}")


if __name__ == "__main__":
    main()
