"""Limits and knee of an open-loop cell, from its sweep.

    python bench/knee.py --workload <cell> [--write]

Reads ``chiprun_out/sweep-<cell>.jsonl`` (``bench/sweep.py``).  The
limits are ``SLO_SCALE`` times the cell's own unloaded latencies: the
p90 TTFT and the p90 worst inter-token gap at the lowest swept rate.  A
request meets them when its TTFT and its worst gap are both within; one
unfinished misses.  The knee is the highest swept rate at which 90% of
the requests due meet both and the waiting queue at the window's close
holds no more than a tenth of them (no growing backlog).  The cell runs
at ``RATE_SHARE`` of the knee; ``--write`` puts that rate and the limits
into ``bench/cells/<cell>.json``.
"""
import argparse
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
SLO_SCALE = 5.0
ATTAIN = 0.9
RATE_SHARE = 0.8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()
    path = Path(args.sweep or ROOT / "chiprun_out" / f"sweep-{args.workload}.jsonl")
    rows = sorted((json.loads(x) for x in path.read_text().splitlines() if x.strip()),
                  key=lambda r: r["rate"])
    base = rows[0]["requests"]
    ttft0 = float(np.percentile([r["ttft"] for r in base if r["ttft"] is not None], 90))
    gap0 = float(np.percentile([r["worst_gap"] for r in base], 90))
    ttft_lim, gap_lim = SLO_SCALE * ttft0, SLO_SCALE * gap0
    knee = None
    for row in rows:
        reqs = row["requests"]
        ok = sum(1 for r in reqs if r["done"] and r["ttft"] is not None
                 and r["ttft"] <= ttft_lim and r["worst_gap"] <= gap_lim)
        share = ok / len(reqs)
        steady = row["queue_at_close"] <= 0.1 * len(reqs)
        print(f"rate {row['rate']:.3f}/s: due {len(reqs)} unfinished {row['unfinished']} "
              f"attain {share:.3f} queue_at_close {row['queue_at_close']} "
              f"ttft_p95 {row['ttft_p95']:.3f}s tbt_p50 {row['tbt_p50'] * 1e3:.2f}ms")
        if share >= ATTAIN and steady:
            knee = row["rate"]
    print(f"unloaded p90: ttft {ttft0:.4f}s worst gap {gap0:.4f}s; limits "
          f"ttft {ttft_lim:.4f}s gap {gap_lim:.4f}s; knee {knee}")
    if knee is None:
        raise SystemExit("no swept rate meets the limits")
    rate = round(RATE_SHARE * knee, 3)
    print(f"cell rate {rate}/s")
    if args.write:
        cell_path = ROOT / "bench" / "cells" / f"{args.workload}.json"
        cell = json.loads(cell_path.read_text())
        cell.update(rate_per_s=rate, knee_per_s=round(knee, 3),
                    slo_ttft_s=round(ttft_lim, 4), slo_worst_gap_s=round(gap_lim, 4))
        cell_path.write_text(json.dumps(cell, indent=2) + "\n")


if __name__ == "__main__":
    main()
