"""Knee sweep of an open-loop cell: latency against offered rate.

    python bench/sweep.py --workload <cell> --seed 7 --seconds 60 --drain 60 \
        --fractions 0.15 0.6 0.7 0.8 0.9 1.0

First a probe times the mixed step (its shapes are fixed, so its time
hardly moves with load) and estimates the chip's capacity in requests/s:
``chunk_tokens`` tokens a step over the mix's mean prompt plus output
length.  Then one process and one engine serve the cell's mix at each
fraction of that capacity in turn
(``--seconds`` of arrivals, then a drain until those requests finish),
lowest first.  For every rate it prints the requests due, those
unfinished, the TTFT and worst-gap percentiles, and the waiting queue at
the window's close; every request's numbers go to
``chiprun_out/sweep-<cell>.jsonl``.  The limits and the knee are set
from that file afterwards (see PERF.md).
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


def probe_capacity(engine, cfg, mix) -> float:
    """Requests/s one chip could prefill and decode at the chunk budget,
    from the median time of 40 steps of a few median-sized requests."""
    import numpy as np

    from harness.driver import Driver, Spans
    from harness.traffic import Traffic, clipped_lognormal_quantiles
    probe = dict(mix, arrivals="backlog", block=8)
    drv = Driver(engine, Traffic(probe, 0, cfg["vocab_size"]), spans=Spans(False),
                 queue_target=2, rid_base=1_000_000_000)
    drv.warm(cfg["serve"]["chunk_tokens"] // 2)
    drv.backlog_warm(60)
    drv.release()
    st = float(np.median([s.t1 - s.t0 for s in drv.steps[-40:] if s.dispatched]))
    means = [clipped_lognormal_quantiles(1000, d["median"], d["sigma"], d["min"],
                                         d["max"]).mean()
             for d in (mix["prompt_tokens"], mix["output_tokens"])]
    while not engine.idle():
        engine.step()
    engine.poll()
    cap = cfg["serve"]["chunk_tokens"] / (st * sum(means))
    print(json.dumps({"probe_step_s": st, "mean_prompt": means[0],
                      "mean_output": means[1], "capacity_per_s": cap}), flush=True)
    return cap


def main():
    import numpy as np

    from harness.driver import Driver, Spans
    from harness.main import build_engine, find_devices, setup_jax
    from harness.spec import Bench
    from harness.traffic import Traffic
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--fractions", type=float, nargs="+", required=True)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"))
    args = ap.parse_args()
    cell = Bench(ROOT).cell(args.workload)
    cfg, mix = cell.config, cell.traffic
    jax = setup_jax(ROOT)
    find_devices(jax, cell.chips, True)
    engine, _ = build_engine(jax, cell, args.seed)
    Path(args.out).mkdir(exist_ok=True)
    out = open(Path(args.out) / f"sweep-{args.workload}.jsonl", "a")
    cap = probe_capacity(engine, cfg, mix)
    rates = [f * cap for f in args.fractions]
    warmed = False
    for k, rate in enumerate(sorted(rates)):
        traffic = Traffic(mix, args.seed, cfg["vocab_size"],
                          window_s=args.seconds, rate=rate)
        drv = Driver(engine, traffic, spans=Spans(False),
                     rid_base=(k + 1) * 10_000_000)
        if not warmed:
            drv.warm(cfg["serve"]["chunk_tokens"] // 2)
            warmed = True
        queue = []
        drv.open_loop(args.seconds, args.drain,
                      on_window=lambda now: queue.append(
                          (now - drv.t0, len(engine.waiting))))
        drv.release()
        reqs = drv.window_requests()
        rows = []
        for r in reqs:
            gaps = np.diff(r.times) if len(r.times) > 1 else np.zeros(0)
            rows.append({"due": r.due - drv.t0, "prompt": len(r.item.prompt),
                         "out": r.item.max_new_tokens,
                         "ttft": (r.times[0] - r.due) if r.times else None,
                         "worst_gap": float(gaps.max()) if gaps.size else 0.0,
                         "done": r.done is not None})
        ttft = [x["ttft"] for x in rows if x["ttft"] is not None]
        worst = [x["worst_gap"] for x in rows]
        all_gaps = np.concatenate([np.diff(r.times) for r in reqs if len(r.times) > 1])
        at_close = [q for t, q in queue if t <= args.seconds]
        summary = {
            "rate": rate, "due": len(reqs),
            "unfinished": sum(not x["done"] for x in rows),
            "ttft_p50": float(np.percentile(ttft, 50)), "ttft_p90": float(np.percentile(ttft, 90)),
            "ttft_p95": float(np.percentile(ttft, 95)),
            "tbt_p50": float(np.percentile(all_gaps, 50)),
            "tbt_p95": float(np.percentile(all_gaps, 95)),
            "worst_gap_p90": float(np.percentile(worst, 90)),
            "queue_at_close": at_close[-1] if at_close else 0,
            "queue_max": max(at_close) if at_close else 0,
        }
        print(json.dumps(summary), flush=True)
        out.write(json.dumps(dict(summary, requests=rows)) + "\n")
        out.flush()
        while not engine.idle():           # leftover load of this rate
            engine.step()
        engine.poll()


if __name__ == "__main__":
    main()
