"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig10] [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows plus a validation block that
checks the paper's headline claims directionally (see EXPERIMENTS.md).
``--json PATH`` additionally writes the rows and check results as
machine-readable JSON (per-scenario throughput/TTFT/TBT/cache stats) so
perf trajectories can be recorded as ``BENCH_*.json``.
"""
import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of suite names to run")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced deterministic sizing for suites that "
                         "support it (CI regression-gate runs)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows + validation results as JSON")
    args, _ = ap.parse_known_args()

    from repro.common.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import batching, kv_usage, mixed_longprompt, open_loop
    from benchmarks import phase_intensity, policy_sweep, pressure
    from benchmarks import sanitizer_overhead, shared_prefix, slo_tenants
    from benchmarks import splitwiser_hf, splitwiser_vllm

    # (name, rows_fn, accepts_smoke)
    suites = [
        ("phase_intensity", phase_intensity.rows, False),   # Figs 2-4
        ("kv_usage", kv_usage.rows, False),                 # Figs 5, 14, 15
        ("splitwiser_hf", splitwiser_hf.rows, False),       # Figs 6-9
        ("splitwiser_vllm", splitwiser_vllm.rows, False),   # Figs 10-11
        ("batching", batching.rows, False),                 # Figs 12-13
        ("pressure", pressure.rows, False),                 # beyond-paper: KV pressure
        ("pressure_int8", pressure.int8_rows, False),       # beyond-paper: int8 KV pages
        ("open_loop", open_loop.rows, True),                # beyond-paper: Poisson arrivals
        ("mixed_longprompt", mixed_longprompt.rows, True),  # beyond-paper: chunked tail TBT
        ("shared_prefix", shared_prefix.rows, False),       # beyond-paper: prefix cache
        ("shared_prefix_int8", shared_prefix.int8_rows, False),  # int8 hit capacity
        ("policy_sweep", policy_sweep.rows, True),          # beyond-paper: policy matrix
        ("sanitizer_overhead", sanitizer_overhead.rows, False),  # analysis layer cost
        ("slo_tenants", slo_tenants.rows, True),            # beyond-paper: SLO deadlines
    ]
    only = args.only.split(",") if args.only else None
    all_rows = []
    print("name,us_per_call,derived")
    for name, fn, accepts_smoke in suites:
        if only and not any(tok in name for tok in only):
            continue
        t0 = time.perf_counter()
        rows = fn(smoke=True) if (args.smoke and accepts_smoke) else fn()
        dt_us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        for r in rows:
            all_rows.append(r)
            derived = {k: v for k, v in r.items() if k not in ("bench", "x")}
            print(f"{r['bench']}[{r['x']}],{dt_us:.0f},"
                  f"\"{json.dumps(derived, default=str)}\"")

    checks = []
    # ---- validation vs the paper's claims (directional) ----
    # checks are keyed on the rows actually collected, so partial runs
    # (--only) still validate — and record in --json — whatever they ran
    if all_rows:

        def by(b):
            return [r for r in all_rows if r["bench"] == b]
        pf = by("fig2_prefill_intensity")
        dc = by("fig3_decode_intensity")
        if pf and dc:
            checks.append(("prefill arithmetic intensity grows with input "
                           "tokens",
                           pf[-1]["arith_intensity"] > pf[0]["arith_intensity"]))
            checks.append(("prefill is compute-bound at 2048 input tokens",
                           pf[-1]["compute_bound"]))
            checks.append(("decode stays bandwidth-bound at every context len",
                           all(not r["compute_bound"] for r in dc)))
        kv = by("fig5_kv_usage_vs_batch")
        if kv:
            checks.append(("KV usage increases with batch size",
                           kv[-1]["token_usage"] > kv[0]["token_usage"]))
        f7 = by("fig7_throughput_4proc")
        if f7:
            checks.append(("throughput(4 streams) >= 1.1x sequential (paper: 1.1x)",
                           f7[0]["ratio"] >= 1.1))
        f9 = by("fig9_mps_arms")
        if f9:
            mps = [r for r in f9 if "fused" in str(r["x"])][0]
            checks.append(("splitwiser+MPS reduces E2E vs sequential (paper: 18.2%)",
                           mps["reduction_vs_seq"] > 0))
            nomps = [r for r in f9 if "noMPS" in str(r["x"])][0]
            checks.append(("MPS arm beats the time-sliced (no-MPS) arm "
                           "(paper Fig 9: splitwiser alone shows no gain on A10)",
                           mps["reduction_vs_seq"] > nomps["reduction_vs_seq"]))
        pr = by("pressure_oversubscribed")
        if pr:
            checks.append(("oversubscribed pool crashes the seed admission "
                           "policy (OutOfPages) in every mode",
                           all(r["seed_crash"] for r in pr)))
            checks.append(("scheduler completes every request under KV "
                           "pressure in every mode",
                           all(r["n_done"] == r["n_requests"]
                               and r["all_complete"] for r in pr)))
            checks.append(("survival is preemption-driven (evictions occurred)",
                           all(r["n_preemptions"] > 0 for r in pr)))
        pi = by("pressure_kv_int8")
        if pi:
            checks.append(("int8 KV pages at equal pool bytes buy >= 1.8x "
                           "usable pages in every mode",
                           all(r["page_ratio"] >= 1.8
                               and r["pool_bytes_int8"] <= r["pool_bytes_fp"]
                               for r in pi)))
            checks.append(("int8 KV strictly reduces preemptions on the "
                           "oversubscribed pool in every mode",
                           all(r["preemptions_int8"] < r["preemptions_fp"]
                               for r in pi)))
            checks.append(("int8 greedy streams bit-identical across all "
                           "serving modes under KV pressure, all requests "
                           "complete",
                           all(r["tokens_match"] and r["all_complete"]
                               for r in pi)))
        si = by("shared_prefix_int8_delta")
        if si:
            checks.append(("int8 pages raise prefix-cache hit capacity at "
                           "equal pool bytes on the tight pool",
                           all(r["cached_tokens_int8"] > r["cached_tokens_fp"]
                               and r["hit_rate_int8"] > r["hit_rate_fp"]
                               for r in si)))
            checks.append(("quantized prefix cache is transparent: int8 "
                           "cache-on streams bit-identical to cache-off",
                           all(r["tokens_match"] for r in si)))
        ol = by("open_loop_poisson")
        if ol:
            checks.append(("open-loop Poisson run finishes every request",
                           all(r["n_done"] == r["n_requests"] for r in ol)))
            checks.append(("every first token lands at/after its request's "
                           "arrival (timed admission)",
                           all(r["respects_arrivals"] for r in ol)))
        od = by("open_loop_det")
        if od:
            checks.append(("deterministic open-loop arm finishes every "
                           "request with timed admission honored",
                           all(r["n_done"] == r["n_requests"]
                               and r["all_complete"]
                               and r["respects_arrivals"] for r in od)))
            checks.append(("serving hot path stays compiled-once: zero "
                           "post-warmup recompiles on the served workload",
                           all(r["dispatch_post_warm"] == 0 for r in od)))
        ml = by("mixed_longprompt_det")
        if ml:
            checks.append(("mixed long-prompt arm finishes every request "
                           "with timed admission honored",
                           all(r["n_done"] == r["n_requests"]
                               and r["all_complete"]
                               and r["respects_arrivals"] for r in ml)))
            checks.append(("greedy streams bit-identical across serving "
                           "modes on the mixed long-prompt workload",
                           all(r["tokens_match"] for r in ml)))
            by_mode = {r["x"]: r for r in ml}
            if {"sequential", "splitwiser", "chunked"} <= by_mode.keys():
                ch, seq, sw = (by_mode["chunked"], by_mode["sequential"],
                               by_mode["splitwiser"])
                checks.append(("chunked prefill bounds the tail: p99 TBT "
                               "strictly below both monolithic modes at "
                               "equal completed tokens",
                               ch["tbt_vp99"] < seq["tbt_vp99"]
                               and ch["tbt_vp99"] < sw["tbt_vp99"]
                               and ch["completed_tokens"]
                               == seq["completed_tokens"]
                               == sw["completed_tokens"]))
                checks.append(("chunked serving stays compiled-once on the "
                               "mixed workload (zero post-warm recompiles)",
                               ch["dispatch_post_warm"] == 0))
        sp = by("shared_prefix_delta")
        if sp:
            k1 = [r for r in sp if "K=1" in str(r["x"])][0]
            kun = sp[-1]    # K == N: every prompt unique
            checks.append(("prefix cache skips prefill work when every "
                           "request shares one system prompt (K=1)",
                           k1["prefill_tokens_saved"] > 0
                           and k1["hit_rate_on"] > 0))
            checks.append(("shared pages lower peak KV usage at K=1",
                           k1["kv_peak_on"] < k1["kv_peak_off"]))
            checks.append(("cache benefit shrinks as prompts diversify "
                           "(K=1 saves more than K=N)",
                           k1["prefill_tokens_saved"]
                           >= kun["prefill_tokens_saved"]))
        mp = by("midpage_delta")
        if mp:
            checks.append(("mid-page divergence: token-level caching "
                           "strictly beats full-page on prefill tokens "
                           "computed",
                           all(r["prefill_tokens_token"]
                               < r["prefill_tokens_page"] for r in mp)))
            checks.append(("mid-page divergence: full-page caching scores "
                           "zero hits, token-level reuses the shared span "
                           "via partial-page COW",
                           all(r["hit_rate_page"] == 0
                               and r["hit_rate_token"] > 0
                               and r["n_partial_hits"] > 0 for r in mp)))
            checks.append(("greedy streams bit-identical across cache "
                           "granularities",
                           all(r["tokens_match"] for r in mp)))
        f10 = by("fig10_elapsed")
        if f10:
            big = f10[-1]
            checks.append(("MPSx2 speedup at largest batch (paper: 1.42x)",
                           big["mps_speedup"] > 1.0))
            checks.append(("MPx2 (time-sliced halves) does NOT beat MPS "
                           "(paper: MPx2 < SP < MPSx2)",
                           big["mp2_speedup"] <= big["mps_speedup"]))
        pw = by("policy_sweep_delta")
        if pw:
            checks.append(("cache_aware admission strictly raises hit rate "
                           "over fcfs on the Zipf-skewed workload (twins no "
                           "longer double-miss) for every eviction x preempt",
                           all(r["hit_rate_cache_aware"] > r["hit_rate_fcfs"]
                               for r in pw)))
            checks.append(("greedy token streams bit-identical across the "
                           "whole policy matrix",
                           all(r["tokens_match"] for r in pw)))
            checks.append(("every policy combination completes every request "
                           "under page pressure with reclaims",
                           all(r["n_done"] == r["n_requests"]
                               and r["n_reclaims"] > 0
                               for r in by("policy_sweep"))))
        sd = by("slo_tenants_det")
        if sd:
            checks.append(("multi-tenant SLO arms finish every request "
                           "with timed admission honored",
                           all(r["n_done"] == r["n_requests"]
                               and r["respects_arrivals"] for r in sd)))
            checks.append(("deadline scheduling stays compiled-once on the "
                           "tenant workload (zero post-warm recompiles)",
                           all(r["dispatch_post_warm"] == 0 for r in sd)))
            checks.append(("per-tenant token quota engaged on the burst "
                           "tenant under deadline admission",
                           all(r["quota_holds"] > 0 for r in sd
                               if "deadline" in str(r["x"]))))
        sdd = by("slo_tenants_delta")
        if sdd:
            checks.append(("deadline admission+preemption strictly raises "
                           "SLO attainment over fcfs+latest at equal load",
                           all(r["attainment_improved"]
                               and r["attainment_deadline"]
                               > r["attainment_fcfs"] for r in sdd)))
            checks.append(("deadline scheduling strictly lowers the gold "
                           "tenant's p99 TTFT (the burst victim)",
                           all(r["victim_p99_improved"]
                               and r["gold_p99_deadline"]
                               < r["gold_p99_fcfs"] for r in sdd)))
        sid = by("slo_tenants_identity")
        if sid:
            checks.append(("deadline policies are ordering-only: greedy "
                           "streams bit-identical to the fcfs oracle in "
                           "every mode when no deadline binds",
                           all(r["tokens_match"] and r["all_complete"]
                               for r in sid)))
        so = by("sanitizer_overhead_delta")
        if so:
            checks.append(("sanitizer is read-only: greedy token streams "
                           "bit-identical across off/finish/step/call",
                           all(r["tokens_match"] for r in so)))
        soh = by("sanitizer_overhead")
        if soh:
            checks.append(("dispatch sentinel sees zero post-warmup "
                           "recompiles at every sanitize level",
                           all(r["dispatch_post_warm"] == 0 for r in soh)))
    if checks:
        print("\n== paper-claim validation ==")
    ok = True
    for msg, passed in checks:
        print(f"[{'PASS' if passed else 'FAIL'}] {msg}")
        ok &= bool(passed)
    if args.json:
        with open(args.json, "w") as f:
            # ok is null for a partial run (--only): its checks are
            # recorded individually (the regression gate compares them),
            # but the run must not be machine-readable as "ALL claims
            # passed" when most suites never executed
            json.dump({"rows": all_rows,
                       "checks": [{"msg": m, "passed": bool(p)}
                                  for m, p in checks],
                       "ok": bool(ok) if not args.only else None},
                      f, indent=1, default=str)
        print(f"wrote {args.json}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
