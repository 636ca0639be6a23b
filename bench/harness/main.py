"""One run of one cell: set up, warm up, measure, check, report.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.  A run that finds
no TPU, or fewer chips than the cell asks for, exits 3 and prints no
result; a cell whose files are missing exits 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from harness import check, trace
from harness.driver import Driver, Spans
from harness.spec import Bench, Cell, SpecError
from harness.traffic import Traffic

SAMPLE_REQUESTS = 16        # requests the correctness check compares
MIN_COMPARED = 200          # fewest served tokens a sample may hold
EXIT_SPEC, EXIT_DEVICE = 2, 3
# sizes every configuration states at its top level
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "norm_eps")


class NoDevice(RuntimeError):
    pass


def pct(vals, q) -> Optional[float]:
    return float(np.percentile(np.asarray(vals, float), q)) if len(vals) else None


# ---------------------------------------------------------------- set-up --
def setup_jax(root: Path):
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def find_devices(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    d = devs[0]
    desc = f"{d.platform} / {d.device_kind} x{len(devs)}"
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"no TPU found: JAX's devices are {desc}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {desc}")
    return devs


class CompileCounter:
    """Counts JAX compile requests and traces while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0

        def on_event(event, **kw):
            if self.on and event == "/jax/compilation_cache/compile_requests_use_cache":
                self.n += 1

        def on_duration(event, duration, **kw):
            if self.on and event.startswith("/jax/core/compile/jaxpr_trace"):
                self.n += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of a configuration: its ``arch``'s,
    with the sizes of ``MODEL_KEYS`` and every key of the optional
    ``"model"`` object set on it.  A key of ``"model"`` that is no other
    field of ``ModelConfig`` is refused."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    settings = cfg.get("model") or {}
    known = {f.name for f in dataclasses.fields(base)} - {"name", *MODEL_KEYS}
    unknown = sorted(set(settings) - known)
    if unknown:
        raise SpecError(f"configuration {cfg.get('name')!r}: model settings "
                        f"{unknown} are not settable fields of ModelConfig "
                        f"(sizes of {MODEL_KEYS} go at the top level)")
    settings = {k: tuple(v) if isinstance(v, list) else v
                for k, v in settings.items()}
    return dataclasses.replace(base, **{k: cfg[k] for k in MODEL_KEYS}, **settings)


def build_model(cfg: dict):
    """The program's model of a configuration."""
    from repro.models.registry import get_model
    return get_model(cfg["arch"], model_config(cfg))


def build_engine(jax, cell: Cell, seed: int):
    import jax.numpy as jnp

    from repro.configs import ServeConfig
    from repro.core.engine import Engine

    from harness.weights import make_params
    cfg = cell.config
    model = build_model(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    params = make_params(shapes, cfg, seed, cell.family.leaf_std)
    serve = ServeConfig(sanitize_level="off", dispatch_sentinel=False,
                        **cfg["serve"])
    return Engine(model, params, serve), params


# --------------------------------------------------------------- tracing --
class Tracer:
    """Traces the measured window.  The profiler starts before the window
    (its start-up is set-up time) and stops at the first moment after the
    window's close at which ``ready`` holds (at most ``LATE_S`` later), so
    its stop pause falls after the window."""

    LATE_S = 10.0

    def __init__(self, jax, on: bool):
        self.jax, self.on = jax, on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.state = "idle"
        self.end_at = 0.0
        self._span = None

    def start(self) -> None:
        if self.on:
            self.jax.profiler.start_trace(self.dir)
            self.state = "armed"

    def arm(self, t0: float, window_s: float) -> None:
        """Mark the window's start; the host span ``bench.window`` that
        the reduction reads is opened here."""
        self.end_at = t0 + window_s
        if self.state == "armed":
            self._span = self.jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.state = "tracing"

    def tick(self, now: float, ready: bool = True) -> None:
        if self.state == "tracing" and now >= self.end_at and (
                ready or now >= self.end_at + self.LATE_S):
            self.finish()

    def finish(self) -> None:
        if self.state != "tracing":
            return
        self._span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.state = "done"

    def read(self, n_devices: int):
        if self.state != "done":
            return None
        try:
            path = next(Path(self.dir).rglob("*.xplane.pb"))
            events = trace.load(str(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi = trace.window_of(events)
        red = trace.reduce(events, lo, hi, n_devices)
        red["lo_ns"], red["hi_ns"] = lo, hi
        return red


# ---------------------------------------------------------- the metrics --
@dataclasses.dataclass
class Context:
    """What the per-layer readers read (``bench/metrics/<name>.py``)."""
    cell: Cell
    driver: Driver
    t0: float
    window_s: float
    peaks: dict
    itemsize: int
    trace: Optional[dict]
    calls_in_window: list
    calls_in_trace: list

    def call_flops(self, c, useful_only=False) -> int:
        chunks = [(st + (r if useful_only else 0), n - (r if useful_only else 0))
                  for st, n, r in c.chunks if n - (r if useful_only else 0) > 0]
        decodes = [L for L, rec in c.decodes if not (useful_only and rec)]
        return self.cell.family.call_flops(self.cell.config, chunks, decodes)

    def call_bytes(self, c) -> int:
        return self.cell.family.call_bytes(
            self.cell.config, [(st, n) for st, n, _ in c.chunks],
            [L for L, _ in c.decodes], self.itemsize)


def end_to_end(cell: Cell, drv: Driver, window_s: float, t_stop: float) -> dict:
    reqs = drv.window_requests()
    out = {}
    names = {m["name"] for m in cell.end_to_end}
    if {"ttft_p95_ms", "tbt_p50_ms", "tbt_p95_ms"} & names:
        ttft, gaps = [], []
        for r in reqs:
            first = r.times[0] if r.times else t_stop   # a stall stays in the tail
            ttft.append(first - r.due)
            gaps.extend(np.diff(r.times).tolist())
        out["ttft_p95_ms"] = pct(ttft, 95) * 1e3 if ttft else None
        out["tbt_p50_ms"] = pct(gaps, 50) * 1e3 if gaps else None
        out["tbt_p95_ms"] = pct(gaps, 95) * 1e3 if gaps else None
    if "out_tok_s" in names:
        lo, hi = drv.t0, drv.t0 + window_s
        n = sum(1 for r in drv.reqs.values() for t in r.times if lo <= t < hi)
        out["out_tok_s"] = n / window_s
    return out


# ------------------------------------------------------------------ run --
def run_cell(root: Path, workload: str, seed: int, seconds: float, traced: bool,
             *, t_start: float, require_tpu: bool = True, fault: Optional[str] = None,
             control: bool = False, log=print) -> dict:
    """One run of ``workload``.  With ``control`` the fp8 reference
    stands in the program's place: its first choices are the tokens the
    verdict judges, and the program's own widest gap is reported beside
    them as ``program_gap``."""
    bench = Bench(root)
    cell = bench.cell(workload)
    cfg, mix, params_cell = cell.config, cell.traffic, cell.cell
    jax = setup_jax(root)
    devs = find_devices(jax, cell.chips, require_tpu)
    dev = devs[0]
    peaks = bench.peaks(dev.device_kind) if require_tpu else bench.peaks("TPU v5 lite")
    cell.family.check_config(cfg)

    engine, params = build_engine(jax, cell, seed)
    if fault:
        inject(engine, fault, cfg["vocab_size"])
    window_s = float(seconds)
    rate = float(params_cell.get("rate_per_s", 0.0))
    traffic = Traffic(mix, seed, cfg["vocab_size"], window_s=window_s, rate=rate)
    serve = cfg["serve"]
    drv = Driver(engine, traffic, spans=Spans(traced),
                 queue_target=int(mix.get("queue_factor", 0) * serve["max_batch"]))
    drv.warm(min(serve["chunk_tokens"] // 2, mix["prompt_tokens"]["max"]))
    counter = CompileCounter()
    tracer = Tracer(jax, traced)
    pre0 = engine.metrics.n_preempt_events
    # a cell whose steps are slow gives its last requests longer to finish
    drain_s = float(params_cell.get("drain_s", mix["drain_s"]))

    if mix["arrivals"] == "poisson":
        tracer.start()
        t0 = time.perf_counter()
        tracer.arm(t0, window_s)
        counter.on = True
        setup_s = t0 - t_start
        # the profiler's stop holds the loop: not while a request due in
        # the window still waits in the queue, whose wait it would swell
        drv.open_loop(window_s, drain_s, on_window=lambda now: tracer.tick(
            now, ready=drv.window_queued() == 0))
    else:
        drv.backlog_warm(int(mix["warm_steps"]))          # steady state
        tracer.start()
        t0 = time.perf_counter()
        tracer.arm(t0, window_s)
        counter.on = True
        setup_s = t0 - t_start
        drv.backlog(window_s, drain_s, on_window=tracer.tick)
    tracer.finish()
    counter.on = False
    t_stop = time.perf_counter()
    t0 = drv.t0
    jax.block_until_ready((engine.k_pages, engine.v_pages))
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    reqs = drv.window_requests()
    attempted = len(reqs)
    failed = sum(1 for r in reqs if r.done is None)
    lags = np.asarray(drv.lags) * 1e3 if drv.lags else np.zeros(1)
    dispatched = [s for s in drv.steps if s.dispatched and t0 <= s.t0 < t0 + window_s]
    # a host that stands still shows as one long step or one long pause
    step_max = max((s.t1 - s.t0 for s in dispatched), default=0.0)
    pause_max = max((b.t0 - a.t1 for a, b in zip(dispatched, dispatched[1:])),
                    default=0.0)
    log(f"generator: setup_s={setup_s:.3f} "
        f"step_max_ms={step_max * 1e3:.3f} pause_max_ms={pause_max * 1e3:.3f} "
        f"lag_max_ms={lags.max():.3f} lag_p99_ms={pct(lags, 99):.3f} "
        f"compiles_in_window={counter.n} "
        f"preemptions={engine.metrics.n_preempt_events - pre0} "
        f"window_steps={len(dispatched)} attempted={attempted} failed={failed}",
        file=sys.stderr)

    metrics = {}
    if not traced:
        e2e = end_to_end(cell, drv, window_s, t_stop)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    red = tracer.read(len(devs[: cell.chips])) if traced else None
    if red is not None:
        log("trace: idle by span " + json.dumps(
            {k: v * 1e-9 for k, v in red["idle_by_span"].items()}) +
            " programs " + json.dumps({k: [v * 1e-9, red["module_calls"][k]]
                                       for k, v in red["modules"].items()}),
            file=sys.stderr)
    calls = [c for c in drv.recorder.calls if t0 <= c.t < t0 + window_s]
    in_trace = calls if red else []
    if traced:
        ctx = Context(cell=cell, driver=drv, t0=t0, window_s=window_s,
                      peaks=peaks,
                      itemsize=np.dtype(cfg["dtype"]).itemsize, trace=red,
                      calls_in_window=calls, calls_in_trace=in_trace)
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state before the reference runs
    served = [check.Served(r.item.idx, r.item.prompt, r.tokens)
              for r in reqs if r.done is not None]
    wrong_len = [r.item.idx for r in reqs if r.done is not None and (
        r.finish_reason != "length" or len(r.tokens) != r.item.max_new_tokens)]
    del engine, drv
    gc.collect()
    sample = check.draw_sample(served, seed, SAMPLE_REQUESTS)
    limit = float(params_cell["logit_gap_limit"])
    min_tokens = int(params_cell.get("min_compared_tokens", MIN_COMPARED))
    gaps = check.widest_gaps(cell.family, params, cfg, sample,
                             t_pad=serve["page_size"] * serve["max_pages_per_seq"],
                             a_pad=int(mix["output_tokens"]["max"]),
                             control=control)
    judged = gaps["control"] if control else gaps["program"]
    ok = bool(sample) and not wrong_len and check.verdict(judged, limit)
    checks = {
        "widest_gap": {"value": judged, "limit": limit},
        "wrong_length": {"value": len(wrong_len), "limit": 0},
        # a floor: the sample has to hold some hundreds of served tokens
        "compared_tokens": {"value": gaps["tokens"], "limit": min_tokens},
    }
    if gaps["tokens"] < min_tokens:
        ok = False
    if control:
        checks["program_gap"] = {"value": gaps["program"], "limit": limit}
    log("check off_top: " + json.dumps({k: v for k, v in gaps.items()
                                        if k.endswith("_off_top") or k == "tokens"}),
        file=sys.stderr)
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_ns"] * 1e-9
        device["window_s"] = red["window_ns"] * 1e-9
        result["breakdown"] = {
            "device_ops": [[k, v * 1e-9] for k, v in trace.top(red["ops"])],
            "idle_gaps": [[k, v * 1e-9] for k, v in red["gaps"][:10]],
        }
    result["checks"] = checks
    return result


def inject(engine, fault: str, vocab: int) -> None:
    """Break the timed path underneath the harness (for its own tests)."""
    if fault == "token":                    # a token altered where produced
        greedy = engine._greedy
        engine._greedy = lambda logits: (greedy(logits) + 1) % vocab
    elif fault == "stale_kv":               # the step returns its state unchanged
        mixed = engine._mixed

        def stale(params, mb, kpg, vpg):
            import jax.numpy as jnp
            before = (jnp.copy(kpg), jnp.copy(vpg))   # the inputs are donated
            p, d, _, aux = mixed(params, mb, kpg, vpg)
            return p, d, before, aux
        engine._mixed = stale
    elif fault == "half_batch":             # half the decode rows left out
        mixed = engine._mixed

        def halved(params, mb, kpg, vpg):
            p, d, pages, aux = mixed(params, mb, kpg, vpg)
            odd = d[1::2].shape[0]          # odd rows take their neighbour's
            return p, d.at[1::2].set(d[0::2][:odd]), pages, aux
        engine._mixed = halved
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv: Optional[List[str]] = None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_SPEC
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_DEVICE
    line = json.dumps(result, allow_nan=False, default=float)
    print(line, flush=True)
    return 0
