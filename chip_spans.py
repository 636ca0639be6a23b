"""Where a benchmark cell's steps spend their time, from the engine's own
spans: one run of one cell through the benchmark harness, on a TPU.

    python chip_spans.py --workload starcoder2-3b.chat --seed 7 --trace 1 \
        --profile 1

Prints one JSON line with the harness's result line and, over the window's
dispatching steps:

  step_ms         the harness's clock around ``Engine.step()``
  span_ms         mean ms per step of each span name (summed within a step)
  longest_steps   the ten longest steps, each with its longest child span
                  and the ``host.gc`` time inside it

and, over the whole run, ``decode_page_share`` from the engine's summary.

With ``--trace 1 --profile 1`` it also reads the profile the harness took,
before the harness deletes it:

  idle_by_span    the window's device idle time, each idle piece split by
                  the innermost host span it fell in (``engine.*``,
                  ``host.gc``, else the ``bench.*`` span or none)
  scope_ms        device self time of each ``jax.named_scope`` region of
                  the mixed program (``embed``, ``qkv``, ... , ``logits``)
"""
import argparse
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent
SCOPES = ("embed", "qkv", "kv_write", "chunk_attn", "decode_attn", "o_proj",
          "ffn", "logits")
HOST_PREFIXES = ("engine.", "host.gc", "bench.")


def span_stats(metrics, t0: float, window_s: float) -> dict:
    """Per-step breakdown of the window's dispatching steps."""
    from repro.core.metrics import self_time
    spans = list(metrics.spans)
    steps = {s.step: s for s in spans if s.name == "engine.step"
             and t0 <= s.t0 < t0 + window_s and s.attrs
             and s.attrs["kind"] not in ("idle", "preempt")}
    per_step = defaultdict(lambda: defaultdict(float))
    longest = {}
    for s in spans:
        if s.step not in steps or s.name == "engine.step":
            continue
        per_step[s.step][s.name] += s.dur
        if s.parent == steps[s.step].sid and s.dur > longest.get(s.step, (0, ""))[0]:
            longest[s.step] = (s.dur, s.name)
    n = max(len(steps), 1)
    names = sorted({k for d in per_step.values() for k in d})
    mean = {k: sum(d.get(k, 0.0) for d in per_step.values()) / n * 1e3
            for k in names}
    mean["engine.step"] = sum(s.dur for s in steps.values()) / n * 1e3
    own = self_time(spans)
    mean["engine.step (self)"] = sum(own[s.sid] for s in steps.values()) / n * 1e3
    top = sorted(steps.values(), key=lambda s: -s.dur)[:10]
    return {
        "n_steps": len(steps),
        "spans_dropped": metrics.spans.n_dropped,
        "span_ms": mean,
        "host_ms": mean["engine.step"] - mean.get("engine.sync", 0.0),
        "longest_steps": [
            {"step": s.step, "ms": s.dur * 1e3,
             "longest_child": longest.get(s.step, (0.0, None))[1],
             "longest_child_ms": longest.get(s.step, (0.0, None))[0] * 1e3,
             "gc_ms": per_step[s.step].get("host.gc", 0.0) * 1e3,
             "at_s": s.t0 - t0,
             "spans_ms": {k: v * 1e3 for k, v in per_step[s.step].items()}}
            for s in top],
    }


def innermost(host) -> list:
    """Host spans ``[(name, start, end)]`` (properly nested) as flat
    ``[(start, end, innermost name)]`` pieces."""
    bounds = sorted({t for _, a, b in host for t in (a, b)})
    starts = sorted(host, key=lambda e: (e[1], -(e[2] - e[1])))
    out, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while stack and stack[-1][2] <= a:
            stack.pop()
        while i < len(starts) and starts[i][1] <= a:
            if starts[i][2] > a:
                stack.append(starts[i])
            i += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        out.append((a, b, stack[-1][0] if stack else "none"))
    return out


def split_idle(gaps, pieces):
    """Each idle gap ``(start, end)`` cut along the ``innermost`` pieces:
    ``[(length, label, start)]`` in gap order (a stretch no piece covers
    is labelled ``none``)."""
    out, k = [], 0
    for a, b in gaps:
        t = a
        while t < b:
            while k < len(pieces) and pieces[k][1] <= t:
                k += 1
            if k < len(pieces) and pieces[k][0] <= t:
                e, label = min(b, pieces[k][1]), pieces[k][2]
            else:
                e = min(b, pieces[k][0]) if k < len(pieces) else b
                label = "none"
            out.append((e - t, label, t))
            t = e
    return out


def read_profile(path: str, hlo_scopes: dict) -> dict:
    """Idle split by innermost host span, and device time by scope."""
    from jax.profiler import ProfileData

    from harness import trace
    host, raw = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events
                            if e.name.startswith(HOST_PREFIXES))
        elif trace.DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    raw.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in ln.events)
    window = [(a, b) for n, a, b in host if n == "bench.window"]
    lo, hi = min(a for a, _ in window), max(b for _, b in window)
    host = [h for h in host if h[0] != "bench.window"]
    ops = [(n, max(a, lo), min(b, hi)) for n, a, b in raw
           if min(b, hi) > max(a, lo)]
    # idle pieces of the window, by the innermost host span they fell in
    busy = trace.union((a, b) for _, a, b in ops)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    cut = split_idle(gaps, innermost(host))
    idle = defaultdict(float)
    for d, label, _ in cut:
        idle[label] += d
    longest = sorted(cut, reverse=True)
    # device self time by named scope: the op events carry no op name, so
    # each is looked up by instruction name in the compiled program's text
    scope_ns = defaultdict(float)
    for name, ns in trace.self_time(ops).items():
        op_name = hlo_scopes.get(name.split(" = ")[0].lstrip("%"), "")
        at = {s: op_name.rfind(f"/{s}/") for s in SCOPES}
        inner = max(at, key=at.get)
        scope_ns[inner if at[inner] >= 0 else "(no scope)"] += ns
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(b - a for a, b in busy) * 1e-9,
        "idle_s": sum(b - a for a, b in gaps) * 1e-9,
        "idle_by_span_s": {k: v * 1e-9 for k, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])},
        "longest_idle": [[label, d * 1e-6, (t - lo) * 1e-9]
                         for d, label, t in longest[:10]],
        "scope_ms": {k: v * 1e-6 for k, v in
                     sorted(scope_ns.items(), key=lambda kv: -kv[1])},
    }


def hlo_op_scopes(model, serve, dtype) -> dict:
    """Instruction name -> op name of the compiled mixed program at the
    engine's shapes (the persistent compile cache usually holds it)."""
    import jax

    from repro.core.engine import step_program_args
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dtype))
    progs = step_program_args(model.cfg, serve, params, prefill_batch=1,
                              prefill_len=serve.page_size)
    prog, args = progs.get("mixed_chunked", progs["mixed"])
    text = prog.lower(*args).compile().as_text()
    return dict(re.findall(r'%(\S+) = [^\n]*?op_name="([^"]*)"', text))


def run(root: Path, workload: str, seed: int, seconds: float, traced: bool,
        *, t_start: float, profile: bool = False,
        require_tpu: bool = True) -> dict:
    """One run of ``workload`` under the benchmark rooted at ``root``,
    with the engine's spans (and the profile) read before they go."""
    import jax

    from harness import main as hm

    seen = {}

    class Driver(hm.Driver):
        def __init__(self, engine, *a, **kw):
            super().__init__(engine, *a, **kw)
            seen.update(metrics=engine.metrics, steps=self.steps,
                        model=engine.model, serve=engine.serve,
                        dtype=jax.tree.leaves(engine.params)[0].dtype)

        def open_loop(self, window_s, *a, **kw):
            super().open_loop(window_s, *a, **kw)
            seen.update(t0=self.t0, window_s=window_s)

        def backlog(self, window_s, *a, **kw):
            super().backlog(window_s, *a, **kw)
            seen.update(t0=self.t0, window_s=window_s)

    class Tracer(hm.Tracer):
        def read(self, n_devices):
            if profile and self.state == "done":
                path = next(Path(self.dir).rglob("*.xplane.pb"))
                seen["profile"] = read_profile(str(path), hlo_op_scopes(
                    seen["model"], seen["serve"], seen["dtype"]))
            return super().read(n_devices)

    drv_cls, tracer_cls = hm.Driver, hm.Tracer
    hm.Driver, hm.Tracer = Driver, Tracer
    try:
        result = hm.run_cell(root, workload, seed, seconds, traced,
                             t_start=t_start, require_tpu=require_tpu,
                             log=print)
    finally:
        hm.Driver, hm.Tracer = drv_cls, tracer_cls
    lo, hi = seen["t0"], seen["t0"] + seen["window_s"]
    steps = [s.t1 - s.t0 for s in seen["steps"]
             if s.dispatched and lo <= s.t0 < hi]
    return {"workload": workload, "seed": seed, "trace": int(traced),
            "result": result,
            "step_ms": sum(steps) / len(steps) * 1e3 if steps else None,
            "decode_page_share": seen["metrics"].summary()[
                "decode_page_share"],
            **span_stats(seen["metrics"], seen["t0"], seen["window_s"]),
            "profile": seen.get("profile")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "bench"))
    sys.path.insert(0, str(ROOT / "src"))
    out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
              t_start=T_START, profile=bool(args.profile))
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
