"""The per-layer metrics read from the engine's own records
(``bench/metrics/host_ms.py``, ``bench/metrics/row_use.py``) on a toy
cell on the CPU: a traced run reports them, host time stays inside the
step, the row share agrees with the rows the harness saw dispatched,
and the engine's dispatch records equal ``CallRecorder``'s calls chunk
by chunk and decode by decode.  An engine without spans reports
nothing."""
import time
import types

import pytest

from benchroot import make_tiny_root
from harness.main import build_engine, run_cell, setup_jax
from harness.spec import Bench
from harness.traffic import Traffic

WINDOW_S = 1.0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("kind", ["chat", "backlog"])
def test_traced_run_reports_host_ms_and_row_use(tiny_root, kind):
    r = run_cell(tiny_root, f"tiny.{kind}", 2**31 + 11, WINDOW_S, True,
                 t_start=time.perf_counter(), require_tpu=False,
                 log=lambda *a, **k: None)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < m[f"host_ms.{kind}"] <= m[f"step_ms.{kind}"]
    assert 0 < m[f"row_use.{kind}"] <= 100
    assert r["correct"] is True


def _driven(root, kind, seed):
    """A driver that ran one window of the toy cell, and its context."""
    from harness.driver import Driver, Spans
    bench = Bench(root)
    cell = bench.cell(f"tiny.{kind}")
    mix, serve = cell.traffic, cell.config["serve"]
    engine, _ = build_engine(setup_jax(root), cell, seed)
    drv = Driver(engine, Traffic(mix, seed, cell.config["vocab_size"],
                                 window_s=WINDOW_S,
                                 rate=float(cell.cell.get("rate_per_s", 0.0))),
                 spans=Spans(False),
                 queue_target=int(mix.get("queue_factor", 0) * serve["max_batch"]))
    drv.warm(8)
    if kind == "chat":
        drv.open_loop(WINDOW_S, float(mix["drain_s"]))
    else:
        drv.backlog_warm(int(mix["warm_steps"]))
        drv.backlog(WINDOW_S, float(mix["drain_s"]))
    ctx = types.SimpleNamespace(driver=drv, t0=drv.t0, window_s=WINDOW_S)
    return drv, ctx, {name: bench.reader(name) for name in
                      (f"host_ms.{kind}", f"row_use.{kind}", f"step_ms.{kind}")}


@pytest.mark.parametrize("kind", ["chat", "backlog"])
def test_dispatch_records_equal_the_recorders_calls(tiny_root, kind):
    drv, ctx, read = _driven(tiny_root, kind, 2**31 + 13)
    calls = drv.recorder.calls
    assert calls
    dispatches = [s for s in drv.engine.metrics.spans
                  if s.name == "engine.dispatch"]
    assert all(s.attrs["program"] == "mixed" for s in dispatches)
    # the recorder saw each call inside the span of its dispatch
    paired = [next(s for s in dispatches if s.t0 <= c.t <= s.t1) for c in calls]
    first, last = dispatches.index(paired[0]), dispatches.index(paired[-1])
    assert paired == dispatches[first:last + 1]
    for c, s in zip(calls, paired):
        assert s.attrs["chunks"] == [(st, n) for st, n, _ in c.chunks]
        assert s.attrs["decodes"] == [L for L, _ in c.decodes]

    # row_use: the rows the recorder saw, over the program's static shape
    cfg = Bench(tiny_root).cell(f"tiny.{kind}").config["serve"]
    shape = cfg["n_streams"] * cfg["chunk_tokens"] + cfg["max_batch"]
    lo, hi = ctx.t0, ctx.t0 + WINDOW_S
    window = [(c, s) for c, s in zip(calls, paired) if lo <= s.t0 < hi]
    live = sum(n for c, _ in window for _, n, _ in c.chunks) + \
        sum(len(c.decodes) for c, _ in window)
    assert read[f"row_use.{kind}"](ctx) == pytest.approx(
        100.0 * live / (shape * len(window)))
    assert 0 < read[f"host_ms.{kind}"](ctx) <= read[f"step_ms.{kind}"](ctx)


def test_an_engine_without_spans_reports_nothing(tiny_root):
    bench = Bench(tiny_root)
    ctx = types.SimpleNamespace(
        driver=types.SimpleNamespace(engine=types.SimpleNamespace(
            metrics=types.SimpleNamespace(requests={}))),
        t0=0.0, window_s=WINDOW_S)
    for name in ("host_ms.chat", "host_ms.backlog", "row_use.chat",
                 "row_use.backlog"):
        assert bench.reader(name)(ctx) is None
