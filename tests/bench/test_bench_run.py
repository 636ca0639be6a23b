"""Whole runs of a toy-sized cell on the CPU, with the harness's look for
a TPU skipped: a sound run is correct, and a run with its timed path
broken underneath comes out not correct.  Also: without a TPU,
``bench/run.py`` exits non-zero and names the device it found."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchroot import REPO, make_tiny_root
from harness.main import run_cell


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


def run(root, workload, seed, **kw):
    return run_cell(root, workload, seed, 1.0, False, t_start=time.perf_counter(),
                    require_tpu=False, log=lambda *a, **k: None, **kw)


def test_no_tpu_exits_nonzero_naming_the_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "starcoder2-3b.chat",
                        "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert "no TPU found" in p.stderr and "cpu" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.backlog"])
def test_sound_run_is_correct_and_reports_its_metrics(tiny_root, workload):
    r = run(tiny_root, workload, 2**31 + 9)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    want = {"ttft_p95_ms", "tbt_p50_ms", "tbt_p95_ms"} if workload.endswith(".chat") \
        else {"out_tok_s"}
    assert set(r["metrics"]) == want | {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["widest_gap"]["value"] <= r["checks"]["widest_gap"]["limit"]
    json.dumps(r, allow_nan=False)


@pytest.mark.parametrize("fault", ["token", "stale_kv", "half_batch"])
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    r = run(tiny_root, "tiny.chat", 2**31 + 9, fault=fault)
    assert r["correct"] is False
    assert r["checks"]["widest_gap"]["value"] > r["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("workload,host_read", [
    ("tiny.backlog", {"step_ms.backlog", "mfu.backlog"}),
    ("tiny.chat", {"step_ms.chat", "step_mfu.chat", "queue_wait_p95_ms"})])
def test_traced_run_reports_per_layer_metrics(tiny_root, workload, host_read):
    r = run_cell(tiny_root, workload, 4, 1.0, True, t_start=time.perf_counter(),
                 require_tpu=False, log=lambda *a, **k: None)
    # the CPU has no device plane: trace-read metrics find nothing to read
    assert host_read <= set(r["metrics"])
    assert not any(k.startswith("mixed_roofline") for k in r["metrics"])
    assert all(0 < m["value"] for m in r["metrics"].values())
    assert all(m["value"] <= 100 for k, m in r["metrics"].items() if "mfu" in k)
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_pause_after_the_window_holds_the_schedule(tiny_root):
    """The profiler's stop holds the open loop after the window: later
    arrivals and the drain's end move on by as much."""
    from harness.driver import Driver, Spans
    from harness.main import build_engine, setup_jax
    from harness.spec import Bench
    from harness.traffic import Traffic
    cell = Bench(tiny_root).cell("tiny.chat")
    engine, _ = build_engine(setup_jax(tiny_root), cell, 3)
    drv = Driver(engine, Traffic(cell.traffic, 3, cell.config["vocab_size"],
                                 window_s=1.0, rate=8.0), spans=Spans(False))
    drv.warm(8)
    paused = []

    def on_window(now):
        if now >= drv.window_end and not paused:
            paused.append(now)
            time.sleep(1.0)
    drv.open_loop(1.0, 0.5, on_window=on_window)
    assert paused and drv.held >= 1.0
    assert all(r.done is not None for r in drv.window_requests())
    late = [lag for rec, lag in zip(drv.reqs.values(), drv.lags)
            if rec.submit > paused[0]]
    assert all(lag < 0.5 for lag in late)


@pytest.mark.parametrize("workload,method", [("tiny.chat", "open_loop"),
                                             ("tiny.backlog", "backlog")])
def test_a_cell_may_give_its_own_drain(tmp_path, monkeypatch, workload, method):
    """A cell file's ``drain_s`` takes the place of its mix's."""
    from harness.driver import Driver
    root = make_tiny_root(tmp_path)
    path = root / "bench" / "cells" / f"{workload}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), drain_s=33.0)))
    seen = []
    drive = getattr(Driver, method)

    def spy(self, window_s, drain_s, on_window=None):
        seen.append(drain_s)
        return drive(self, window_s, drain_s, on_window=on_window)
    monkeypatch.setattr(Driver, method, spy)
    r = run(root, workload, 2**31 + 9)
    assert seen == [33.0] and r["correct"] is True
