"""Configurations, mixes, cells and per-layer metrics are found by file
name: adding a file and an entry adds it, with no edit to the harness."""
import json
import subprocess
import sys
import time

import pytest

from benchroot import REPO, make_tiny_root
from harness.main import run_cell
from harness.spec import Bench, SpecError, discover


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


def test_repo_files_are_discovered():
    assert "starcoder2-3b" in discover(REPO, "configs")
    assert {"chat", "backlog"} <= set(discover(REPO, "traffic"))
    assert {"step_ms", "mfu", "step_mfu", "mixed_roofline", "idle_share",
            "queue_wait_p95_ms"} <= set(discover(REPO, "metrics"))
    bench = Bench(REPO)
    for w in bench.all_cells():
        cell = bench.cell(w)
        assert cell.config["name"] == w.split(".")[0]
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_every_metric_names_cells_that_report_what_it_moves():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"], (m, w)


def test_unknown_names_are_refused(tmp_path):
    bench = Bench(REPO)
    with pytest.raises(SpecError):
        bench.workload("no-such.cell")
    with pytest.raises(SpecError):
        bench.reader("no_such_metric")
    with pytest.raises(SpecError):
        bench.peaks("TPU v99")


def test_a_new_file_and_entry_add_a_metric(tiny_root):
    (tiny_root / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n"
        "    lo, hi = ctx.t0, ctx.t0 + ctx.window_s\n"
        "    return float(sum(1 for s in ctx.driver.steps if lo <= s.t0 < hi))\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "engine step loop",
        "moves": "ttft_p95_ms", "workloads": ["tiny.chat"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert "steps_in_window" in discover(tiny_root, "metrics")
    r = run_cell(tiny_root, "tiny.chat", 5, 1.0, True, t_start=time.perf_counter(),
                 require_tpu=False, log=lambda *a, **k: None)
    assert r["metrics"]["steps_in_window"]["value"] > 0
    assert r["correct"] is True


def test_a_new_config_file_is_a_new_configuration(tiny_root):
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg["n_layers"] = 3
    (tiny_root / "bench/configs/tiny3.json").write_text(json.dumps(cfg))
    assert "tiny3" in discover(tiny_root, "configs")
    assert Bench(tiny_root).config("tiny3")["n_layers"] == 3


def test_run_py_refuses_a_tree_without_the_program(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's own
    files, a run exits non-zero and prints no result."""
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "starcoder2-3b.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
