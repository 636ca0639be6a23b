"""The reductions of ``chip_spans.py`` on hand-made spans: nested host
spans flattened to their innermost name, idle gaps cut along them, and
the per-step breakdown of an engine's span ring."""
import sys
from pathlib import Path

import jax
import numpy as np

from conftest import reduced_model
from repro.configs import ServeConfig
from repro.core.engine import Engine, Request, SamplingParams

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_spans  # noqa: E402


def test_innermost_names_each_piece_by_its_deepest_span():
    host = [("bench.step", 0, 100), ("engine.step", 10, 90),
            ("engine.dispatch", 20, 40), ("host.gc", 25, 30),
            ("engine.sync", 50, 80), ("bench.submit", 120, 130)]
    assert chip_spans.innermost(host) == [
        (0, 10, "bench.step"), (10, 20, "engine.step"),
        (20, 25, "engine.dispatch"), (25, 30, "host.gc"),
        (30, 40, "engine.dispatch"), (40, 50, "engine.step"),
        (50, 80, "engine.sync"), (80, 90, "engine.step"),
        (90, 100, "bench.step"), (100, 120, "none"),
        (120, 130, "bench.submit")]


def test_idle_gaps_split_along_the_pieces():
    pieces = chip_spans.innermost([("engine.step", 10, 90),
                                   ("engine.sync", 50, 80)])
    cut = chip_spans.split_idle([(0, 15), (45, 60), (85, 95)], pieces)
    assert cut == [(10, "none", 0), (5, "engine.step", 10),
                   (5, "engine.step", 45), (10, "engine.sync", 50),
                   (5, "engine.step", 85), (5, "none", 90)]
    assert sum(d for d, _, _ in cut) == 15 + 15 + 10


def test_span_stats_of_an_engine_run():
    model = reduced_model("qwen3-0.6b")
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(
        mode="chunked", max_batch=3, page_size=4, n_pages=96,
        max_pages_per_seq=12, prefill_chunk=4, n_streams=2, chunk_tokens=8))
    rng = np.random.RandomState(0)
    m = eng.run([Request(rid=i, prompt=list(rng.randint(2, 100, size=12)),
                         sampling=SamplingParams(max_new_tokens=5))
                 for i in range(4)])
    st = chip_spans.span_stats(m, 0.0, float("inf"))
    steps = [s for s in m.spans if s.name == "engine.step"]
    assert st["n_steps"] == len(steps) == m.n_steps
    ms = st["span_ms"]
    assert ms["engine.step"] == sum(s.dur for s in steps) / len(steps) * 1e3
    parts = sum(v for k, v in ms.items()
                if k not in ("engine.step", "host.gc"))   # gc nests deeper
    assert parts <= ms["engine.step"] + 1e-9
    assert st["host_ms"] == ms["engine.step"] - ms["engine.sync"]
    top = st["longest_steps"]
    assert len(top) == min(10, len(steps))
    assert top[0]["ms"] == max(s.dur for s in steps) * 1e3
    assert top[0]["longest_child"] in top[0]["spans_ms"]
