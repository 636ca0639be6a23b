"""Spans and counters of the engine's host loop (``core/metrics.py``
``Span`` / ``EngineMetrics.span``, recorded by ``Engine.step``).

* The recorder: nesting and parent links, a span's self time, a
  collection recorded as a ``host.gc`` span under the span it fell in,
  and the drop count when the ring is full.
* The engine: every step is one ``engine.step`` span carrying its kind
  and KV usage, the per-step views and running aggregates agree with
  it, the mixed dispatches' live rows add up to the packed tokens the
  planner counted, and token stamps are taken after the device sync.
"""
import dataclasses
import gc

import jax
import numpy as np
import pytest

from conftest import reduced_model
from repro.configs import ServeConfig
from repro.core import metrics as metrics_mod
from repro.core.engine import Engine, Request, SamplingParams
from repro.core.metrics import EngineMetrics, EventRing, self_time

ARCH = "qwen3-0.6b"
BASE = ServeConfig(mode="chunked", max_batch=3, page_size=4, n_pages=96,
                   max_pages_per_seq=12, prefill_chunk=4, n_streams=2,
                   chunk_tokens=8)
PHASES = {"engine.schedule", "engine.plan", "engine.compose",
          "engine.dispatch", "engine.sync", "engine.advance", "host.gc"}


class _Ticks:
    """A perf_counter that advances one second a reading."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 1.0
        return self.t


@pytest.fixture
def ticking(monkeypatch):
    monkeypatch.setattr(metrics_mod, "time", _Ticks())


# ------------------------------------------------------------- recorder --
def test_spans_nest_and_link_to_their_parents():
    m = EngineMetrics()
    m.n_steps = 7
    with m.span("a") as a:
        with m.span("b") as b:
            with m.span("c") as c:
                pass
        with m.span("d", {"x": 1}) as d:
            pass
    assert [s.name for s in m.spans] == ["c", "b", "d", "a"]   # close order
    assert a.parent is None
    assert (b.parent, c.parent, d.parent) == (a.sid, b.sid, a.sid)
    assert len({a.sid, b.sid, c.sid, d.sid}) == 4
    assert all(s.step == 7 for s in m.spans)
    assert d.attrs == {"x": 1} and a.attrs is None
    assert a.t0 <= b.t0 <= c.t0 <= c.t1 <= b.t1 <= d.t0 <= d.t1 <= a.t1
    assert not m._open and m._gc_hook not in gc.callbacks


def test_self_time_is_length_less_direct_children(ticking):
    m = EngineMetrics()
    with m.span("a") as a:            # ticks: a 1..10
        with m.span("b") as b:        # b 2..7
            with m.span("c") as c:    # c 3..4
                pass
            with m.span("c") as c2:   # c 5..6
                pass
        with m.span("d") as d:        # d 8..9
            pass
    assert (a.dur, b.dur, c.dur, c2.dur, d.dur) == (9.0, 5.0, 1.0, 1.0, 1.0)
    own = self_time(list(m.spans))
    assert own == {a.sid: 9.0 - 5.0 - 1.0, b.sid: 5.0 - 1.0 - 1.0,
                   c.sid: 1.0, c2.sid: 1.0, d.sid: 1.0}


def test_a_child_whose_parent_was_dropped_counts_alone(ticking):
    m = EngineMetrics()
    with m.span("a"):
        with m.span("b") as b:
            pass
    assert self_time([b]) == {b.sid: b.dur}


def test_full_ring_drops_the_oldest_and_counts_them():
    m = EngineMetrics(spans=EventRing(4))
    for i in range(10):
        with m.span(f"s{i}"):
            pass
    assert [s.name for s in m.spans] == ["s6", "s7", "s8", "s9"]
    assert m.spans.n_dropped == 6 and m.spans.n_total == 10
    assert m.summary()["spans_dropped"] == 6


def test_a_collection_inside_a_span_is_a_host_gc_span():
    m = EngineMetrics()
    m.n_steps = 3
    gc.collect()                       # outside any span: not recorded
    assert len(m.spans) == 0
    with m.span("engine.step") as step:
        with m.span("engine.advance") as adv:
            gc.collect()
    gcs = [s for s in m.spans if s.name == "host.gc"]
    assert gcs and all(s.parent == adv.sid and s.step == 3 for s in gcs)
    assert all(adv.t0 <= s.t0 <= s.t1 <= adv.t1 for s in gcs)
    assert {"generation", "collected"} <= set(gcs[0].attrs)
    assert step.parent is None
    assert m._gc_hook not in gc.callbacks


def test_a_span_closes_on_an_exception():
    m = EngineMetrics()
    with pytest.raises(RuntimeError):
        with m.span("engine.step"):
            with m.span("engine.dispatch"):
                raise RuntimeError("boom")
    assert [s.name for s in m.spans] == ["engine.dispatch", "engine.step"]
    assert not m._open and m._gc_hook not in gc.callbacks


# --------------------------------------------------------------- engine --
@pytest.fixture(scope="module")
def setup():
    model = reduced_model(ARCH)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _requests(vocab, n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=list(rng.randint(2, vocab, size=9 + 3 * i)),
                    sampling=SamplingParams(max_new_tokens=4 + i))
            for i in range(n)]


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_chunked_live_rows_are_the_packed_tokens(setup):
    model, params = setup
    eng = Engine(model, params, BASE)
    m = eng.run(_requests(model.cfg.vocab_size))
    disp = _by_name(m.spans, "engine.dispatch")
    assert disp and all(d.attrs["program"] == "mixed" for d in disp)
    live = sum(d.attrs["rows_live"] for d in disp)
    assert live == sum(k * v for k, v in m.packed_tokens_hist.items())
    assert len(disp) == sum(m.packed_tokens_hist.values())
    shape_rows = BASE.n_streams * BASE.chunk_tokens + BASE.max_batch
    for d in disp:
        a = d.attrs
        assert 0 < a["rows_live"] <= a["rows_program"] == shape_rows
        assert a["rows_live"] == sum(n for _, n in a["chunks"]) + len(a["decodes"])
        assert len(a["rids"]) == len(a["chunks"]) + len(a["decodes"])
        assert a["decode_pages"] == sum(-(-(n + 1) // BASE.page_size)
                                        for n in a["decodes"])
    assert (m.rows_live, m.rows_program) == (
        live, sum(d.attrs["rows_program"] for d in disp))
    assert m.summary()["row_use"] == pytest.approx(live / m.rows_program)
    pages = sum(d.attrs["decode_pages"] for d in disp)
    table = len(disp) * BASE.max_batch * BASE.max_pages_per_seq
    assert 0 < pages < table
    assert (m.decode_pages_live, m.decode_pages_table) == (pages, table)
    assert m.summary()["decode_page_share"] == pytest.approx(pages / table)
    # every prompt token is a live prefill row exactly once
    assert sum(n for d in disp for _, n in d.attrs["chunks"]) == \
        m.n_prefill_tokens


def test_step_spans_carry_kind_and_kv_usage(setup):
    model, params = setup
    eng = Engine(model, params, dataclasses.replace(BASE, n_pages=40))
    m = eng.run(_requests(model.cfg.vocab_size))
    steps = _by_name(m.spans, "engine.step")
    assert len(steps) == m.n_steps and [s.step for s in steps] == \
        list(range(m.n_steps))
    assert m.step_kinds == [s.attrs["kind"] for s in steps]
    assert m.kv_usage_trace == [s.attrs["kv_usage"] for s in steps]
    s = m.summary()
    assert s["kv_usage_peak"] == max(m.kv_usage_trace) > 0
    assert s["kv_usage_mean"] == pytest.approx(
        sum(m.kv_usage_trace) / len(m.kv_usage_trace))
    # phases hang under their step, and no name is the harness's
    by_sid = {sp.sid: sp for sp in m.spans}
    for sp in m.spans:
        assert not sp.name.startswith("bench.")
        if sp.name != "engine.step":
            assert sp.name in PHASES
            top = sp
            while top.parent is not None:
                top = by_sid[top.parent]
            assert top.name == "engine.step" and top.step == sp.step


def test_views_read_only_the_ring(setup):
    """The per-step views hold what the ring holds; the summary's
    aggregates stay exact over the whole run."""
    model, params = setup
    eng = Engine(model, params, BASE)
    eng.metrics.spans = EventRing(16)
    m = eng.run(_requests(model.cfg.vocab_size))
    assert m.spans.n_dropped > 0
    assert 0 < len(m.step_kinds) < m.n_steps
    assert m.summary()["n_steps"] == m.n_steps
    with pytest.raises(AttributeError):
        m.step_kinds = []


@pytest.mark.parametrize("mode", ["sequential", "splitwiser",
                                  "splitwiser_mps", "chunked"])
def test_every_mode_names_its_phases_alike(setup, mode):
    model, params = setup
    serve = dataclasses.replace(BASE, mode=mode, enable_prefix_cache=True)
    eng = Engine(model, params, serve)
    reqs = _requests(model.cfg.vocab_size)
    reqs.append(Request(rid=99, prompt=list(reqs[3].prompt),
                        sampling=SamplingParams(max_new_tokens=3)))
    m = eng.run(reqs)
    assert {s.name for s in m.spans} - {"engine.step"} <= PHASES
    children = {}
    for s in m.spans:
        children.setdefault(s.step, []).append(s.name)
    for s in _by_name(m.spans, "engine.step"):
        if s.attrs["kind"] in ("idle", "preempt"):
            continue
        names = children[s.step]
        # a step that samples no token (a prefill chunk that completes no
        # prompt) has no sync: the host does not wait on the device
        assert {"engine.schedule", "engine.compose", "engine.dispatch",
                "engine.advance"} <= set(names)
    assert _by_name(m.spans, "engine.sync")
    disp = _by_name(m.spans, "engine.dispatch")
    mixed = [d for d in disp if d.attrs["program"] == "mixed"]
    assert sum(d.attrs["rows_live"] for d in mixed) == m.rows_live
    assert all(d.attrs["rows_live"] <= d.attrs["rows_program"] for d in mixed)
    assert {d.attrs["program"] for d in disp} <= {"mixed", "decode", "prefill"}


@pytest.mark.parametrize("mode", ["chunked", "sequential", "splitwiser"])
def test_token_stamps_follow_the_device_sync(setup, mode):
    """A token's stamp is taken once the host holds it: after the step's
    last ``engine.sync``, before the step ends (closed loop: the engine's
    clock is ``perf_counter`` with no offset)."""
    model, params = setup
    eng = Engine(model, params, dataclasses.replace(BASE, mode=mode))
    for r in _requests(model.cfg.vocab_size):
        eng.submit(r)
    n = 0
    while not eng.idle():
        events = eng.step()
        spans = eng.metrics.spans
        step = spans[-1]
        assert step.name == "engine.step"
        syncs = [s for s in spans if s.name == "engine.sync"
                 and s.step == step.step]
        for e in events:
            assert syncs[-1].t1 <= e.t <= step.t1
            assert e.t == eng.metrics.requests[e.rid].token_times[e.index]
            n += 1
    assert n > 0


def test_a_collection_in_a_step_is_parented_to_that_step(setup):
    model, params = setup
    eng = Engine(model, params, BASE)
    greedy = eng._greedy

    def collecting(logits):
        gc.collect()
        return greedy(logits)
    eng._greedy = collecting
    m = eng.run(_requests(model.cfg.vocab_size, n=2))
    steps = {s.step: s for s in _by_name(m.spans, "engine.step")}
    gcs = _by_name(m.spans, "host.gc")
    assert gcs
    for g in gcs:
        assert g.step in steps
        assert steps[g.step].t0 <= g.t0 <= g.t1 <= steps[g.step].t1
    assert eng.metrics._gc_hook not in gc.callbacks



def test_spans_land_on_the_profilers_host_plane(tmp_path):
    """In a profiled run each span is also a host annotation, nested as
    it was opened."""
    from pathlib import Path
    from jax.profiler import ProfileData
    m = EngineMetrics()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with m.span("engine.step"):
            with m.span("engine.sync"):
                gc.collect()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    host = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("engine.", "host.gc"))}
    assert {"engine.step", "engine.sync", "host.gc"} <= set(host)
    (s0, s1), (y0, y1), (g0, g1) = (host["engine.step"], host["engine.sync"],
                                    host["host.gc"])
    assert s0 <= y0 <= g0 <= g1 <= y1 <= s1
