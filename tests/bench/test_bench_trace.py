"""The trace reduction: device busy union, idle gaps labelled with the
harness span they fell in, and device time per program.  Checked on a
hand-made trace with known answers, and on a short stretch of a trace
recorded on a TPU v5e (opt-125m.chat), trimmed to a few kB."""
import json
from pathlib import Path

import pytest

import benchroot  # noqa: F401  (puts bench/ on the path)
from harness import trace

DATA = Path(__file__).resolve().parent / "data" / "trace_opt125m_chat.json"


def test_hand_made_trace():
    ev = {
        "host": [["bench.window", 0, 100], ["bench.step", 0, 40],
                 ["bench.submit", 40, 5], ["bench.wait_arrival", 45, 30],
                 ["bench.step", 75, 25]],
        # a loop op holds two ops (busy once), one sits inside the wait
        "ops": [["while.1", 5, 15], ["fusion.1", 5, 5], ["fusion.2", 12, 8],
                ["copy.3", 50, 5], ["fusion.1", 80, 10], ["late", 95, 20]],
        "modules": [["jit_mixed_fn(1)", 5, 15], ["jit_greedy_tokens(2)", 50, 5],
                    ["jit_mixed_fn(1)", 80, 30]],
    }
    lo, hi = trace.window_of(ev)
    assert (lo, hi) == (0, 100)
    red = trace.reduce(ev, lo, hi)
    # busy: [5,20) [50,55) [80,90) [95,100) = 15 + 5 + 10 + 5
    assert red["busy_ns"] == 35
    assert red["idle_share"] == pytest.approx(0.65)
    # idle [0,5) [20,50) [55,80) [90,95), cut at the spans' edges
    assert red["idle_by_span"] == {"bench.step": 5 + 20 + 5 + 5,
                                   "bench.submit": 5, "bench.wait_arrival": 5 + 20}
    assert sorted(red["gaps"], key=lambda g: (-g[1], g[0]))[:2] == [
        ("bench.step", 20), ("bench.wait_arrival", 20)]
    # self time: the loop op less the two ops inside it
    assert red["ops"] == {"while.1": 2, "fusion.1": 15, "fusion.2": 8,
                          "copy.3": 5, "late": 5}
    assert trace.program_time(red, "mixed_fn") == (15 + 20, 2)
    assert trace.top(red["ops"], 2) == [["fusion.1", 15], ["fusion.2", 8]]


def test_op_names_are_shortened_to_name_and_type():
    long = ("%copy.150 = bf16[12,8192,16,12,64]{1,4,3,2,0:T(8,128)(2,1)} "
            "copy(bf16[12,8192,16,12,64]{1,4,3,2,0} %param.3)")
    assert trace.short_name(long) == "%copy.150 = bf16[12,8192,16,12,64]"
    assert trace.short_name("jit_mixed_fn(1)") == "jit_mixed_fn(1)"


def brute_busy(ops, lo, hi):
    """Busy time by marking every boundary-delimited segment."""
    cuts = sorted({lo, hi, *(max(min(s, hi), lo) for _, s, _ in ops),
                   *(max(min(s + d, hi), lo) for _, s, d in ops)})
    busy = 0
    for a, b in zip(cuts, cuts[1:]):
        if any(s <= a and b <= s + d for _, s, d in ops):
            busy += b - a
    return busy


def _nested(ops, lo, hi):
    """Time of ops that lie inside another op (counted twice above)."""
    ops = sorted(([s, s + d] for _, s, d in ops if s < hi and s + d > lo),
                 key=lambda o: (o[0], -o[1]))
    inner, stack = 0.0, []
    for a, b in ops:
        a, b = max(a, lo), min(b, hi)
        while stack and stack[-1] <= a:
            stack.pop()
        if stack:
            inner += min(b, stack[-1]) - a
        stack.append(b)
    return inner


def test_recorded_chip_trace():
    ev = json.loads(DATA.read_text())
    lo, hi = trace.window_of(ev)
    red = trace.reduce(ev, lo, hi)
    assert red["busy_ns"] == pytest.approx(brute_busy(ev["ops"], lo, hi))
    assert 0 < red["idle_share"] < 1
    idle = sum(d for _, d in red["gaps"])
    assert idle == pytest.approx(red["window_ns"] - red["busy_ns"])
    ns, runs = trace.program_time(red, "mixed_fn")
    assert runs >= 1 and 0 < ns <= red["busy_ns"] * 1.0001
    assert set(red["idle_by_span"]) <= {"bench.step", "bench.submit",
                                        "bench.wait_arrival", "outside bench spans"}
    # self times add up to the time the ops cover, nested ones once
    assert sum(red["ops"].values()) == pytest.approx(
        sum(min(s + d, hi) - max(s, lo) for _, s, d in ev["ops"]
            if s < hi and s + d > lo) - _nested(ev["ops"], lo, hi), rel=1e-6)
