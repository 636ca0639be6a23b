"""Drives the engine through a warm-up, a measured window and a drain.

Every timestamp is the harness's own ``time.perf_counter()``, read when
``Engine.step()`` returns; the engine's own token stamps are not used.
A request's ``arrival`` is set to its due time on the same clock, so the
engine's ``t_prefill_start - arrival`` is its queue wait.

``CallRecorder`` stands in for the engine's ``_mixed`` program attribute:
at each dispatch it notes, from host state only, which streams and decode
slots the program serves, so that the FLOPs and bytes of every call can
be counted afterwards without a device sync.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from harness.traffic import Item, Traffic

HOLD_S = 0.5      # a pause in ``on_window`` longer than this holds the schedule


@dataclass
class ReqRec:
    item: Item
    due: float                   # absolute perf_counter time
    in_window: bool
    submit: float = 0.0
    times: List[float] = field(default_factory=list)
    done: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    finish_reason: Optional[str] = None


@dataclass
class Call:
    """One dispatch of the mixed program, as the host saw it."""
    t: float
    streams: list                # [(stream object, rid, pos at dispatch)]
    slots: list                  # [(rid, seq_len at dispatch)]
    chunks: list = field(default_factory=list)    # [(start, n, n_recomputed)]
    decodes: list = field(default_factory=list)   # [(seq_len, recomputed)]


class CallRecorder:
    """Wraps ``engine._mixed``; records calls while ``on``."""

    def __init__(self, engine):
        self.engine = engine
        self.program = engine._mixed
        self.on = False
        self.calls: List[Call] = []
        self.n_dispatch = 0
        self._high: Dict[int, int] = {}      # rid -> positions computed
        self._pending: Optional[Call] = None
        engine._mixed = self

    def __call__(self, params, mb, kpg, vpg):
        self.n_dispatch += 1
        if self.on:
            eng = self.engine
            self._pending = Call(
                t=time.perf_counter(),
                streams=[(st, st.req.rid, st.pos) for st in eng.streams
                         if st is not None],
                slots=[(s.req.rid, s.seq_len) for s in eng.slots
                       if s is not None])
            self.calls.append(self._pending)
        return self.program(params, mb, kpg, vpg)

    def settle(self) -> None:
        """After a step: turn the last call's stream positions into chunk
        sizes, and mark rows that recompute positions a request had
        already computed before a preemption."""
        c, self._pending = self._pending, None
        if c is None:
            return
        for st, rid, pos in c.streams:
            n = st.pos - pos
            if n <= 0:
                continue
            high = self._high.get(rid, 0)
            c.chunks.append((pos, n, max(0, min(high, pos + n) - pos)))
            self._high[rid] = max(high, pos + n)
        for rid, seq_len in c.slots:
            high = self._high.get(rid, 0)
            c.decodes.append((seq_len, seq_len < high))
            self._high[rid] = max(high, seq_len + 1)


class Spans:
    """Host spans in the profiler's trace, or nothing when not tracing."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


@dataclass
class StepRec:
    t0: float
    t1: float
    dispatched: bool
    n_tokens: int


class Driver:
    """Runs one mix against one engine and keeps what the host saw."""

    def __init__(self, engine, traffic: Traffic, *, spans: Spans,
                 queue_target: int = 0, rid_base: int = 0):
        from repro.core.engine import Request
        from repro.core.sampler import SamplingParams
        self._Request, self._Sampling = Request, SamplingParams
        self.engine = engine
        self.traffic = traffic
        self.items = iter(traffic)
        self.next_item: Item = next(self.items)
        self.spans = spans
        self.queue_target = queue_target
        self.rid_base = rid_base
        self.recorder = CallRecorder(engine)
        self.reqs: Dict[int, ReqRec] = {}
        self.steps: List[StepRec] = []
        self.lags: List[float] = []
        self.t0 = 0.0
        self.window_end = 0.0
        self.held = 0.0          # seconds the open loop stood still after the window

    # ---------------------------------------------------------- requests --
    def _submit(self, it: Item, due: float, in_window: bool) -> None:
        rec = ReqRec(item=it, due=due, in_window=in_window)
        rid = self.rid_base + it.idx
        self.reqs[rid] = rec
        self.engine.submit(self._Request(
            rid=rid, prompt=it.prompt, arrival=due,
            sampling=self._Sampling(max_new_tokens=it.max_new_tokens)))
        rec.submit = time.perf_counter()

    def _offer_due(self, now: float) -> None:
        """Submit every open-loop request whose time has come."""
        with self.spans("bench.submit"):
            while self.t0 + self.held + self.next_item.due <= now:
                it = self.next_item
                due = self.t0 + self.held + it.due
                self._submit(it, due, it.due < self.window_end - self.t0)
                self.lags.append(self.reqs[self.rid_base + it.idx].submit - due)
                self.next_item = next(self.items)

    def _top_up(self, in_window: bool) -> None:
        """Backlog: keep ``queue_target`` requests waiting."""
        with self.spans("bench.submit"):
            while len(self.engine.waiting) < self.queue_target:
                it = self.next_item
                self._submit(it, time.perf_counter(), in_window)
                self.next_item = next(self.items)

    def step(self) -> None:
        n0 = self.recorder.n_dispatch
        with self.spans("bench.step"):
            t0 = time.perf_counter()
            events = self.engine.step()
            t1 = time.perf_counter()
        self.recorder.settle()
        for ev in events:
            rec = self.reqs[ev.rid]
            rec.times.append(t1)
            rec.tokens.append(ev.token)
            if ev.finish_reason is not None:
                rec.done = t1
                rec.finish_reason = ev.finish_reason
        self.steps.append(StepRec(t0, t1, self.recorder.n_dispatch > n0,
                                  len(events)))

    # --------------------------------------------------------- open loop --
    def open_loop(self, window_s: float, drain_s: float,
                  on_window=None) -> None:
        """Offer the mix from now on; measure requests due in the first
        ``window_s`` seconds; then keep offering load until each of them
        has finished, or ``drain_s`` more seconds have passed.  Where
        ``on_window`` holds the loop after the window (the profiler's stop
        takes a minute), later arrivals and the drain's end move on by as
        much, so that the pause neither bunches the load nor eats the
        drain."""
        self.t0 = time.perf_counter()
        self.window_end = self.t0 + window_s
        self.recorder.on = True
        stop = self.window_end + drain_s
        while True:
            now = time.perf_counter()
            self._offer_due(now)
            if on_window is not None:
                on_window(now)
                pause = time.perf_counter() - now
                if now >= self.window_end and pause > HOLD_S:
                    self.held += pause
                    stop += pause
            if now >= self.window_end and all(
                    r.done is not None for r in self.reqs.values() if r.in_window):
                break
            if now >= stop:
                break
            if self.engine.idle():
                wake = min(self.t0 + self.held + self.next_item.due, stop)
                with self.spans("bench.wait_arrival"):
                    while time.perf_counter() < wake:
                        time.sleep(min(0.001, max(0.0, wake - time.perf_counter())))
                continue
            self.step()
        self.recorder.on = False

    # ----------------------------------------------------------- backlog --
    def backlog_warm(self, steps: int) -> None:
        """Serve the backlog, unmeasured, for ``steps`` steps, so that the
        window starts in steady state."""
        for _ in range(steps):
            self._top_up(False)
            self.step()

    def backlog(self, window_s: float, drain_s: float, on_window=None) -> None:
        """Keep the queue full for ``window_s`` measured seconds.  At the
        close, requests that never started are withdrawn and those in
        flight drain, for at most ``drain_s`` seconds."""
        self.t0 = time.perf_counter()
        self.window_end = self.t0 + window_s
        self.recorder.on = True
        for rec in self.reqs.values():
            if rec.done is None:
                rec.in_window = True
        while time.perf_counter() < self.window_end:
            self._top_up(True)
            if on_window is not None:
                on_window(time.perf_counter())
            self.step()
        if on_window is not None:
            on_window(time.perf_counter())          # the window has closed
        self.recorder.on = False
        started = self.engine.metrics.requests
        waiting = self.engine.waiting
        keep = [r for r in waiting
                if started[r.rid].t_prefill_start is not None]
        for r in waiting:
            if started[r.rid].t_prefill_start is None:
                self.reqs[r.rid].in_window = False
        waiting.clear()
        waiting.extend(keep)
        stop = time.perf_counter() + drain_s
        while not self.engine.idle() and time.perf_counter() < stop:
            self.step()

    # ------------------------------------------------------------ warm-up --
    def warm(self, prompt_len: int) -> None:
        """Compile every program the window runs: one request whose prompt
        fits one chunk and that decodes once, through the same engine."""
        rid = self.rid_base - 1
        it = Item(idx=-1, due=0.0, prompt=[1] * prompt_len, max_new_tokens=2)
        self.reqs[rid] = ReqRec(item=it, due=time.perf_counter(), in_window=False)
        self.engine.submit(self._Request(
            rid=rid, prompt=it.prompt,
            sampling=self._Sampling(max_new_tokens=2)))
        while not self.engine.idle():
            self.step()
        self.engine.poll()
        del self.reqs[rid]
        self.steps.clear()

    @property
    def engine_metrics(self):
        """The engine's per-request records, by rid."""
        return self.engine.metrics.requests

    def release(self) -> None:
        """Give the engine its own program back."""
        self.engine._mixed = self.recorder.program

    def window_queued(self) -> int:
        """Requests due in the window that wait for their first chunk."""
        started = self.engine.metrics.requests
        return sum(1 for r in self.engine.waiting
                   if self.reqs[r.rid].in_window
                   and started[r.rid].t_prefill_start is None)

    def window_requests(self) -> List[ReqRec]:
        return [r for r in self.reqs.values() if r.in_window]
