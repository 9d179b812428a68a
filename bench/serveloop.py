"""The measured window: an open-loop generator and the engine in one
thread.

Before each `engine.step()` every request whose due time has passed is
submitted; latencies run from the due time, so a generator held up by a
long step charges the wait to the requests it delayed. Tokens are observed
when `step()` returns, which is when the engine hands them back.

The window opens at t0 and closes at the first step return at or after
t0 + seconds (or at t0 + seconds exactly, if the engine is idle then), so
every step inside it is whole. tok_s counts, over that window, the prompt
tokens of requests whose first token came back in it and every output
token that came back in it; padded positions are never counted.

After the window the drain serves the requests due in it until all have
finished or `drain_cap_s` passes; those unfinished then are failed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable


@dataclasses.dataclass
class Record:
    spec: object
    req: object
    due: float                 # clock time the request was due
    submitted: float
    times: list = dataclasses.field(default_factory=list)  # per token

    @property
    def done(self) -> bool:
        return self.req.state == "done"

    def ttft(self) -> float:
        return self.times[0] - self.due if self.done else math.inf

    def tpot(self) -> float:
        if not self.done:
            return math.inf
        n = len(self.times)
        return (self.times[-1] - self.times[0]) / (n - 1) if n > 1 else 0.0


@dataclasses.dataclass
class Window:
    t0: float
    t_close: float
    records: list              # due in the window / admitted in it
    prompt_tokens: int         # prefilled in the window
    output_tokens: int         # returned in the window
    lateness: list             # submit time - due time, open loop
    steps: int                 # engine steps in the window
    drain_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t0

    @property
    def tok_s(self) -> float:
        return (self.prompt_tokens + self.output_tokens) / self.seconds


def _null(name: str):
    return contextlib.nullcontext()


class Loop:
    def __init__(self, engine, make_request: Callable, *,
                 clock=time.perf_counter, sleep=time.sleep,
                 annotate=None, tick=None, on_close=None):
        self.engine = engine
        self.make_request = make_request
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate or _null
        self.tick = tick or (lambda now: None)
        self.on_close = on_close or (lambda: None)
        self.inflight: list[Record] = []
        self.prompt_tokens = 0
        self.output_tokens = 0

    def _submit(self, spec, due, now) -> Record:
        with self.annotate("bench.submit"):
            rec = Record(spec, self.make_request(spec), due, now)
            self.engine.submit(rec.req)
            self.inflight.append(rec)
        return rec

    def _observe(self, now: float, in_window: bool) -> None:
        with self.annotate("bench.observe"):
            keep = []
            for rec in self.inflight:
                new = len(rec.req.out) - len(rec.times)
                if new > 0:
                    if in_window:
                        if not rec.times:
                            self.prompt_tokens += len(rec.req.prompt)
                        self.output_tokens += new
                    rec.times.extend([now] * new)
                if not rec.req.finished:
                    keep.append(rec)
            self.inflight = keep

    def _busy(self) -> bool:
        return bool(self.engine.queue) or any(
            r is not None for r in self.engine.active)

    def _step(self, in_window: bool) -> None:
        with self.annotate("engine.step"):
            self.engine.step()
        self._observe(self.clock(), in_window)

    def _drain(self, records, t_close: float, cap: float) -> float:
        while any(not r.req.finished for r in records) and \
                self.clock() - t_close < cap and self._busy():
            self._step(False)
        return self.clock() - t_close

    def run_open(self, specs, seconds: float, drain_cap: float) -> Window:
        pending = sorted(specs, key=lambda s: s.due)
        records, lateness = [], []
        steps, i = 0, 0
        t0 = self.clock()
        while True:
            now = self.clock()
            self.tick(now)
            while i < len(pending) and t0 + pending[i].due <= now:
                due = t0 + pending[i].due
                records.append(self._submit(pending[i], due, now))
                lateness.append(now - due)
                i += 1
            if now - t0 >= seconds:
                break
            if self._busy():
                self._step(True)
                steps += 1
            else:
                nxt = t0 + (pending[i].due if i < len(pending) else seconds)
                with self.annotate("bench.wait"):
                    self.sleep(max(0.0, min(nxt, t0 + seconds) - now))
        t_close = self.clock()
        self.on_close()
        w = Window(t0, t_close, records, self.prompt_tokens,
                   self.output_tokens, lateness, steps)
        w.drain_s = self._drain(records, t_close, drain_cap)
        return w
