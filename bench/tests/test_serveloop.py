"""The window's accounting, with a fake engine on a fake clock."""

import math

import pytest

from loadgen import Spec
from serveloop import Loop


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Req:
    def __init__(self, rid, n_prompt, n_out):
        self.rid, self.prompt, self.max_new_tokens = rid, [0] * n_prompt, n_out
        self.out, self.state = [], "queued"

    @property
    def finished(self):
        return self.state in ("done", "rejected", "expired")


class Engine:
    """Each step takes `dt` seconds: it prefills every queued request
    (first token) and decodes one more token for every earlier one."""

    def __init__(self, clock, dt, slots=4):
        self.clock, self.dt = clock, dt
        self.queue, self.active = [], [None] * slots

    def submit(self, r):
        self.queue.append(r)

    def step(self):
        self.clock.t += self.dt
        for i, r in enumerate(self.active):
            if r is not None:
                r.out.append(1)
                if len(r.out) >= r.max_new_tokens:
                    r.state = "done"
                    self.active[i] = None
        while self.queue and None in self.active:
            r = self.queue.pop(0)
            r.out.append(1)
            self.active[self.active.index(None)] = r


def make_loop(dt):
    clock = Clock()
    eng = Engine(clock, dt)
    loop = Loop(eng, lambda s: Req(s.idx, s.prompt_len, s.out_len),
                clock=clock, sleep=clock.sleep)
    return clock, eng, loop


def test_open_window_closes_on_a_whole_step_and_counts_its_tokens():
    clock, eng, loop = make_loop(dt=0.3)
    specs = [Spec(0, 0.0, 10, 5)]
    w = loop.run_open(specs, seconds=1.0, drain_cap=10.0)
    # steps return at 0.3, 0.6, 0.9, 1.2: the window closes at 1.2
    assert w.seconds == pytest.approx(1.2)
    assert w.steps == 4
    # 10 prompt tokens, 4 output tokens returned in the window; the fifth
    # comes back in the drain and is not counted
    assert (w.prompt_tokens, w.output_tokens) == (10, 4)
    assert w.tok_s == pytest.approx(14 / 1.2)
    r = w.records[0]
    assert r.done and len(r.times) == 5
    assert r.ttft() == pytest.approx(0.3)
    assert r.tpot() == pytest.approx(0.3)
    assert w.drain_s == pytest.approx(0.3)


def test_open_window_idle_end_and_late_submission():
    clock, eng, loop = make_loop(dt=0.5)
    specs = [Spec(0, 0.0, 4, 2), Spec(1, 0.2, 6, 2)]
    w = loop.run_open(specs, seconds=2.0, drain_cap=10.0)
    # the second request was due at 0.2 but the first step ran to 0.5
    assert w.lateness == pytest.approx([0.0, 0.3])
    assert w.records[1].ttft() == pytest.approx(0.8)
    # engine idle from 1.5: the window closes at 2.0 exactly
    assert w.seconds == pytest.approx(2.0)
    assert (w.prompt_tokens, w.output_tokens) == (10, 4)


def test_unfinished_requests_are_failed_and_infinite():
    clock, eng, loop = make_loop(dt=1.0)
    specs = [Spec(0, 0.0, 4, 100)]
    w = loop.run_open(specs, seconds=0.5, drain_cap=3.0)
    r = w.records[0]
    assert not r.done
    assert math.isinf(r.ttft()) and math.isinf(r.tpot())
