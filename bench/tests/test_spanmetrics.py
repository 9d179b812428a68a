"""The three readers of the engine's span tree (first_token_hold_p90_ms,
prefill_stall_share, step_host_ms) on hand-built span lists, on spans
without the tree (they report nothing), and on a tiny engine's own spans."""

import math
import types

import pytest

import cells

HOLD = cells.load_module("metrics", "first_token_hold_p90_ms")
STALL = cells.load_module("metrics", "prefill_stall_share")
HOST = cells.load_module("metrics", "step_host_ms")


def span(name, t, dur, sid, parent, **args):
    return (name, t, dur, {"id": sid, "parent": parent, **args})


# two steps in a window [0, 10): the second admits a request at the
# recycle pass under decode.retire, and a third step outside the window
TREE = [
    span("step", 0.0, 1.0, 0, None, prefills=1, decode_steps=8),
    span("admit", 0.0, 0.35, 1, 0),
    span("prefill.pack", 0.0, 0.1, 2, 1),
    span("prefill/bucket8", 0.1, 0.2, 3, 1, bucket=8, lanes=2, tokens=9,
         rids=[1, 2], stalled_lanes=2, compiled=False),
    span("decode/chunk8", 0.4, 0.5, 4, 0, steps=8, lanes=4, tokens=32,
         live_end=4, rids=[1, 2, 5, 6], compiled=False),
    span("decode.retire", 0.9, 0.05, 5, 0),
    span("step", 1.0, 1.2, 6, None, prefills=1, decode_steps=8),
    span("decode/chunk8", 1.0, 0.5, 7, 6, steps=8, lanes=2, tokens=16,
         live_end=1, rids=[5, 6], compiled=False),
    span("decode.retire", 1.5, 0.6, 8, 6),
    span("admit", 1.6, 0.3, 9, 8),
    span("prefill/bucket16", 1.6, 0.2, 10, 9, bucket=16, lanes=1,
         tokens=12, rids=[3], stalled_lanes=1, compiled=False),
    span("step", 2.2, 0.01, 11, None, prefills=0, decode_steps=0),
    span("admit", 2.2, 0.01, 12, 11),
    span("step", 10.5, 1.0, 13, None, prefills=0, decode_steps=8),
    span("decode/chunk8", 10.5, 1.0, 14, 13, steps=8, lanes=1, tokens=8,
         live_end=1, rids=[3], compiled=False),
]


def ctx(spans, rids):
    recs = [types.SimpleNamespace(req=types.SimpleNamespace(rid=r), due=0.0)
            for r in rids]
    window = types.SimpleNamespace(t0=0.0, t_close=10.0, records=recs)
    return types.SimpleNamespace(spans=spans, window=window, notes={})


def test_first_token_hold_reads_the_holding_step():
    # rids 1, 2: step ends at 1.0, prefill at 0.3; rid 3 prefilled in the
    # recycle pass: step ends at 2.2, prefill at 1.8
    assert HOLD.read(ctx(TREE, [1, 2, 3])) == pytest.approx(700.0)
    assert HOLD.read(ctx(TREE, [3])) == pytest.approx(400.0)
    # a request never prefilled is infinite and takes the tail with it
    assert HOLD.read(ctx(TREE, [1, 2, 3, 4])) is None
    assert HOLD.read(ctx(TREE, [1, 2, 3, 4] + [3] * 30)) == pytest.approx(
        400.0)


def test_prefill_stall_share_weights_by_lanes():
    stalled = 0.2 * 2 + 0.2 * 1
    decoding = 0.5 * 4 + 0.5 * 2           # the chunk at 10.5 is outside
    assert STALL.read(ctx(TREE, [])) == pytest.approx(
        100.0 * stalled / (stalled + decoding))


def test_step_host_ms_leaves_out_device_calls():
    # step 0: 1.0 - 0.2 - 0.5; step 6: 1.2 - 0.5 - 0.2 (its prefill sits
    # under decode.retire); step 11 ran no decode, step 13 is outside
    assert HOST.read(ctx(TREE, [])) == pytest.approx(
        1e3 * ((1.0 - 0.7) + (1.2 - 0.7)) / 2)


def test_spans_without_the_tree_give_nothing():
    flat = [(name, t, d, {k: v for k, v in a.items()
                          if k not in ("id", "parent", "stalled_lanes",
                                       "compiled")})
            for name, t, d, a in TREE if "/" in name]
    for metric in (HOLD, STALL, HOST):
        assert metric.read(ctx(flat, [1, 2, 3])) is None


def test_readers_on_a_tiny_engine():
    import jax
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models.model import Model
    from repro.serve.engine import Request, ServeEngine
    from run import SpanLog

    cfg = reduced(get_arch("granite-8b"))
    model = Model(cfg)
    log = SpanLog(0.0)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                      max_len=32, decode_chunk=4, tracer=log)
    rng = np.random.default_rng(0)
    for i, n in enumerate((5, 9, 17)):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                                      dtype=np.int32),
                           max_new_tokens=6))
    eng.run_to_completion()
    c = ctx(log.spans, [0, 1, 2])
    c.window.t_close = math.inf
    steps = [s for s in log.spans if s[0] == "step"]
    assert sum(s[3]["prefills"] for s in steps) == 3    # three buckets
    hold = HOLD.read(c)
    assert 0 < hold < 1e3 * max(s[2] for s in steps)
    assert 0 < STALL.read(c) < 100
    decoding = [s[2] for s in steps if s[3]["decode_steps"]]
    assert 0 < HOST.read(c) < 1e3 * sum(decoding) / len(decoding)
