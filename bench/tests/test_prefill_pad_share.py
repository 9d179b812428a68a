"""prefill_pad_share on hand-built span lists, on spans without `width`
(it reports nothing), and on a tiny engine's own spans."""

import math
import types

import pytest

import cells

PAD = cells.load_module("metrics", "prefill_pad_share")


def span(name, t, dur, **args):
    return (name, t, dur, args)


def ctx(spans):
    window = types.SimpleNamespace(t0=0.0, t_close=10.0, records=[])
    return types.SimpleNamespace(spans=spans, window=window, notes={})


# two prefill calls in the window [0, 10), 4 and 2 lanes wide, a decode
# chunk, and a third prefill call outside the window
SPANS = [
    span("prefill/bucket64", 0.1, 0.2, bucket=64, lanes=2, width=4),
    span("decode/chunk8", 0.4, 0.5, steps=8, lanes=3),
    span("prefill/bucket128", 1.6, 0.2, bucket=128, lanes=1, width=2),
    span("prefill/bucket16", 10.5, 0.2, bucket=16, lanes=1, width=8),
]


def test_prefill_pad_share_reads_width_over_lanes():
    assert PAD.read(ctx(SPANS)) == pytest.approx(
        100.0 * ((4 - 2) + (2 - 1)) / (4 + 2))


def test_spans_without_width_give_nothing():
    bare = [(n, t, d, {k: v for k, v in a.items() if k != "width"})
            for n, t, d, a in SPANS]
    assert PAD.read(ctx(bare)) is None
    assert PAD.read(ctx([s for s in SPANS if s[0].startswith("decode")])) \
        is None


def test_prefill_pad_share_on_a_tiny_engine():
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
    c = ctx(log.spans)
    c.window.t_close = math.inf
    # three one-request calls, each two lanes wide (W(b) = slots = 2)
    assert PAD.read(c) == pytest.approx(50.0)
