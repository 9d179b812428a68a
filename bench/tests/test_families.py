"""A CPU rehearsal that the harness takes families other than the dense
one from new files alone: a tiny state-space config and a tiny hybrid
config, read from files outside bench/configs, go through `run.Setup`
(weights, engine, warm-up) and a short open-loop window of bursty (gamma)
arrivals, and every request finishes. The per-layer metrics that read the
engine's spans alone read a number there, and the served programs' memory
analysis compiles. `correct` is not read: no reference of these families
is kept here yet."""

from pathlib import Path

import pytest

import cells
import run
from repro.configs.base import SSMConfig

DATA = Path(__file__).resolve().parent / "data"
SECONDS = 3.0
SPAN_METRICS = ("queue_wait_p90_ms", "prefill_call_ms", "decode_step_ms",
                "first_token_hold_p90_ms", "prefill_stall_share",
                "step_host_ms")


@pytest.mark.parametrize("config,family", [("tiny-ssm", "ssm"),
                                           ("tiny-hybrid", "hybrid")])
def test_family_serves_a_bursty_window(config, family):
    conf = cells.load_json("configs", config, root=DATA)
    mix = cells.load_json("traffic", "rehearsal_burst", root=DATA)
    cfg = run.arch_config(conf)
    assert cfg.family == family and isinstance(cfg.ssm, SSMConfig)
    dep = conf["deployment"]
    st = run.Setup(conf, mix, 2 ** 35 + 3, trace=True)
    assert st.engine.bucketed
    assert st.n_warm == len(run.buckets(mix, dep["max_len"]))
    win = run.serve(st.loop(2 ** 35 + 3, True), mix, 2 ** 35 + 3, SECONDS)
    assert len(win.records) == round(mix["arrivals"]["rate_per_s"] * SECONDS)
    assert all(r.done for r in win.records)
    assert all(len(r.req.out) == r.spec.out_len for r in win.records)
    assert win.tok_s > 0
    ctx = run.Ctx(conf=conf, ref=None, dep=dep, window=win,
                  spans=st.spans.spans, trace=None, peak=None,
                  buckets=run.buckets(mix, dep["max_len"]))
    for name in SPAN_METRICS:
        v = cells.load_module("metrics", name).read(ctx)
        assert v is not None and v >= 0, name
    run._memory_analysis(st.engine, dep, mix)
