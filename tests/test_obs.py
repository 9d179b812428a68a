"""Observability layer tests: metrics registry semantics, engine
telemetry population, Chrome trace-event export, kernel autotune metrics,
and the model-vs-measured drift gate (the wave model's predicted
utilization over the slice-accurate scheduler's measured utilization on
the engine's actually-recorded timeline must stay inside the calibrated
parity band of tests/test_simulator.py)."""

import json
import math

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models.model import Model
from repro.obs.drift import drift_report, effective_tops_summary
from repro.obs.export import Span, to_chrome_trace, write_chrome_trace
from repro.obs.metrics import MetricsRegistry, percentile, registry
from repro.serve.engine import Request, ServeEngine
from repro.tenancy.trace import ServeTraceRecorder

# the wave model may be optimistic by up to the bert-family calibrated
# ceiling (tests/test_simulator.py PARITY_CASES) and must never predict
# below the slice-accurate scheduler by more than the resnet floor
DRIFT_BAND = (0.8, 1.55)


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_counter_gauge_series_and_labels():
    reg = MetricsRegistry()
    reg.counter("hits", path="bucketed").inc()
    reg.counter("hits", path="bucketed").inc(2)
    reg.counter("hits", path="exact").inc()
    reg.gauge("depth").set(7)
    assert reg.value("hits", path="bucketed") == 3
    assert reg.value("hits", path="exact") == 1
    assert reg.value("depth") == 7
    assert reg.value("never_written") is None
    # same name, different labels -> distinct series, both findable
    assert set(reg.find("hits")) == {"hits{path=bucketed}",
                                     "hits{path=exact}"}
    with pytest.raises(ValueError):
        reg.counter("hits", path="exact").inc(-1)


def test_histogram_percentiles_and_decimation():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1 and s["max"] == 100
    assert s["p50"] == pytest.approx(np.percentile(range(1, 101), 50))
    assert s["p99"] == pytest.approx(np.percentile(range(1, 101), 99))
    # bounded buffer: exact count/total survive decimation
    from repro.obs.metrics import Histogram
    small = Histogram(max_samples=8)
    for v in range(1000):
        small.record(float(v))
    assert small.count == 1000
    assert len(small._samples) <= 8
    assert small.max == 999.0
    # n-at-once recording (a chunk charging every delivered token)
    hh = Histogram()
    hh.record(5.0, n=10)
    assert hh.count == 10 and hh.total == 50.0


def test_percentile_matches_numpy():
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (0, 10, 50, 90, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert math.isnan(percentile([], 50))


def test_snapshot_is_json_round_trippable():
    reg = MetricsRegistry()
    reg.counter("c", a="1").inc(5)
    reg.gauge("g").set(2.5)
    reg.histogram("h").record(1.0)
    snap = json.loads(reg.dumps())
    assert snap["counters"] == {"c{a=1}": 5.0}
    assert snap["gauges"] == {"g": 2.5}
    assert snap["histograms"]["h"]["count"] == 1
    assert len(reg) == 3
    reg.clear()
    assert len(reg) == 0


# --------------------------------------------------------------------------
# engine telemetry + trace export
# --------------------------------------------------------------------------

def _served_engine(metrics=None, tracer=None, lengths=(5, 9, 17), max_new=4):
    cfg = reduced(get_arch("granite-8b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=2, max_len=32,
                      metrics=metrics, tracer=tracer)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                               dtype=np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=200)
    assert all(r.done for r in reqs)
    return cfg, eng, reqs


def test_engine_populates_serving_metrics():
    reg = MetricsRegistry()
    cfg, eng, reqs = _served_engine(metrics=reg)
    snap = reg.snapshot()
    assert reg.value("serve.prefill.tokens") == 5 + 9 + 17
    assert reg.value("serve.prefill.calls", path="bucketed") >= 1
    # every request's decode tokens were counted (prefill token excluded)
    decoded = sum(len(r.out) - 1 for r in reqs)
    assert reg.value("serve.decode.tokens") == decoded
    assert reg.value("serve.decode.chunks") >= 1
    assert reg.value("serve.queue_depth") == 0          # drained
    assert 0 < snap["gauges"]["serve.slot_occupancy"] <= 1.0
    assert snap["histograms"]["serve.decode.chunk_len"]["count"] \
        == reg.value("serve.decode.chunks")
    assert reg.value("serve.prefill.seconds") > 0
    assert reg.value("serve.decode.seconds") > 0


def test_engine_emits_spans_and_valid_chrome_trace(tmp_path):
    rec = ServeTraceRecorder()
    _, eng, _ = _served_engine(tracer=rec)
    assert rec.spans, "engine emitted no spans"
    cats = {s.cat for s in rec.spans}
    assert cats == {"prefill", "decode", "engine"}
    assert rec.phase_seconds("prefill") > 0
    assert rec.phase_seconds("decode") > 0
    # decode spans carry the device-side accumulators in their args
    dspans = [s for s in rec.spans if s.cat == "decode"]
    assert sum(s.args["tokens"] for s in dspans) \
        == rec.phase_tokens("decode")

    out = tmp_path / "trace.json"
    n = write_chrome_trace(str(out), rec.spans)
    assert n == len(rec.spans)
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["name"] for e in meta} >= {"sosa-serve", "prefill",
                                                "decode"}
    assert len(complete) == len(rec.spans)
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0          # rebased to t=0
        assert {"name", "cat", "pid", "tid", "args"} <= set(e)
    # phase tracks: distinct tid per category
    tids = {e["cat"]: e["tid"] for e in complete}
    assert tids["prefill"] != tids["decode"]
    # chronological within the engine's step-locked order
    ts = [e["ts"] for e in complete]
    assert min(ts) == 0.0


# the engine's span tree: each span's name with any /<size> suffix
# dropped, and the name of the span it must sit in (recycling off)
SPAN_PARENT = {
    "step": None, "admit": "step", "prefill.pack": "admit",
    "prefill": "admit", "prefill.dispatch": "prefill",
    "prefill.sync": "prefill", "decode.prep": "step", "decode": "step",
    "decode.dispatch": "decode", "decode.sync": "decode",
    "decode.retire": "step",
}


def _kind(span):
    return span.name.split("/")[0]


def _root(by_id, s):
    while s.args["parent"] is not None:
        s = by_id[s.args["parent"]]
    return s


def test_engine_span_tree_names_links_and_nesting():
    rec = ServeTraceRecorder()
    _served_engine(tracer=rec)
    by_id = {s.args["id"]: s for s in rec.spans}
    assert len(by_id) == len(rec.spans)                # ids are unique
    assert {_kind(s) for s in rec.spans} == set(SPAN_PARENT)
    for s in rec.spans:
        pid = s.args["parent"]
        want = SPAN_PARENT[_kind(s)]
        if want is None:
            assert pid is None
            continue
        parent = by_id[pid]
        assert _kind(parent) == want and pid < s.args["id"]
        assert parent.ts <= s.ts and s.end <= parent.end, (s, parent)
    for step in (s for s in rec.spans if s.name == "step"):
        under = [s for s in rec.spans if s.args["parent"] is not None
                 and _root(by_id, s) is step]
        assert step.args["prefills"] == sum(
            s.cat == "prefill" for s in under)
        assert step.args["decode_steps"] == sum(
            s.args["steps"] for s in under if s.cat == "decode")
    # the device-call spans keep their names and args
    for s in rec.spans:
        if s.cat == "prefill":
            assert s.name == f"prefill/bucket{s.args['bucket']}"
            assert len(s.args["rids"]) == s.args["lanes"]
        elif s.cat == "decode":
            assert s.name == f"decode/chunk{s.args['steps']}"


def test_engine_spans_carry_rids_stalled_lanes_and_compiles():
    rec, reg = ServeTraceRecorder(), MetricsRegistry()
    # 5 and 9 fall in different buckets: two prefill calls in the first
    # step, the second stalling the lane the first one started
    _served_engine(metrics=reg, tracer=rec)
    pre = [s for s in rec.spans if s.cat == "prefill"]
    dec = [s for s in rec.spans if s.cat == "decode"]
    assert [(s.args["rids"], s.args["stalled_lanes"]) for s in pre] == [
        ([0], 0), ([1], 1), ([2], 0)]
    assert dec[0].args["rids"] == [0, 1]
    assert all(len(s.args["rids"]) == s.args["lanes"] for s in dec)
    # compiled on the first call of each shape and never after
    for spans in (pre, dec):
        seen = set()
        for s in spans:
            assert s.args["compiled"] == (s.name not in seen), s
            seen.add(s.name)
    assert reg.value("serve.compiles", fn="prefill") == len(
        {s.name for s in pre})
    assert reg.value("serve.compiles", fn="decode") == len(
        {s.name for s in dec})


@pytest.mark.parametrize("buckets", [True, False])
def test_failed_device_calls_leave_no_span_and_no_orphan(buckets):
    """A device call that fails for good leaves no span, and none of the
    spans inside it; on the exact-length path (buckets off) the prefill's
    children sit under prefill/exact{S}."""
    from repro.serve.chaos import ChaosConfig
    cfg = reduced(get_arch("granite-8b"))
    model = Model(cfg)
    rec, reg = ServeTraceRecorder(), MetricsRegistry()
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                      max_len=32, metrics=reg, tracer=rec,
                      prefill_buckets=buckets, max_retries=0,
                      chaos=ChaosConfig(seed=3, p_fault=0.4,
                                        transient_tries=9))
    rng = np.random.default_rng(0)
    for i, n in enumerate((5, 9, 17, 12, 7, 20)):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                                      dtype=np.int32),
                           max_new_tokens=4))
    eng.run_to_completion(max_steps=200)
    assert reg.value("serve.chaos.permanent_faults") > 0
    by_id = {s.args["id"]: s for s in rec.spans}
    assert all(s.args["parent"] is None or s.args["parent"] in by_id
               for s in rec.spans)
    calls = sum(reg.value("serve.prefill.calls", path=p) or 0
                for p in ("bucketed", "exact"))
    assert calls == sum(s.cat == "prefill" for s in rec.spans) > 0
    assert reg.value("serve.decode.chunks") == sum(
        s.cat == "decode" for s in rec.spans)
    pre = "prefill/bucket" if buckets else "prefill/exact"
    for s in rec.spans:
        if s.name in ("prefill.dispatch", "prefill.sync") or (
                s.name == "prefill.pack" and not buckets):
            assert by_id[s.args["parent"]].name.startswith(pre)


def test_engine_without_tracer_makes_no_span_and_no_annotation(monkeypatch):
    import repro.serve.engine as engine_mod
    opened = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    def no_span(*a, **k):
        raise AssertionError("span made with no tracer")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    real_span = engine_mod._OpenSpan
    monkeypatch.setattr(engine_mod, "_OpenSpan", no_span)
    _, eng, _ = _served_engine(metrics=MetricsRegistry())
    assert eng._tree is None and opened == []
    # the counter sees the annotations of a traced engine
    monkeypatch.setattr(engine_mod, "_OpenSpan", real_span)
    rec = ServeTraceRecorder()
    _served_engine(tracer=rec)
    assert len(opened) == len(rec.spans)
    assert {n.split(".")[1] for n in opened} == {"step", "admit", "prefill",
                                                 "decode"}


def test_profiler_trace_holds_the_engine_spans(tmp_path):
    """With a tracer attached, a jax.profiler capture shows the engine's
    spans on the host plane, nested as the tree is."""
    from jax.profiler import ProfileData
    rec = ServeTraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _served_engine(tracer=rec, lengths=(5, 9))
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.prefill.sync", "engine.decode", "engine.decode.sync",
            "engine.decode.retire"} <= set(host)
    assert len(host["engine.step"]) == sum(s.name == "step"
                                           for s in rec.spans)
    for s, e in host["engine.decode.sync"]:
        assert any(ps <= s and e <= pe for ps, pe in host["engine.decode"])


def test_to_chrome_trace_empty_spans():
    doc = to_chrome_trace([])
    assert doc["traceEvents"][0]["args"]["name"] == "sosa-serve"
    assert all(e["ph"] == "M" for e in doc["traceEvents"])


def test_span_end_property():
    s = Span(name="x", ts=1.5, dur=0.25)
    assert s.end == 1.75


# --------------------------------------------------------------------------
# kernel autotune metrics
# --------------------------------------------------------------------------

def test_choose_blocks_records_autotune_metrics():
    from repro.parallel.autoshard import choose_blocks, tile_utilization
    reg = registry()
    shape = (7777, 4096, 4096)                   # unique -> guaranteed miss
    choose_blocks.cache_clear()
    before_miss = reg.value("autotune.cache", result="miss") or 0
    before_hit = reg.value("autotune.cache", result="hit") or 0
    blocks = choose_blocks(*shape)
    assert (reg.value("autotune.cache", result="miss") or 0) \
        == before_miss + 1
    choose_blocks(*shape)
    assert (reg.value("autotune.cache", result="hit") or 0) \
        == before_hit + 1
    util = reg.value("autotune.tile_util",
                     shape="x".join(str(d) for d in shape))
    assert util is not None
    assert 0 < util <= 1.0
    assert util == pytest.approx(tile_utilization(*shape, blocks=blocks))


def test_tile_utilization_penalizes_padding():
    from repro.parallel.autoshard import tile_utilization
    # aligned shape wastes nothing; a ragged M pays padded-MAC overhead
    full = tile_utilization(4096, 4096, 4096, blocks=(256, 256, 256))
    ragged = tile_utilization(100, 4096, 4096, blocks=(256, 256, 256))
    assert full == pytest.approx(1.0)
    assert ragged < full


# --------------------------------------------------------------------------
# drift + effective TOPS (the acceptance gates)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    reg = MetricsRegistry()
    rec = ServeTraceRecorder()
    cfg = reduced(get_arch("granite-8b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=4, max_len=64,
                      metrics=reg, tracer=rec)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                               dtype=np.int32),
                    max_new_tokens=6)
            for i, n in enumerate((5, 9, 17, 12, 33, 7))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=300)
    assert all(r.done for r in reqs)
    return cfg, reg, rec


def test_drift_rows_per_phase_inside_calibrated_band(traced_run):
    """The tentpole gate: one drift row per serving phase, and predicted
    (wave model) utilization over measured (slice-accurate) utilization on
    the engine's real recorded timeline stays inside the calibrated
    parity band."""
    cfg, reg, rec = traced_run
    rows = drift_report(rec, cfg, metrics=reg, max_events_per_phase=16)
    assert {r.phase for r in rows} == {"prefill", "decode"}
    lo, hi = DRIFT_BAND
    for r in rows:
        assert r.events > 0 and r.gemms > 0
        assert 0 < r.measured_utilization <= 1.0
        assert 0 < r.predicted_utilization <= 1.0
        assert lo <= r.drift <= hi, \
            f"{r.phase}: drift {r.drift:.3f} outside [{lo}, {hi}]"
        assert r.predicted_cycles > 0 and r.measured_cycles > 0
        # the gauge mirror the benchmark suite reads
        assert reg.value("obs.drift", phase=r.phase) \
            == pytest.approx(r.drift)
        assert reg.value("obs.predicted_util", phase=r.phase) \
            == pytest.approx(r.predicted_utilization)


def test_drift_skips_unrecorded_phases():
    rec = ServeTraceRecorder()
    rec.on_prefill(0, 8)
    cfg = reduced(get_arch("granite-8b"))
    rows = drift_report(rec, cfg, metrics=MetricsRegistry())
    assert [r.phase for r in rows] == ["prefill"]


def test_effective_tops_gauge_live(traced_run):
    """Effective TOPS as the paper defines it — measured throughput x
    utilization — computed from live telemetry and recorded as a gauge."""
    cfg, reg, rec = traced_run
    kreg = MetricsRegistry()
    from repro.parallel.autoshard import choose_blocks as cb, \
        tile_utilization
    blocks = cb(64, cfg.d_model, cfg.d_ff)
    kreg.gauge("autotune.tile_util",
               shape=f"64x{cfg.d_model}x{cfg.d_ff}").set(
        tile_utilization(64, cfg.d_model, cfg.d_ff, blocks))
    rows = effective_tops_summary(rec, cfg, reg, kernel_metrics=kreg)
    assert {r.phase for r in rows} == {"prefill", "decode"}
    for r in rows:
        assert r.tokens == rec.phase_tokens(r.phase)
        assert r.seconds == pytest.approx(
            reg.value(f"serve.{r.phase}.seconds"))
        assert r.tok_s > 0 and r.macs_per_token > 0
        assert 0 < r.tile_utilization <= 1.0
        # effective = measured x utilization, by construction and as gauge
        assert r.effective_tops == pytest.approx(
            r.measured_tops * r.tile_utilization)
        assert reg.value("obs.effective_tops", phase=r.phase) \
            == pytest.approx(r.effective_tops)


def test_effective_tops_unit_utilization_without_kernel_gauges(traced_run):
    cfg, reg, rec = traced_run
    rows = effective_tops_summary(rec, cfg, reg,
                                  kernel_metrics=MetricsRegistry())
    for r in rows:
        assert r.tile_utilization == 1.0
        assert r.effective_tops == pytest.approx(r.measured_tops)
