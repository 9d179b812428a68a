"""Overload & failure semantics: SLO-aware admission, deadlines,
backpressure, and the seeded chaos harness (serve/admission.py,
serve/chaos.py) threaded through both serving engines.

The contract under test:
  * malformed requests raise typed `InvalidRequest` at submit, naming the
    offending field — they never reach the hot loop;
  * every submitted request reaches exactly ONE terminal state
    (done | rejected | expired), chaos or not, and slot occupancy returns
    to zero at drain (no leaks);
  * a wedged engine raises `ServeStalled` naming the stuck requests
    instead of returning silently from run_to_completion;
  * the default engine (fifo, unbounded, no deadlines, no chaos) is
    bit-identical to the seed: same tokens, same jit cache sizes, same
    host-sync count (the PR 7 discipline);
  * under deterministic 2x overload (virtual time) edf and slo-aware beat
    fifo on SLO attainment;
  * injected transient faults retry with backoff and heal; retries
    exhausted sheds the affected requests with their slots reclaimed; and
    every request a chaos engine completes carries token-exact output vs
    the bare ReferenceEngine oracle.
"""

import math

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_arch, reduced
from repro.models.model import Model
from repro.serve.admission import (AdmissionConfig, AdmissionController,
                                   InvalidRequest, ServeStalled,
                                   TERMINAL_STATES, WaveLatencyPredictor)
from repro.serve.chaos import (ChaosConfig, FaultInjector, SlowChunkDetector,
                               TransientDeviceError, VirtualClock)
from repro.serve.engine import Request, ServeEngine
from repro.serve.reference import ReferenceEngine
from repro.train.fault import Ewma


@pytest.fixture(scope="module")
def parts():
    cfg = reduced(get_arch("granite-8b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab, n,
                                                dtype=np.int32)


def _drain(eng, reqs, max_steps=2000):
    eng.run_to_completion(max_steps=max_steps)
    assert not any(eng.active), "slot leak: occupancy nonzero at drain"
    assert not eng.queue
    for r in reqs:
        assert r.state in TERMINAL_STATES, (r.rid, r.state)
    return {r.rid: list(r.out) for r in reqs}


# --------------------------------------------------------------------------
# satellite: typed validation at submit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine_cls", [ServeEngine, ReferenceEngine])
def test_submit_rejects_malformed_requests_with_typed_errors(parts,
                                                             engine_cls):
    cfg, model, params = parts
    eng = engine_cls(model, params, slots=2, max_len=32)
    cases = [
        (Request(rid=0, prompt=np.zeros(0, np.int32)), "prompt"),
        (Request(rid=1, prompt=_prompt(cfg, 33)), "prompt"),
        (Request(rid=2, prompt=_prompt(cfg, 4), max_new_tokens=0),
         "max_new_tokens"),
        (Request(rid=3, prompt=_prompt(cfg, 4), max_new_tokens=-2),
         "max_new_tokens"),
        (Request(rid=4, prompt=_prompt(cfg, 4), deadline_s=0.0),
         "deadline_s"),
        (Request(rid=5, prompt=_prompt(cfg, 4), deadline_s=-1.0),
         "deadline_s"),
    ]
    for req, field in cases:
        with pytest.raises(InvalidRequest) as ei:
            eng.submit(req)
        assert ei.value.field == field
        assert field in str(ei.value)
        # the reject never entered the system
        assert not eng.queue and req.state == "new"
    assert eng.admission.counts["submitted"] == 0
    # boundary: prompt length == max_len is VALID (retires with the
    # prefill token, the existing cache-full contract)
    ok = Request(rid=9, prompt=_prompt(cfg, 32), max_new_tokens=2)
    eng.submit(ok)
    eng.run_to_completion(max_steps=50)
    assert ok.done and ok.state == "done" and len(ok.out) == 1


def test_admission_config_validation():
    with pytest.raises(ValueError, match="unknown admission policy"):
        AdmissionConfig(policy="lifo")
    with pytest.raises(ValueError, match="max_queue"):
        AdmissionConfig(max_queue=0)


# --------------------------------------------------------------------------
# satellite: ServeStalled on exhausted max_steps
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine_cls", [ServeEngine, ReferenceEngine])
def test_wedged_engine_raises_serve_stalled(parts, engine_cls):
    cfg, model, params = parts
    eng = engine_cls(model, params, slots=1, max_len=32)
    r = Request(rid=7, prompt=_prompt(cfg, 4), max_new_tokens=4)
    eng.submit(r)
    eng._admit = lambda: None          # wedge: admission never runs
    with pytest.raises(ServeStalled) as ei:
        eng.run_to_completion(max_steps=5)
    assert ei.value.pending == {7: "queued"}
    assert ei.value.max_steps == 5
    assert "rid 7: queued" in str(ei.value)


def test_run_to_completion_still_returns_cleanly_when_drained(parts):
    cfg, model, params = parts
    eng = ServeEngine(model, params, slots=2, max_len=32)
    r = Request(rid=0, prompt=_prompt(cfg, 5), max_new_tokens=3)
    eng.submit(r)
    eng.run_to_completion(max_steps=200)       # no raise
    assert r.done and len(r.out) == 3


# --------------------------------------------------------------------------
# the PR 7-style no-change gate: default engine == seed, bit for bit
# --------------------------------------------------------------------------

def test_fifo_no_faults_is_bit_identical_to_seed(parts, monkeypatch):
    """Default-constructed engine vs one with every new knob at its
    explicit default: same tokens, same jit cache sizes, same host-sync
    count (counted as np.asarray on jax.Array, the PR 7 accounting)."""
    import repro.serve.engine as engine_mod
    from test_serving import _SyncCountingNumpy
    cfg, model, params = parts
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 9, 17, 12, 7)]

    counts, outs = {}, {}
    for name, kw in (("bare", {}),
                     ("threaded", {"admission": AdmissionConfig(
                         policy="fifo"), "max_retries": 3})):
        proxy = _SyncCountingNumpy(np)
        monkeypatch.setattr(engine_mod, "np", proxy)
        eng = ServeEngine(model, params, slots=2, max_len=64,
                          decode_chunk=8, **kw)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_to_completion(max_steps=500)
        monkeypatch.setattr(engine_mod, "np", np)
        counts[name] = (eng._prefill_fn._cache_size(),
                        eng._decode_fn._cache_size(), proxy.syncs)
        outs[name] = {r.rid: list(r.out) for r in reqs}
        assert all(r.done and r.state == "done" for r in reqs)
    assert outs["threaded"] == outs["bare"]
    assert counts["threaded"] == counts["bare"], (
        f"admission changed (prefill compiles, decode compiles, syncs): "
        f"{counts}")


def test_fifo_tokens_match_reference_oracle(parts):
    cfg, model, params = parts
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (4, 9, 6)]
    outs = {}
    for cls in (ServeEngine, ReferenceEngine):
        eng = cls(model, params, slots=2, max_len=32)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        outs[cls.__name__] = _drain(eng, reqs)
    assert outs["ServeEngine"] == outs["ReferenceEngine"]


# --------------------------------------------------------------------------
# deadlines, backpressure, degradation
# --------------------------------------------------------------------------

def test_queued_requests_expire_past_deadline(parts):
    """More work than one slot can serve before the deadline: the tail of
    the queue expires (terminal `expired`, reason queued-past-deadline)
    rather than being served late or leaking."""
    cfg, model, params = parts
    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=1, max_len=32, clock=clk,
                      admission=AdmissionConfig(policy="edf"),
                      chaos=ChaosConfig(seed=0, service_seconds=0.2))
    reqs = [Request(rid=i, prompt=_prompt(cfg, 4, i), max_new_tokens=4,
                    deadline_s=0.5) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    _drain(eng, reqs)
    states = {r.state for r in reqs}
    assert "expired" in states and "done" in states
    expired = [r for r in reqs if r.state == "expired"]
    assert all(r.reason == "queued-past-deadline" and not r.done
               and r.out == [] for r in expired)
    assert eng.admission.slo_attainment < 1.0


def test_running_request_expires_at_chunk_sync(parts):
    """A deadline that passes mid-decode is enforced at the existing
    chunk sync: the lane is reclaimed, tokens already emitted stay."""
    cfg, model, params = parts
    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=1, max_len=64, decode_chunk=4,
                      clock=clk, admission=AdmissionConfig(policy="edf"),
                      chaos=ChaosConfig(seed=0, service_seconds=0.3))
    r = Request(rid=0, prompt=_prompt(cfg, 4), max_new_tokens=32,
                deadline_s=0.5)
    eng.submit(r)
    _drain(eng, [r])
    assert r.state == "expired" and r.reason == "deadline-exceeded"
    assert not r.done
    assert 1 <= len(r.out) < 32            # partial output survives


def test_bounded_queue_sheds_with_queue_full(parts):
    cfg, model, params = parts
    eng = ServeEngine(model, params, slots=1, max_len=32,
                      admission=AdmissionConfig(max_queue=2))
    reqs = [Request(rid=i, prompt=_prompt(cfg, 4, i), max_new_tokens=2)
            for i in range(5)]
    for r in reqs:
        eng.submit(r)
    # nothing admits between submits: the first two queue, the rest shed
    shed = [r for r in reqs if r.state == "rejected"]
    assert len(shed) == 3 and all(r.reason == "queue-full" for r in shed)
    _drain(eng, reqs)
    assert sum(1 for r in reqs if r.state == "done") == 2
    c = eng.admission.counts
    assert c["submitted"] == 5 and c["rejected"] == 3 and c["done"] == 2


def test_slo_aware_degrades_budgets_under_overload(parts):
    """Deep queue + slo-aware: newly admitted requests get shrunk decode
    budgets (graceful degradation) and everyone still terminates."""
    cfg, model, params = parts
    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=1, max_len=64, clock=clk,
                      admission=AdmissionConfig(
                          policy="slo-aware", overload_queue_per_slot=2.0),
                      chaos=ChaosConfig(seed=0, service_seconds=0.01))
    reqs = [Request(rid=i, prompt=_prompt(cfg, 4, i), max_new_tokens=9)
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    _drain(eng, reqs)
    assert eng.admission.counts["degraded"] > 0
    assert any(r.state == "done" and len(r.out) < 9 for r in reqs)
    assert all(r.state == "done" for r in reqs)   # degraded, not dropped


def test_edf_and_slo_aware_beat_fifo_attainment_at_overload(parts):
    """The headline acceptance: deterministic 2x overload in virtual
    time, mixed tight/loose deadlines — deadline-aware policies must beat
    arrival order on SLO attainment."""
    cfg, model, params = parts

    def run(policy):
        clk = VirtualClock()
        eng = ServeEngine(model, params, slots=2, max_len=64,
                          decode_chunk=8, clock=clk,
                          admission=AdmissionConfig(policy=policy),
                          chaos=ChaosConfig(seed=0, service_seconds=0.05))
        rng = np.random.default_rng(11)
        reqs = []
        for i in range(12):
            p = rng.integers(0, cfg.vocab, int(rng.integers(5, 9)),
                             dtype=np.int32)
            reqs.append(Request(rid=i, prompt=p, max_new_tokens=6,
                                deadline_s=0.8 if i % 2 else 7.0))
        for r in reqs:
            eng.submit(r)
        _drain(eng, reqs)
        return eng.admission.slo_attainment

    att = {p: run(p) for p in ("fifo", "edf", "slo-aware")}
    assert att["edf"] > att["fifo"], att
    assert att["slo-aware"] > att["fifo"], att


# --------------------------------------------------------------------------
# chaos: seeded faults, retry-with-backoff, oracle parity
# --------------------------------------------------------------------------

def test_transient_faults_retry_and_heal_token_exact(parts):
    """transient_tries <= max_retries: every injected fault heals on
    retry; all requests complete with tokens identical to the bare
    ReferenceEngine oracle, and the backoff advanced the virtual clock."""
    cfg, model, params = parts
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (4, 7, 5, 9)]

    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=2, max_len=32, clock=clk,
                      max_retries=3, backoff_s=1e-3,
                      chaos=ChaosConfig(seed=1, p_fault=0.4,
                                        transient_tries=2))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    out = _drain(eng, reqs)
    assert all(r.state == "done" for r in reqs)
    assert eng._chaos.injected["faults"] > 0, "seed injected nothing"
    assert clk.t > 0                       # backoff slept on the clock

    oracle = ReferenceEngine(model, params, slots=2, max_len=32)
    oreqs = [Request(rid=i, prompt=p, max_new_tokens=4)
             for i, p in enumerate(prompts)]
    for r in oreqs:
        oracle.submit(r)
    oracle.run_to_completion(max_steps=500)
    assert out == {r.rid: list(r.out) for r in oreqs}


def test_retries_exhausted_sheds_without_slot_leak(parts):
    """transient_tries > max_retries: the faulty call escalates to
    PermanentFault; its requests end `rejected` (reason device-fault),
    slots are reclaimed, and the rest of the traffic completes."""
    cfg, model, params = parts
    eng = ServeEngine(model, params, slots=2, max_len=32, max_retries=1,
                      chaos=ChaosConfig(seed=1, p_fault=0.4,
                                        transient_tries=5))
    reqs = [Request(rid=i, prompt=_prompt(cfg, 4 + i, i), max_new_tokens=3)
            for i in range(6)]
    for r in reqs:
        eng.submit(r)
    _drain(eng, reqs)
    rejected = [r for r in reqs if r.state == "rejected"]
    assert rejected, "seed 1 must trip at least one permanent fault"
    assert all(r.reason == "device-fault" and not r.done for r in rejected)
    assert any(r.state == "done" for r in reqs), \
        "surviving traffic must still complete"


def test_fault_schedule_is_deterministic():
    """Same (seed, kind, index) -> same fate, independent of retries and
    interleaving; different seeds differ somewhere."""
    def fates(seed, tries=1):
        inj = FaultInjector(ChaosConfig(seed=seed, p_fault=0.5,
                                        transient_tries=tries))
        out = []
        for _ in range(20):
            hits = 0
            while True:
                try:
                    inj.before("decode")
                    break
                except TransientDeviceError:
                    hits += 1
            out.append(hits)
        return out
    a, b = fates(7), fates(7)
    assert a == b and sum(a) > 0
    assert fates(8) != a
    # transient_tries raises the per-site consecutive failure count
    assert sum(fates(7, tries=3)) == 3 * sum(a)


def test_slow_chunk_detector_flags_streaks_not_spikes():
    det = SlowChunkDetector(slow_factor=2.0, patience=2)
    for _ in range(5):
        assert not det.observe(1.0)        # healthy baseline
    assert not det.observe(10.0)           # one spike: strike, no flag
    assert det.observe(10.0)               # second consecutive: flagged
    assert det.flagged_chunks == 1
    # the spikes did not pollute the healthy baseline
    assert det.ewma.value == pytest.approx(1.0)
    assert not det.observe(1.0)            # recovery resets strikes
    assert det.strikes == 0


def test_slow_chunks_shrink_next_chunk(parts):
    """A flagged slow streak halves the next decode chunk (mitigation),
    and the engine still drains with correct terminal states."""
    cfg, model, params = parts
    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=2, max_len=64, decode_chunk=8,
                      clock=clk,
                      chaos=ChaosConfig(seed=2, p_slow=0.8, slow_factor=6.0,
                                        service_seconds=0.01))
    # low patience so the streak flags within this short run
    eng._slow_detect.patience = 1
    reqs = [Request(rid=i, prompt=_prompt(cfg, 5, i), max_new_tokens=16)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    caps = []
    while eng.queue or any(eng.active):
        eng.step()
        caps.append(eng._chunk_cap)
    assert eng._chaos.injected["slow"] > 0
    assert any(c is not None for c in caps), "detector never flagged"
    assert all(r.done for r in reqs)


def test_ewma_shared_primitive():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.observe(10.0) == 10.0         # first sample seeds
    assert e.observe(0.0) == 5.0
    assert e.observe(5.0) == 5.0


# --------------------------------------------------------------------------
# property test: randomized traffic, bare + chaos engines
# --------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), with_chaos=st.booleans(),
       policy=st.sampled_from(["fifo", "edf", "slo-aware"]))
def test_random_traffic_terminal_states_and_no_leaks(parts, seed,
                                                     with_chaos, policy):
    """Invariants over randomized traffic: admitted lanes never exceed
    slots, running/queued states are consistent at every quantum, every
    request reaches exactly one terminal state, outputs respect budgets,
    and occupancy returns to zero at drain — with and without chaos."""
    cfg, model, params = parts
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(1, 4))
    chaos = ChaosConfig(seed=seed, p_fault=0.2, p_slow=0.2,
                        service_seconds=0.02, transient_tries=1) \
        if with_chaos else None
    eng = ServeEngine(model, params, slots=slots, max_len=32,
                      decode_chunk=4, clock=VirtualClock(),
                      admission=AdmissionConfig(
                          policy=policy,
                          max_queue=int(rng.integers(2, 8))),
                      chaos=chaos)
    reqs = []
    for i in range(int(rng.integers(1, 9))):
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, int(rng.integers(1, 33)),
                                dtype=np.int32),
            # >= 2: a budget-0 lane takes one forced decode step (seed
            # semantics, both engines), so max_new_tokens=1 yields 2 tokens
            max_new_tokens=int(rng.integers(2, 7)),
            deadline_s=float(rng.uniform(0.05, 2.0))
            if rng.random() < 0.5 else None,
            priority=int(rng.integers(0, 3))))
    for r in reqs:
        eng.submit(r)
        eng.step()
    for _ in range(2000):
        live = [r for r in eng.active if r is not None]
        assert len(live) <= slots
        assert all(r.state == "running" for r in live)
        assert all(r.state == "queued" for r in eng.queue)
        if not eng.queue and not live:
            break
        eng.step()
    assert not any(eng.active) and not eng.queue
    states = [r.state for r in reqs]
    assert all(s in TERMINAL_STATES for s in states), states
    for r in reqs:
        assert r.done == (r.state == "done")
        assert len(r.out) <= r.max_new_tokens
    c = eng.admission.counts
    assert c["submitted"] == len(reqs)
    assert c["done"] + c["rejected"] + c["expired"] == len(reqs)


# --------------------------------------------------------------------------
# wave-model prediction plumbing
# --------------------------------------------------------------------------

def test_wave_predictor_monotone_and_bucket_cached(parts):
    cfg, _, _ = parts
    p = WaveLatencyPredictor(cfg)
    small = p.model_seconds(8, 4)
    big = p.model_seconds(8, 32)
    assert 0 < small < big                 # more tokens, more seconds
    p.model_seconds(9, 4)                  # same pow2 bucket as 16? no: 16
    assert len(p._cache) == 3
    p.model_seconds(15, 4)                 # bucket 16 again: cache hit
    assert len(p._cache) == 3


def test_calibration_gates_predictions():
    ctl = AdmissionController(AdmissionConfig(policy="slo-aware"),
                              slots=2, max_len=64)
    assert ctl.predicted_wall_seconds(8, 4) is None    # no predictor
    cfg = reduced(get_arch("granite-8b"))
    ctl = AdmissionController(AdmissionConfig(policy="slo-aware"),
                              slots=2, max_len=64,
                              predictor=WaveLatencyPredictor(cfg))
    assert ctl.predicted_wall_seconds(8, 4) is None    # unwarmed kappa
    ctl.observe_service(model_seconds=1e-6, wall_seconds=1e-3)
    pred = ctl.predicted_wall_seconds(8, 4)
    assert pred is not None and pred > 0


# --------------------------------------------------------------------------
# satellite: trace lowering is time-ordered under priority scheduling
# --------------------------------------------------------------------------

def test_trace_to_gemms_sorts_interleaved_timeline():
    """Priority scheduling can *record* a short-deadline lane's prefill
    after decode chunks that started later; the lowering must follow
    start-time order, not record order."""
    from repro.tenancy.trace import ServeTraceRecorder, trace_to_gemms
    cfg = reduced(get_arch("granite-8b"))

    ordered = ServeTraceRecorder()
    ordered.on_prefill(0, 8, t=0.0)
    ordered.on_decode(1, [8], t=1.0)
    ordered.on_prefill(1, 4, t=2.0)
    ordered.on_decode(2, [9, 4], t=3.0)

    shuffled = ServeTraceRecorder()        # same timeline, recorded badly
    shuffled.on_decode(2, [9, 4], t=3.0)
    shuffled.on_prefill(1, 4, t=2.0)
    shuffled.on_decode(1, [8], t=1.0)
    shuffled.on_prefill(0, 8, t=0.0)

    want = [(g.d1, g.d2, g.d3, g.name)
            for g in trace_to_gemms(ordered, cfg)]
    got = [(g.d1, g.d2, g.d3, g.name)
           for g in trace_to_gemms(shuffled, cfg)]
    assert got == want
    assert want[0][0] == 8                 # prefill-at-8 lowers first


def test_trace_events_without_stamps_keep_record_order():
    """Synthetic traces (no timestamps) must lower exactly as recorded —
    the stamp defaults to the record index, and the sort is stable."""
    from repro.tenancy.trace import ServeTraceRecorder, trace_to_gemms
    cfg = reduced(get_arch("granite-8b"))
    rec = ServeTraceRecorder()
    rec.on_decode(1, [4])
    rec.on_prefill(0, 8)
    gemms = trace_to_gemms(rec, cfg)
    assert gemms[0].d1 == 1                # decode stayed first
    assert rec.num_prefills == 1 and rec.num_decode_steps == 1
    assert rec.phase_tokens("prefill") == 8


# --------------------------------------------------------------------------
# satellite: benchmarks.run --check must exit nonzero on ERROR rows
# --------------------------------------------------------------------------

def _bench_run_module():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_adm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_exits_nonzero_on_error_rows(monkeypatch, capsys, tmp_path):
    run = _bench_run_module()
    # an env-named cache dir keeps run.main() off this process's jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def boom():
        raise RuntimeError("suite blew up")

    monkeypatch.setattr(run, "load_suites", lambda: {"boom": boom})
    monkeypatch.setattr("sys.argv", ["run.py", "--check"])
    monkeypatch.setenv("SOSA_BENCH_CHECK", "1")   # restored at teardown
    with pytest.raises(SystemExit) as ei:
        run.main()
    assert ei.value.code == 1
    out = capsys.readouterr()
    assert "boom/ERROR" in out.out
    assert "CHECK FAIL" in out.err


def test_check_passes_on_clean_suite(monkeypatch, capsys, tmp_path):
    run = _bench_run_module()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(run, "load_suites",
                        lambda: {"tiny": lambda: ["tiny/x,1,ok=1"]})
    monkeypatch.setattr("sys.argv", ["run.py", "--check"])
    monkeypatch.setenv("SOSA_BENCH_CHECK", "1")   # restored at teardown
    run.main()                             # no SystemExit
    out = capsys.readouterr()
    assert "tiny/_total" in out.out
    assert "OK" in out.err


# --------------------------------------------------------------------------
# PR 10 bugfixes: κ-calibration skew, cold-start pollution, bounded cache
# --------------------------------------------------------------------------

def test_calibration_observes_actual_tokens_not_budget(parts):
    """κ-skew regression: the calibration sample after retire must price
    the tokens the request ACTUALLY produced (len(out)), not the full
    max_new_tokens budget. An early-EOS request that emits 4 of 16 tokens
    would otherwise divide its wall by a 4x-too-large model_seconds and
    drag κ (and every prediction behind it) down."""
    cfg, model, params = parts

    # discover a token the model actually emits a few steps in
    ref = ReferenceEngine(model, params, slots=1, max_len=64)
    probe = Request(rid=0, prompt=_prompt(cfg, 6, 5), max_new_tokens=16)
    ref.submit(probe)
    ref.run_to_completion(max_steps=200)
    eos = int(probe.out[3])
    if eos in [int(t) for t in probe.out[:3]]:
        eos = int(probe.out[4])

    clk = VirtualClock()
    eng = ServeEngine(model, params, slots=1, max_len=64, decode_chunk=8,
                      eos_id=eos, clock=clk,
                      admission=AdmissionConfig(policy="slo-aware"),
                      chaos=ChaosConfig(seed=0, service_seconds=0.05))
    samples = []
    real = eng.admission.observe_service

    def spy(model_seconds, wall_seconds):
        samples.append((model_seconds, wall_seconds))
        real(model_seconds, wall_seconds)

    eng.admission.observe_service = spy
    # warmup trace with a DIFFERENT prompt (same pow2 bucket, no eos in
    # its early tokens is irrelevant — it may stop early too) to populate
    # the jit caches so the measured request's epoch matches
    warm = Request(rid=1, prompt=_prompt(cfg, 7, 9), max_new_tokens=16)
    eng.submit(warm)
    _drain(eng, [warm])
    kappa_steady = eng.admission._calibration.value
    samples.clear()

    r = Request(rid=2, prompt=_prompt(cfg, 6, 5), max_new_tokens=16)
    eng.submit(r)
    _drain(eng, [r])
    assert r.state == "done" and r.out[-1] == eos
    assert len(r.out) < 16, "probe token must end the request early"
    assert len(samples) == 1, "warm retire must contribute one κ sample"
    p = eng.admission.predictor
    want = p.model_seconds(len(r.prompt), len(r.out))
    not_want = p.model_seconds(len(r.prompt), r.max_new_tokens)
    assert samples[0][0] == pytest.approx(want)
    assert samples[0][0] < not_want, "sample priced at budget, not output"
    # and κ itself stays in the steady-state band instead of cratering
    if kappa_steady is not None:
        assert eng.admission._calibration.value > 0.5 * kappa_steady


def test_cold_start_compile_does_not_pollute_kappa(parts):
    """Cold-start regression: request 1 of a cold engine retires with the
    prefill/decode compiles inside its service wall. That sample must be
    SKIPPED (jit epoch grew during service) — κ stays unwarmed — so a
    deadline-carrying request 2 is not shed on a compile-inflated
    prediction. Request 2's own retire, with stable caches, seeds κ."""
    cfg, model, params = parts
    eng = ServeEngine(model, params, slots=1, max_len=64, decode_chunk=8,
                      admission=AdmissionConfig(policy="slo-aware"))
    r1 = Request(rid=1, prompt=_prompt(cfg, 6, 1), max_new_tokens=8)
    import time as _time
    t0 = _time.perf_counter()
    eng.submit(r1)
    _drain(eng, [r1])
    r1_wall = _time.perf_counter() - t0
    assert r1.state == "done"
    # the poisoned sample was dropped: κ is still unwarmed
    assert eng.admission._calibration.value is None
    # request 2: same shapes (warm), deadline far below r1's cold wall —
    # a κ seeded from r1 would predict a miss and shed it at admission
    r2 = Request(rid=2, prompt=_prompt(cfg, 6, 2), max_new_tokens=8,
                 deadline_s=max(0.05, 0.25 * r1_wall))
    eng.submit(r2)
    _drain(eng, [r2])
    assert r2.state == "done", (r2.state, r2.reason)
    assert r2.reason != "shed-predicted-miss"
    # r2 ran on stable caches: ITS sample warms κ
    assert eng.admission._calibration.value is not None


def test_wave_predictor_cache_is_bounded(parts, monkeypatch):
    """Bounded-predictor-cache regression: 10k requests with random
    (prompt, budget) shapes must not grow the memo past cache_cap, and
    the hot (recently used) entries stay resident."""
    cfg, _, _ = parts
    from repro.serve import admission as adm
    monkeypatch.setattr(adm, "request_gemms", lambda *a, **k: None)
    monkeypatch.setattr(adm, "predict_latency_s",
                        lambda *a, **k: 1e-3)
    p = WaveLatencyPredictor(cfg, cache_cap=256)
    rng = np.random.default_rng(0)
    for _ in range(10_000):
        p.model_seconds(int(rng.integers(1, 4096)),
                        int(rng.integers(1, 512)))
        assert len(p._cache) <= p.cache_cap
    # LRU, not FIFO: touching an old key keeps it through later inserts
    p2 = WaveLatencyPredictor(cfg, cache_cap=4)
    for n in (1, 2, 3, 4):
        p2.model_seconds(8, n)
    p2.model_seconds(8, 1)                  # refresh the oldest entry
    p2.model_seconds(8, 5)                  # evicts (8->bucket, 2), not 1
    assert (p2._bucket(8), 1) in p2._cache
    assert (p2._bucket(8), 2) not in p2._cache
