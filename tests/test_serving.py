"""Serving execution backend tests: bucketed prefill + fused decode engine
vs the seed reference engine (the oracle), Pallas-path logits parity, the
jit-compile-count regression gate, the src_len threading regression, the
block autotuner, and the benchmark JSON schema."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models.model import Model
from repro.parallel.autoshard import choose_blocks
from repro.serve.engine import Request, ServeEngine
from repro.serve.reference import ReferenceEngine


def _setup(arch="granite-8b", seed=0, **model_kw):
    cfg = reduced(get_arch(arch))
    model = Model(cfg, **model_kw)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _run(engine_cls, model, params, prompts, max_new=4, **kw):
    eng = engine_cls(model, params, **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=500)
    assert all(r.done for r in reqs)
    return eng, {r.rid: r.out for r in reqs}


# --------------------------------------------------------------------------
# bucketed + fused engine == seed oracle
# --------------------------------------------------------------------------

def test_bucketed_engine_matches_reference_mixed_lengths():
    """Same greedy tokens from the on-device hot loop and the seed
    per-token engine, across mixed prompt lengths and buckets."""
    cfg, model, params = _setup(seed=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 9, 3, 17, 12, 33)]
    _, ref = _run(ReferenceEngine, model, params, prompts,
                  slots=2, max_len=64)
    eng, new = _run(ServeEngine, model, params, prompts,
                    slots=2, max_len=64)
    assert eng.bucketed
    assert new == ref


@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-370m"])
def test_single_slot_engine_matches_reference(arch):
    """Regression: _probe_batch_axes used to hardcode axis 0 for every
    leaf when slots == 1, scattering stacked-layer cache leaves (batch on
    axis 1) along the LAYER axis — a 1-slot engine served garbage for the
    first decode chunk while every layer past the first started from a
    zeroed prefill. The axes are now probed from 2-vs-1-lane throwaway
    trees regardless of slot count."""
    cfg, model, params = _setup(arch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, 6, dtype=np.int32)]
    _, ref = _run(ReferenceEngine, model, params, prompts, max_new=16,
                  slots=1, max_len=64)
    _, new = _run(ServeEngine, model, params, prompts, max_new=16,
                  slots=1, max_len=64)
    assert new == ref


def test_fused_decode_mixed_budgets():
    """Lanes with different budgets finish at the right lengths even when
    they share fused decode chunks."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(2)
    eng = ServeEngine(model, params, slots=3, max_len=64, decode_chunk=8)
    budgets = [2, 7, 5, 1, 9]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4 + i,
                                               dtype=np.int32),
                    max_new_tokens=b)
            for i, b in enumerate(budgets)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=100)
    for r, b in zip(reqs, budgets):
        # seed semantics: prefill token + max(1, max_new - 1) decode steps
        assert r.done and len(r.out) == max(2, b), (r.rid, len(r.out), b)


def test_fused_decode_eos_truncates():
    """EOS inside a fused chunk stops the lane at the eos token (inclusive)
    and matches the reference engine's eos behavior."""
    cfg, model, params = _setup(seed=5)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32) for n in (6, 11)]
    _, free = _run(ReferenceEngine, model, params, prompts, max_new=8,
                   slots=2, max_len=64)
    eos = free[0][2]          # third greedy token of request 0 becomes eos
    _, ref = _run(ReferenceEngine, model, params, prompts, max_new=8,
                  slots=2, max_len=64, eos_id=eos)
    _, new = _run(ServeEngine, model, params, prompts, max_new=8,
                  slots=2, max_len=64, eos_id=eos)
    assert new == ref
    assert new[0][-1] == eos and len(new[0]) <= 3


def test_prompt_filling_cache_retires_without_decode():
    """A prompt of length max_len leaves no room for a decode append: the
    lane must retire with just the prefill token, never clobber the last
    KV slot (both engines)."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, 16, dtype=np.int32),
               rng.integers(0, cfg.vocab, 5, dtype=np.int32)]
    outs = {}
    for cls in (ServeEngine, ReferenceEngine):
        _, out = _run(cls, model, params, prompts, max_new=4,
                      slots=2, max_len=16)
        assert len(out[0]) == 1          # prefill token only, cache intact
        assert len(out[1]) == 4
        outs[cls.__name__] = out
    assert outs["ServeEngine"] == outs["ReferenceEngine"]


def test_requests_with_extras_skip_the_bucket_batch():
    """extras carry per-request shapes: they must ride the exact-length
    prefill path even on a bucketed engine (never silently dropped)."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(5)
    eng = ServeEngine(model, params, slots=2, max_len=32)
    assert eng.bucketed
    reqs = [Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6,
                                               dtype=np.int32),
                    max_new_tokens=3, extras={"unused": np.zeros((1, 2))}),
            Request(rid=1, prompt=rng.integers(0, cfg.vocab, 7,
                                               dtype=np.int32),
                    max_new_tokens=3)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=50)
    assert all(r.done and len(r.out) == 3 for r in reqs)
    # the extras request went down the exact-length path (recorded by
    # prompt length, not bucket)
    assert 6 in eng._buckets_seen


# --------------------------------------------------------------------------
# prefill width: a bucket-b call computes W(b) lanes, not every slot
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-370m"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("lengths,width,calls", [
    ((70, 90, 120), 2, [2, 1]),    # bucket 128: W = 2 < 4 slots, split
    ((9, 12, 15), 4, [3]),         # bucket 16: W = slots, one call
], ids=["split", "whole"])
def test_prefill_group_splits_by_bucket_width(arch, paged, lengths, width,
                                              calls):
    """A same-bucket group of k requests is prefilled in ceil(k / W(b))
    calls of width W(b) = min(slots, next_pow2(ceil(256 / b))), whose
    spans carry `width` and `lanes` summing to k; the served tokens equal
    a one-request-at-a-time run's, one program per bucket, and
    serve.prefill.pad_lanes counts the computed lanes that held no
    request."""
    from repro.obs.metrics import MetricsRegistry
    from repro.tenancy.trace import ServeTraceRecorder
    cfg, model, params = _setup(arch)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in lengths]
    kw = dict(slots=4, max_len=256, decode_chunk=4, paged=paged)
    rec, reg = ServeTraceRecorder(), MetricsRegistry()
    eng, grouped = _run(ServeEngine, model, params, prompts, max_new=6,
                        tracer=rec, metrics=reg, **kw)
    assert eng.bucketed
    pre = [s for s in rec.spans if s.cat == "prefill"]
    assert [s.args["lanes"] for s in pre] == calls
    assert all(s.args["width"] == width == eng._width(s.args["bucket"])
               for s in pre)
    assert reg.value("serve.prefill.pad_lanes") == \
        width * len(calls) - len(lengths)
    assert eng.prefill_compiles == len(eng._buckets_seen) == 1

    alone = ServeEngine(model, params, **kw)
    for i, p in enumerate(prompts):
        r = Request(rid=i, prompt=p, max_new_tokens=6)
        alone.submit(r)
        alone.run_to_completion(max_steps=100)
        assert r.done and r.out == grouped[i], i


# --------------------------------------------------------------------------
# jit compile-count regression (the bounded-bucket guarantee)
# --------------------------------------------------------------------------

def test_prefill_compile_count_bounded():
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    lengths = (3, 4, 5, 7, 9, 12, 17, 25, 31, 33, 48)   # 11 distinct
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in lengths]
    eng, _ = _run(ServeEngine, model, params, prompts, max_new=2,
                  slots=2, max_len=64)
    # bucketed prefill compiles one variant per pow2 bucket, never one per
    # prompt length: <= log2(max_len) on any workload
    assert eng.prefill_compiles <= int(math.log2(64))
    assert eng.prefill_compiles < len(set(lengths))
    # the actual jit cache (not just engine bookkeeping) is bounded too
    assert eng.prefill_compiles == len(eng._buckets_seen)


def test_decode_chunk_compile_count_bounded():
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 4 + i, dtype=np.int32)
               for i in range(6)]
    eng, _ = _run(ServeEngine, model, params, prompts, max_new=11,
                  slots=2, max_len=64, decode_chunk=8)
    # pow2-floored chunks: at most log2(decode_chunk)+1 compiled variants
    assert eng._decode_fn._cache_size() <= int(math.log2(8)) + 1


@pytest.mark.parametrize("arch", ["mamba2-370m", "hymba-1.5b"])
def test_stateful_prefill_compile_count_bounded(arch):
    """SSM / ring families now ride the bucketed path (masked state
    updates): their prefill jit cache must obey the same <= log2(max_len)
    bound as the dense gate, not one entry per prompt length."""
    cfg, model, params = _setup(arch)
    rng = np.random.default_rng(0)
    lengths = (3, 4, 5, 7, 9, 12, 17, 25, 31, 33, 48)   # 11 distinct
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in lengths]
    eng, _ = _run(ServeEngine, model, params, prompts, max_new=2,
                  slots=2, max_len=64)
    assert eng.bucketed
    assert eng.prefill_compiles <= int(math.log2(64))
    assert eng.prefill_compiles < len(set(lengths))
    assert eng.prefill_compiles == len(eng._buckets_seen)


# --------------------------------------------------------------------------
# src_len threading (seed regression: _prefill_into dropped src_len)
# --------------------------------------------------------------------------

def test_prefill_threads_src_len_encoder_decoder():
    cfg, model, params = _setup("whisper-small")
    src_len = 8
    eng = ServeEngine(model, params, slots=2, max_len=32, src_len=src_len)
    rng = np.random.default_rng(0)
    frames = rng.standard_normal((1, src_len, cfg.d_model)).astype(
        np.float32)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4 + i,
                                               dtype=np.int32),
                    max_new_tokens=4, extras={"frames": frames})
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(max_steps=50)
    assert all(r.done and len(r.out) == 4 for r in reqs)
    # the cross K/V lanes were actually written (the seed bug left the
    # batched cross cache silently untouched / shape-mismatched)
    ck = np.asarray(eng.cache["dec"]["cross"].k, np.float32)
    assert ck.shape[-3] == src_len
    assert np.abs(ck).sum() > 0


def test_reference_engine_threads_src_len_too():
    cfg, model, params = _setup("whisper-small")
    eng = ReferenceEngine(model, params, slots=2, max_len=32, src_len=8)
    rng = np.random.default_rng(0)
    frames = rng.standard_normal((1, 8, cfg.d_model)).astype(np.float32)
    r = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 5, dtype=np.int32),
                max_new_tokens=3, extras={"frames": frames})
    eng.submit(r)
    eng.run_to_completion(max_steps=50)
    assert r.done and len(r.out) == 3
    assert np.abs(np.asarray(eng.cache["dec"]["cross"].k,
                             np.float32)).sum() > 0


# --------------------------------------------------------------------------
# use_pallas execution backend: logits parity with the reference path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-8b", "minitron-8b"])
def test_pallas_backend_logits_parity(arch):
    """Model(use_pallas=True) == reference einsum path within bf16
    accumulation noise, prefill and decode (interpret mode on CPU)."""
    cfg = reduced(get_arch(arch))
    mref = Model(cfg)
    mpal = Model(cfg, use_pallas=True)
    params = mref.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)),
                                   jnp.int32)}
    l_ref, _ = mref.forward(params, batch)
    l_pal, _ = mpal.forward(params, batch)
    scale = float(np.abs(np.asarray(l_ref, np.float32)).max())
    np.testing.assert_allclose(np.asarray(l_pal, np.float32),
                               np.asarray(l_ref, np.float32),
                               atol=0.05 * scale, rtol=0.1)

    c_ref = mref.init_cache(2, 16)
    c_pal = mpal.init_cache(2, 16)
    _, c_ref = mref.prefill(params, batch, c_ref)
    _, c_pal = mpal.prefill(params, batch, c_pal)
    tok = jnp.asarray([3, 5], jnp.int32)
    d_ref, _ = mref.decode_step(params, tok, c_ref, 8)
    d_pal, _ = mpal.decode_step(params, tok, c_pal, 8)
    np.testing.assert_allclose(np.asarray(d_pal, np.float32),
                               np.asarray(d_ref, np.float32),
                               atol=0.05 * scale, rtol=0.1)


def test_pallas_backend_serves_end_to_end():
    """The engine runs on the Pallas execution backend (interpret mode)."""
    cfg, model, params = _setup(use_pallas=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32) for n in (4, 7)]
    eng, out = _run(ServeEngine, model, params, prompts, max_new=3,
                    slots=2, max_len=32)
    for toks in out.values():
        assert len(toks) == 3
        assert all(0 <= t < cfg.vocab for t in toks)


def test_stacked_weights_serve_the_per_layer_tokens(monkeypatch):
    """On the pod GEMM an untaped serving scan leaves the MLP's and the
    attention's weights in their stacks (transformer.layer_view) and the
    kernel reads each layer's blocks from them (q/k/v of 16-wide heads
    fall back to a per-layer slice inside the GEMM). The served tokens
    are those of the per-layer path, which slices every layer out
    first."""
    import repro.models.model as model_mod
    from repro.models.layers import LayerSlice
    cfg, model, params = _setup("yi-6b", use_pallas=True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 11, 3)]
    real = model_mod.layer_view
    streamed_leaves = []

    def counting(p_seg, i, cfg):
        view = real(p_seg, i, cfg)
        streamed_leaves.extend(
            k for k, v in jax.tree_util.tree_leaves_with_path(
                view, is_leaf=lambda a: isinstance(a, LayerSlice))
            if isinstance(v, LayerSlice))
        return view

    monkeypatch.setattr(model_mod, "layer_view", counting)
    _, streamed = _run(ServeEngine, model, params, prompts, max_new=6,
                       slots=2, max_len=64)
    names = {jax.tree_util.keystr(k) for k in streamed_leaves}
    assert names == {"['mlp']['up']", "['mlp']['gate']", "['mlp']['down']",
                     "['attn']['q']", "['attn']['k']", "['attn']['v']",
                     "['attn']['o']"}

    def sliced(p_seg, i, cfg):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            p_seg)

    monkeypatch.setattr(model_mod, "layer_view", sliced)
    _, per_layer = _run(ServeEngine, Model(cfg, use_pallas=True), params,
                        prompts, max_new=6, slots=2, max_len=64)
    assert streamed == per_layer


def test_moe_decode_hot_path_runs_grouped_gemm(monkeypatch):
    """With use_pallas the MoE serving hot loop must trace the grouped
    pod kernel into both prefill and decode (no einsum dispatch): the
    grouped launches appear when each phase compiles, and the LM head
    traces the fused-lane pod GEMM."""
    import repro.kernels.systolic_gemm.ops as gops
    calls = {"grouped": 0}
    real = gops.grouped_gemm

    def counting(*a, **k):
        calls["grouped"] += 1
        return real(*a, **k)

    monkeypatch.setattr(gops, "grouped_gemm", counting)
    cfg, model, params = _setup("dbrx-132b", use_pallas=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32) for n in (4, 6)]
    _, out = _run(ServeEngine, model, params, prompts, max_new=3,
                  slots=2, max_len=32)
    # 3 launches (up/gate/down) x (prefill trace + decode-chunk traces)
    assert calls["grouped"] >= 6
    assert all(len(t) == 3 for t in out.values())


def test_tied_embedding_lm_head_runs_transposed_kernel(monkeypatch):
    """mamba2's tied embeddings route the unembed through the
    transposed-weight pod GEMM (no [d, vocab] transpose copy)."""
    import repro.kernels.systolic_gemm.ops as gops
    calls = {"nt": 0}
    real = gops.systolic_gemm_t

    def counting(*a, **k):
        calls["nt"] += 1
        return real(*a, **k)

    monkeypatch.setattr(gops, "systolic_gemm_t", counting)
    cfg, model, params = _setup("mamba2-370m", use_pallas=True)
    assert cfg.tie_embeddings
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 5, dtype=np.int32)]
    _, out = _run(ServeEngine, model, params, prompts, max_new=2,
                  slots=1, max_len=16)
    assert calls["nt"] >= 2            # prefill + decode traces
    assert len(out[0]) == 2


# --------------------------------------------------------------------------
# tile_stats-driven block autotuner
# --------------------------------------------------------------------------

def test_choose_blocks_vmem_feasible_and_cached():
    before = choose_blocks.cache_info().hits
    bm, bn, bk = choose_blocks(4096, 4096, 4096)
    # 128-multiples that divide 4096: the problem is never padded
    assert all(b % 128 == 0 and 4096 % b == 0 for b in (bm, bn, bk))
    # VMEM working set of the chosen geometry under the 12 MiB budget
    vmem = 2 * (bm * bk + bk * bn) * 2 + bm * bn * (4 + 4)
    assert vmem <= 12 * 2 ** 20
    choose_blocks(4096, 4096, 4096)                 # per-shape cache hit
    assert choose_blocks.cache_info().hits > before


def test_choose_blocks_drives_kernel_and_stays_exact():
    """Autotuned (default) blocks must not change the GEMM result."""
    from repro.kernels.systolic_gemm.ops import systolic_gemm
    from repro.kernels.systolic_gemm.ref import systolic_gemm_ref
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(-50, 50, (100, 130)), jnp.int8)
    w = jnp.asarray(rng.integers(-50, 50, (130, 70)), jnp.int8)
    out = systolic_gemm(x, w, interpret=True)       # blocks=None -> DSE
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(systolic_gemm_ref(x, w)),
                               rtol=1e-6)


def test_choose_blocks_memory_bound_prefers_wide_n():
    """A skinny decode GEMM (tiny M) is HBM-bound on activations: the
    autotuner widens block_n to cut x-block reloads."""
    bm, bn, bk = choose_blocks(8, 4096, 4096)
    assert bn >= 256


# --------------------------------------------------------------------------
# telemetry must be free: no compiles, no syncs, no token changes
# --------------------------------------------------------------------------

class _SyncCountingNumpy:
    """numpy proxy that counts device->host materializations (np.asarray
    on a jax.Array) — the engine's host-sync accounting unit."""

    def __init__(self, real):
        self._real = real
        self.syncs = 0

    def asarray(self, x, *a, **k):
        if isinstance(x, jax.Array):
            self.syncs += 1
        return self._real.asarray(x, *a, **k)

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_metrics_and_tracer_add_no_compiles_or_syncs(monkeypatch):
    """The zero-overhead gate: an engine with a metrics registry and a
    span-recording tracer must produce the same tokens with the same jit
    cache sizes and the same number of host syncs as a bare engine — the
    device-side telemetry accumulators ride the existing chunk sync."""
    import repro.serve.engine as engine_mod
    from repro.obs.metrics import MetricsRegistry
    from repro.tenancy.trace import ServeTraceRecorder
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 9, 17, 12, 33, 7)]

    counts = {}
    outs = {}
    for name, kw in (("bare", {}),
                     ("instrumented", {"metrics": MetricsRegistry(),
                                       "tracer": ServeTraceRecorder()})):
        proxy = _SyncCountingNumpy(np)
        monkeypatch.setattr(engine_mod, "np", proxy)
        eng, out = _run(ServeEngine, model, params, prompts, max_new=5,
                        slots=2, max_len=64, decode_chunk=8, **kw)
        monkeypatch.setattr(engine_mod, "np", np)
        counts[name] = (eng._prefill_fn._cache_size(),
                        eng._decode_fn._cache_size(), proxy.syncs)
        outs[name] = out
    assert outs["instrumented"] == outs["bare"]
    assert counts["instrumented"] == counts["bare"], (
        "telemetry changed (prefill compiles, decode compiles, host syncs):"
        f" {counts}")
    # and the host genuinely synced once per device call, not per token
    eng_steps = sum(1 for _ in outs["bare"])           # lanes, not steps
    assert counts["bare"][2] < sum(len(o) for o in outs["bare"].values())
    assert eng_steps > 0


# --------------------------------------------------------------------------
# benchmark JSON schema (benchmarks/run.py --json)
# --------------------------------------------------------------------------

def _load_bench_run():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_json_schema(tmp_path):
    run = _load_bench_run()
    rows = [run.parse_row("serving/decode_fused,109,tok_s=9158;p50_us=109"),
            run.parse_row("kernels/_total,123,done")]
    assert rows[0] == {"suite": "serving", "name": "serving/decode_fused",
                       "us_per_call": 109.0,
                       "derived": "tok_s=9158;p50_us=109"}
    out = tmp_path / "BENCH_test.json"
    run.write_json(rows, str(out))
    doc = json.loads(out.read_text())
    assert doc["schema"] == "sosa-bench-v1"
    assert doc["rows"][1]["suite"] == "kernels"
    assert {"suite", "name", "us_per_call", "derived"} <= set(
        doc["rows"][0])


def test_parse_row_keeps_commas_in_derived():
    """`derived` is everything past the second comma, verbatim — error
    messages (and future derived values) containing commas must survive
    the CSV round trip."""
    run = _load_bench_run()
    row = run.parse_row(
        "serving/ERROR,0,error_type=ValueError;"
        "error_msg=bad shapes (4, 8), expected (8, 4)")
    assert row["suite"] == "serving"
    assert row["us_per_call"] == 0.0
    assert row["derived"] == ("error_type=ValueError;"
                              "error_msg=bad shapes (4, 8), expected (8, 4)")


def test_error_row_carries_exception_type_and_message():
    run = _load_bench_run()
    try:
        raise RuntimeError("jit cache blew\n  past the,bound")
    except RuntimeError as e:
        line = run.error_row("serving", e)
    row = run.parse_row(line)
    assert row["name"] == "serving/ERROR"
    # type and message are greppable key=value fields; newlines flattened,
    # commas intact
    assert "error_type=RuntimeError" in row["derived"]
    assert "error_msg=jit cache blew past the,bound" in row["derived"]
    # empty-message exceptions still say something
    assert "error_msg=<no message>" in run.error_row("x", ValueError())


def test_validate_doc_catches_malformed_records():
    run = _load_bench_run()
    good = {"schema": "sosa-bench-v1", "created_unix": 1e9,
            "argv": ["--json", "x"],
            "rows": [{"suite": "s", "name": "s/a", "us_per_call": 1.0,
                      "derived": "d"},
                     {"suite": "s", "name": "s/_total", "us_per_call": 2.0,
                      "derived": "done"}]}
    assert run.validate_doc(good) == []
    assert run.validate_doc({"schema": "wrong"})       # missing everything
    bad_suite = json.loads(json.dumps(good))
    bad_suite["rows"][0]["name"] = "other/a"           # name != suite
    assert any("does not start with suite" in p
               for p in run.validate_doc(bad_suite))
    no_total = {**good, "rows": [good["rows"][0]]}
    assert any("_total" in p for p in run.validate_doc(no_total))


@pytest.mark.tier1
def test_committed_bench_records_validate():
    """Every BENCH_*.json committed at the repo root must parse against
    the sosa-bench-v1 schema (at least one must exist — the perf
    trajectory record this repo keeps across PRs)."""
    import glob
    run = _load_bench_run()
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    assert paths, "no BENCH_*.json committed at the repo root"
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        problems = run.validate_doc(doc)
        assert problems == [], f"{os.path.basename(path)}: {problems}"
        # a committed record must be a clean run: no ERROR rows
        errors = [r["name"] for r in doc["rows"]
                  if r["name"].endswith("/ERROR")]
        assert errors == [], f"{os.path.basename(path)}: {errors}"
