"""Chip benchmark of the served path: ServeEngine over the Pallas pod GEMM.

    python3 bench/run.py --workload yi6b_chat --seed 7 --seconds 40 --trace 0

One process, one chip. A cell of BENCHMARK.json names a configuration
(bench/configs/) and a traffic mix (bench/traffic/). The run draws the
weights from --seed (bench/weights.py), builds the engine as
`launch/serve.build_engine` does, warms every prefill bucket and decode
chunk length the cell uses, measures --seconds of traffic, drains, and
then checks a sample of the served tokens against the plain float32
reference of the model family (bench/check.py). With --trace 1 it reports
the per-layer metrics instead, read from the engine's spans and from a
profiler trace of the window's last seconds.

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device, check). Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits non-zero.

`--rehearse CONFIG:TRAFFIC` runs a configuration and mix that no cell uses
on any backend (`JAX_PLATFORMS=cpu` for a CPU rehearsal at tiny size). It
prints what a run would report and always exits 1: a rehearsal is not a
measurement.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import cells  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 3.0
WARM_NEW_TOKENS = 16           # decode chunks 8, 4, 2, 1 on an empty queue
REPORT_INF = 1e300             # a tail that reached a failed request


def say(*a) -> None:
    print(*a, flush=True)


def err(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# -- configuration -------------------------------------------------------

def _get(d: dict, dotted: str):
    for k in dotted.split("."):
        d = d[k]
    return d


def _tuples(v):
    """A JSON value with its lists made tuples (and its objects copied)."""
    if isinstance(v, dict):
        return {k: _tuples(x) for k, x in v.items()}
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def _put(kw: dict, dotted: str, value) -> None:
    *group, leaf = dotted.split(".")
    for g in group:
        kw = kw.setdefault(g, {})
    kw[leaf] = _tuples(value)


def arch_config(conf: dict):
    """The program's ArchConfig, derived from the file's published keys:
    `program_keys` maps an ArchConfig field to the published key that
    fixes it; `program_fixed` holds the fields that no published key
    gives, such as the family. A field of a nested group is dotted in
    either (`ssm.d_state`); the groups `ssm`, `moe` and `mla` become
    SSMConfig, MoEConfig and MLAConfig. JSON lists become tuples."""
    from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, \
        SSMConfig
    groups = {"ssm": SSMConfig, "moe": MoEConfig, "mla": MLAConfig}
    kw = {"name": conf["name"]}
    for field, value in conf.get("program_fixed", {}).items():
        _put(kw, field, value)
    for field, key in conf["program_keys"].items():
        _put(kw, field, _get(conf, key))
    for field, value in kw.items():
        if isinstance(value, dict):
            if field not in groups:
                raise ValueError(f"{conf['name']}: {field!r} is no nested "
                                 f"group of ArchConfig")
            kw[field] = groups[field](**value)
    return ArchConfig(**kw)


def buckets(mix: dict, max_len: int, min_bucket: int = 8) -> list[int]:
    """The engine's power-of-two prefill buckets the mix's prompts fall in
    (ServeEngine rounds a prompt up to max(min_bucket, len), capped at
    max_len)."""
    def bucket(n):
        return min(1 << (max(min_bucket, n) - 1).bit_length(), max_len)
    lo, hi = loadgen.length_bounds(mix)
    out, b = [], bucket(lo)
    while b <= bucket(hi):
        out.append(b)
        b *= 2
    return out


# -- tracing -------------------------------------------------------------

class SpanLog:
    """The engine's tracer: keeps its timed spans (device calls) with the
    engine-relative start moved onto the host clock."""

    def __init__(self, origin: float):
        self.origin = origin
        self.spans: list[tuple[str, float, float, dict]] = []

    def on_prefill(self, rid, prompt_len, t=None) -> None:
        pass

    def on_decode(self, lanes, contexts, t=None) -> None:
        pass

    def on_span(self, name, ts, dur, cat="serve", **args) -> None:
        self.spans.append((name, self.origin + ts, dur, args))


class Profiler:
    """jax.profiler over the window's last TRACE_SECONDS; a marker
    annotation ties the host clock to the trace's."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.started = None
        self.mark = None
        self.stopped = None

    def tick(self, now: float) -> None:
        import jax
        if self.started is None and now >= self.t_start:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self.started = time.perf_counter()
            import devtrace as tr
            self.mark = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.MARK):
                pass

    def stop(self) -> None:
        import jax
        if self.started is not None and self.stopped is None:
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCount:
    """Counts traces and compiles JAX reports while `on` is set."""

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.events: dict[str, int] = {}
        mon.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, duration, **kw) -> None:
        if self.on and name.startswith("/jax/core/compile/"):
            self.events[name] = self.events.get(name, 0) + 1

    @property
    def count(self) -> int:
        return sum(self.events.values())


# -- the run ---------------------------------------------------------------

class Ctx:
    """What a per-layer metric reads (bench/metrics/<name>.py)."""

    def __init__(self, **kw):
        self.notes: dict[str, str] = {}
        self.__dict__.update(kw)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def warm(engine, Request, mix: dict, dep: dict, vocab: int) -> int:
    """Compile (or load from the cache) every prefill bucket of the mix
    and the decode chunk lengths 8, 4, 2, 1: one request per bucket, all
    at once, each with WARM_NEW_TOKENS to decode."""
    import numpy as np
    rng = np.random.default_rng(0)
    lo, hi = loadgen.length_bounds(mix)
    reqs = [Request(rid=-1 - i,
                    prompt=rng.integers(0, vocab, min(max(b, lo), hi),
                                        dtype=np.int32),
                    max_new_tokens=WARM_NEW_TOKENS)
            for i, b in enumerate(buckets(mix, dep["max_len"]))]
    for r in reqs:
        engine.submit(r)
    while engine.queue or any(r is not None for r in engine.active):
        engine.step()
    bad = [r.rid for r in reqs if r.state != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests not done: {bad}")
    return len(reqs)


class Setup:
    """The served model of one configuration: weights from a seed, the
    engine as launch/serve.build_engine builds it (pod GEMM on every
    projection, dense cache, default admission) and its warm-up."""

    def __init__(self, conf: dict, mix: dict, seed: int, trace: bool):
        import jax

        from repro.models.model import Model
        from repro.serve.engine import Request, ServeEngine

        import weights
        self.Request = Request
        self.conf, self.mix = conf, mix
        self.dep = dep = conf["deployment"]
        self.model = Model(arch_config(conf), use_pallas=True)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.params = weights.draw(self.shapes, seed)
        jax.block_until_ready(self.params)
        self.t_weights = time.perf_counter()
        self.spans = SpanLog(time.perf_counter()) if trace else None
        self.engine = ServeEngine(self.model, self.params,
                                  slots=dep["slots"], max_len=dep["max_len"],
                                  decode_chunk=dep["decode_chunk"],
                                  tracer=self.spans)
        if self.spans is not None:
            self.spans.origin = time.perf_counter()
        self.n_warm = warm(self.engine, Request, mix, dep,
                           dep["prompt_vocab"])
        self.t_warm = time.perf_counter()

    def reseed(self, seed: int) -> None:
        """New weights in the same engine (calibration: one set-up, many
        seeds). The old weights go first: two sets need not fit."""
        import jax

        import weights
        self.engine.params = self.params = None
        gc.collect()
        self.params = weights.draw(self.shapes, seed)
        jax.block_until_ready(self.params)
        self.engine.params = self.params

    def loop(self, seed: int, trace: bool, tick=None, on_close=None):
        from serveloop import Loop
        tokens = loadgen.TokenSource(seed, self.dep["prompt_vocab"])
        Request = self.Request

        def make(spec):
            return Request(rid=spec.idx, prompt=tokens(spec),
                           max_new_tokens=spec.out_len)
        return Loop(self.engine, make, annotate=annotate if trace else None,
                    tick=tick, on_close=on_close)


def serve(loop, mix: dict, seed: int, seconds: float):
    if mix["loop"] != "open":
        raise ValueError(f"{mix['name']}: unknown loop {mix['loop']!r}")
    specs = loadgen.open_loop(mix, seed, seconds)
    return loop.run_open(specs, seconds, mix["drain_cap_s"])


def compare(conf: dict, ref, params, win, seed: int, control=False):
    """Widest gap over the sample of finished requests (bench/check.py),
    as {"program": gap[, "control": gap]}, with the requests and served
    tokens compared."""
    import check
    chk = conf["check"]
    done = [r.req for r in win.records if r.done]
    picks = check.sample(done, seed, chk["sample_tokens"],
                         chk["max_requests"])
    if not picks:
        return {"program": math.nan}, 0, 0
    g = check.gaps(ref, conf, params, picks, chk["max_requests"],
                   control=control)
    widest = {k: float(max(float(x.max()) for x in v if len(x)))
              for k, v in g.items()}
    return widest, len(picks), sum(len(r.out) for r in picks)


def report_window(win, setup_s=None, compiles=None) -> dict:
    """Print the window's counts and latencies; return the end-to-end
    values it gives."""
    recs = win.records
    done = sum(r.done for r in recs)
    extra = (f"; compiles in window {compiles.count} {compiles.events}"
             if compiles is not None else "")
    say(f"window: {win.seconds:.4f}s, {win.steps} steps, {len(recs)} "
        f"requests ({done} done, {len(recs) - done} failed), prompt tokens "
        f"{win.prompt_tokens}, output tokens {win.output_tokens}, drain "
        f"{win.drain_s:.3f}s{extra}")
    values = {"tok_s": win.tok_s}
    if setup_s is not None:
        values["setup_s"] = setup_s
    if win.lateness:
        lat = win.lateness
        say(f"generator lateness: p50 {stats.percentile(lat, 50) * 1e3:.3f} "
            f"ms, p99 {stats.percentile(lat, 99) * 1e3:.3f} ms, max "
            f"{max(lat) * 1e3:.3f} ms over {len(lat)} submissions")
        ttft = [r.ttft() for r in recs]
        tpot = [r.tpot() for r in recs]
        values["ttft_p90_ms"] = stats.percentile(ttft, 90) * 1e3
        values["tpot_p90_ms"] = stats.percentile(tpot, 90) * 1e3
        say(f"latency: ttft p50 {stats.percentile(ttft, 50) * 1e3:.3f} ms "
            f"p90 {values['ttft_p90_ms']:.3f} ms; tpot p50 "
            f"{stats.percentile(tpot, 50) * 1e3:.3f} ms p90 "
            f"{values['tpot_p90_ms']:.3f} ms; n={len(recs)} "
            f"({sum(math.isinf(x) for x in ttft)} infinite)")
    return values


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_proc: float = T_PROC) -> dict:
    conf = cells.load_json("configs", cell["config"])
    mix = cells.load_json("traffic", cell["traffic"])
    ref = cells.load_module("refs", conf["ref"])
    metric_mods = {m["name"]: cells.load_module("metrics", m["name"])
                   for m in cell["per_layer"]} if trace else {}
    st = Setup(conf, mix, seed, trace)
    prof = Profiler(math.inf)
    compiles = CompileCount()

    def close():
        compiles.on = False
        prof.stop()
    loop = st.loop(seed, trace, tick=prof.tick, on_close=close)
    gc.collect()
    t_window = time.perf_counter()
    setup_s = t_window - t_proc
    if trace:
        prof.t_start = t_window + max(0.0, seconds - TRACE_SECONDS)
    compiles.on = True
    win = serve(loop, mix, seed, seconds)
    device = device_info(cell["chips"])
    say(f"setup: weights {st.t_weights - t_proc:.3f}s, engine and warm-up "
        f"of {st.n_warm} buckets {st.t_warm - st.t_weights:.3f}s, setup_s "
        f"{setup_s:.3f}")
    values = report_window(win, setup_s, compiles)
    say(f"device: {json.dumps(device)}")

    metrics, breakdown = {}, None
    result_device = dict(device)
    if trace:
        ctx = _per_layer_ctx(conf, ref, mix, st.dep, win, st.spans, prof,
                             device)
        for name, mod in metric_mods.items():
            v = mod.read(ctx)
            if v is not None:
                metrics[name] = v
        for name, note in ctx.notes.items():
            say(f"note {name}: {note}")
        if ctx.trace is not None:
            result_device["busy_s"] = ctx.trace["busy_s"]
            result_device["window_s"] = ctx.trace["window_s"]
            breakdown = ctx.trace["breakdown"]
        _memory_analysis(st.engine, st.dep, mix)
    else:
        for m in cell["end_to_end"]:
            v = values.get(m["name"])
            if v is None:
                say(f"metric {m['name']}: this mix does not give it")
                continue
            metrics[m["name"]] = REPORT_INF if math.isinf(v) else v

    # correctness, after the window, with the program's state freed
    params = st.params
    st.engine = st.params = loop = None
    gc.collect()
    t_ref = time.perf_counter()
    widest, n_req, n_tok = compare(conf, ref, params, win, seed)
    gap = widest["program"]
    limit = conf["check"]["gap_limit"]
    correct = n_req > 0 and gap <= limit
    say(f"reference: {n_req} requests, {n_tok} served tokens compared in "
        f"{time.perf_counter() - t_ref:.3f}s")
    err(f"check: gap_max {gap} limit {limit} over {n_req} requests, "
        f"{n_tok} served tokens")

    unit = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    unit.update({m["name"]: m["unit"] for m in cell["per_layer"]})
    recs = win.records
    result = {
        "correct": correct, "attempted": len(recs),
        "failed": sum(not r.done for r in recs),
        "metrics": {k: {"value": v, "unit": unit[k]}
                    for k, v in metrics.items()},
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {"gap_max": {"value": gap, "limit": limit}}
    return result


def _per_layer_ctx(conf, ref, mix, dep, win, spans, prof, device):
    import devtrace
    import peaks
    tdata = None
    if prof.started is not None:
        t = devtrace.load(TRACE_DIR)
        if t.ops and t.mark_ns is not None:
            off = t.mark_ns - prof.mark * 1e9       # trace ns - host ns
            engine_spans = [(f"engine.{name.split('/')[0]} call",
                             int(s * 1e9 + off), int(d * 1e9))
                            for name, s, d, _ in spans.spans]
            tdata = devtrace.summarize(t, int(prof.started * 1e9 + off),
                                       int(prof.stopped * 1e9 + off),
                                       engine_spans)
            say(f"idle by host activity (s): "
                f"{json.dumps(tdata['idle_by_host'])}")
    return Ctx(conf=conf, ref=ref, dep=dep, window=win,
               spans=spans.spans if spans else [], trace=tdata,
               peak=peaks.peak(device["kind"]) if device["platform"] == "tpu"
               else None, buckets=buckets(mix, dep["max_len"]))


def _memory_analysis(engine, dep, mix) -> None:
    """Bytes of the served programs (largest prefill bucket, decode chunk
    of decode_chunk steps) as the compiler reports them, beside the live
    bytes on the device."""
    import jax
    import jax.numpy as jnp
    S = dep["slots"]
    b = buckets(mix, dep["max_len"])[-1]
    z = jnp.zeros(S, jnp.int32)
    progs = {
        f"prefill[{S},{b}]": engine._prefill_fn.lower(
            engine.params, jnp.zeros((S, b), jnp.int32), engine.cache,
            jnp.full(S, -1, jnp.int32), jnp.ones(S, jnp.int32)),
        f"decode chunk {dep['decode_chunk']}": engine._decode_fn.lower(
            engine.params, engine.cache, z, z, z, jnp.zeros(S, bool),
            n=dep["decode_chunk"]),
    }
    live = sum(a.nbytes for a in jax.live_arrays())
    for name, low in progs.items():
        m = low.compile().memory_analysis()
        say(f"memory_analysis {name}: args {m.argument_size_in_bytes} out "
            f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} alias "
            f"{m.alias_size_in_bytes}; live bytes now {live}")


def cell_for(workload: str | None, rehearse: str | None) -> dict:
    """A cell of BENCHMARK.json, or for a rehearsal (CONFIG:TRAFFIC) one
    made up that reports every metric the benchmark defines."""
    bench = cells.load_benchmark()
    if not rehearse:
        return cells.cell(bench, workload)
    config, traffic = rehearse.split(":")
    return {"name": f"rehearsal-{config}", "config": config,
            "traffic": traffic, "chips": 1,
            "end_to_end": bench["end_to_end"],
            "per_layer": list(bench["per_layer"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if (args.workload is None) == (args.rehearse is None):
        ap.error("give exactly one of --workload and --rehearse")

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        err(f"no program: {src / 'repro'} is missing")
        return 2
    sys.path.insert(0, str(src))
    cell = cell_for(args.workload, args.rehearse)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    on_chip = devs[0].platform == "tpu" and len(devs) >= cell["chips"]
    if not on_chip and not args.rehearse:
        err(f"no chip: JAX's devices are {len(devs)} x "
            f"{devs[0].platform!r}; the cell needs {cell['chips']} TPU")
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    if args.rehearse:
        result["correct"] = False           # a rehearsal is never a pass
        say(f"rehearsal result (not a measurement): {json.dumps(result)}")
        err(f"rehearsal on {devs[0].platform!r}: no result line")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
