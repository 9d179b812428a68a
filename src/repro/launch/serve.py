"""Serving entry point: batched requests through the continuous-batching
engine, every projection on the Pallas pod GEMM (Mosaic on a TPU,
interpret mode on the CPU backend).

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 6 --slots 3 --max-new 12 --max-len 128 \
        --metrics --trace-out serve_trace.json

`build_engine` and `serve` are the same steps for other callers in one
process (chip_smoke.py). The run exits 1 if any request was shed for
non-finite logits.

`--metrics` prints the engine's telemetry snapshot (obs.metrics) after the
run; `--trace-out PATH` writes the run as Chrome trace-event JSON —
drag-and-drop it into ui.perfetto.dev or chrome://tracing.

Overload & failure knobs (serve/admission.py, serve/chaos.py):
`--policy {fifo,edf,slo-aware}` selects the admission policy, `--deadline
SECONDS` stamps every generated request with that deadline, `--max-queue N`
bounds the queue (backpressure: over-budget submissions are shed with
`Request.state == "rejected"`), and `--chaos-*` arm the seeded fault
injector so the retry/shedding machinery is observable from the CLI.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduced as reduce_cfg
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.serve.admission import AdmissionConfig, POLICIES
from repro.serve.chaos import ChaosConfig
from repro.serve.engine import Request, ServeEngine


def build_engine(arch: str, *, reduced: bool = False, slots: int,
                 max_len: int, seed: int = 0, **engine_kw) -> ServeEngine:
    """The served model: `arch` (cut by configs.reduced when `reduced`)
    with every projection on the Pallas pod GEMM, random weights from
    `seed`, behind a continuous-batching ServeEngine. `engine_kw` passes
    through to ServeEngine (metrics, tracer, chaos, admission, ...)."""
    cfg = get_arch(arch)
    if reduced:
        cfg = reduce_cfg(cfg)
    model = Model(cfg, use_pallas=True)
    params = model.init(jax.random.PRNGKey(seed))
    return ServeEngine(model, params, slots=slots, max_len=max_len,
                       **engine_kw)


def serve(engine: ServeEngine, reqs: list[Request]) -> tuple[int, float]:
    """Submit `reqs` and step the engine until queue and slots drain.
    Returns (engine steps, wall seconds)."""
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    steps = 0
    while engine.queue or any(engine.active):
        engine.step()
        steps += 1
    return steps, time.perf_counter() - t0


def non_finite(reqs: list[Request]) -> list[Request]:
    """Requests the engine shed because their logits went NaN/Inf."""
    return [r for r in reqs
            if r.state == "rejected" and r.reason == "non-finite-logits"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--metrics", action="store_true",
                    help="print the obs.metrics snapshot after the run")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="write the run as Perfetto/Chrome trace JSON")
    ap.add_argument("--policy", choices=POLICIES, default="fifo",
                    help="admission policy (serve/admission.py)")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request deadline in seconds from submit")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bounded queue: shed submissions beyond N queued")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="arm the fault injector with this seed")
    ap.add_argument("--chaos-fault-p", type=float, default=0.1,
                    help="per-call transient-fault probability")
    ap.add_argument("--chaos-slow-p", type=float, default=0.1,
                    help="per-call slow-chunk probability")
    args = ap.parse_args(argv)

    use_compile_cache()
    metrics = tracer = None
    if args.metrics:
        from repro.obs.metrics import MetricsRegistry
        metrics = MetricsRegistry()
    if args.trace_out:
        from repro.tenancy.trace import ServeTraceRecorder
        tracer = ServeTraceRecorder()
    chaos = None
    if args.chaos_seed is not None:
        chaos = ChaosConfig(seed=args.chaos_seed,
                            p_fault=args.chaos_fault_p,
                            p_slow=args.chaos_slow_p)
    engine = build_engine(
        args.arch, reduced=args.reduced, slots=args.slots,
        max_len=args.max_len, metrics=metrics, tracer=tracer, chaos=chaos,
        admission=AdmissionConfig(policy=args.policy,
                                  max_queue=args.max_queue))

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, engine.model.cfg.vocab,
                                        rng.integers(4, 24), dtype=np.int32),
                    max_new_tokens=args.max_new, deadline_s=args.deadline)
            for i in range(args.requests)]
    steps, dt = serve(engine, reqs)
    total_new = sum(len(r.out) for r in reqs)
    for r in reqs:
        tail = "" if r.state == "done" else \
            f"  [{r.state}{': ' + r.reason if r.reason else ''}]"
        print(f"req {r.rid}: prompt_len={len(r.prompt)} -> {r.out}{tail}")
    dev = jax.devices()[0]
    print(f"{args.requests} requests, {total_new} tokens, {steps} engine "
          f"steps, {dt:.1f}s wall on {dev.platform} {dev.device_kind} "
          f"x{jax.device_count()}")
    c = engine.admission.counts
    if c["rejected"] or c["expired"] or args.deadline is not None:
        print(f"admission[{args.policy}]: {c}; "
              f"slo_attainment={engine.admission.slo_attainment:.2f}")
    if metrics is not None:
        print("metrics snapshot:")
        print(metrics.dumps(indent=1))
    if tracer is not None:
        from repro.obs.export import write_chrome_trace
        n = write_chrome_trace(args.trace_out, tracer.spans)
        print(f"wrote {n} spans to {args.trace_out} "
              f"(open in ui.perfetto.dev)")
    bad = non_finite(reqs)
    if bad:
        print(f"non-finite logits shed {len(bad)} request(s): "
              f"{[r.rid for r in bad]}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
