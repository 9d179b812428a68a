"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py

Serves yi-6b at its published widths and full depth (32 layers, d_model
4096, 6.06 B parameters, random weights from a seed) through the same
functions as `python -m repro.launch.serve`: a ServeEngine whose every
projection runs on the Pallas pod GEMM, compiled by Mosaic. One process
holds the chip. Phases, each printed on its own line:

  a. device and environment;
  b. the pod GEMM at yi-6b's decode, prefill and LM-head shapes against
     jnp.dot, with Mosaic (`tpu_custom_call`) in the compiled text;
  c. eight seeded requests over three prefill buckets, each required to
     finish `done` with its full token budget;
  d. last-position prefill logits of the served model (pod GEMM) against
     the einsum model on the same weights.

The last line of standard output is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every phase passed on a
TPU. On any other backend the script exits non-zero without it.
`--reduced` runs the phases at configs.reduced widths: a CPU rehearsal of
the control flow, which never passes. Times printed here are smoke output
(compilation included), not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import build_engine, non_finite, serve  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve.engine import Request  # noqa: E402

ARCH = "yi-6b"
SEED = 0
SLOTS = 4
MAX_LEN = 1024
MAX_NEW = 32
# three prefill buckets (16, 128, 512), two to four requests each
PROMPT_LENS = (14, 16, 100, 120, 300, 350, 400, 420)

# Pod GEMM against jnp.dot on the same bf16 inputs. A bf16 x bf16 product
# is exact in float32 and both sides accumulate in float32, so they differ
# only in summation order: |err_ij| <~ K * 2^-24 * ||x_i|| * ||w_j||
# (Cauchy-Schwarz), about 1e-7 of that scale for random inputs. Accumulating
# in bf16 (2^-9 per add) would give about 2e-3 of it, so 1e-4 passes only a
# float32 accumulation.
KERNEL_TOL = 1e-4
# Served (pod GEMM) against einsum logits, both bf16 end to end: the two
# paths round GEMM outputs and activations at different points (the pod
# GEMM applies silu to the float32 accumulator, einsum to the bf16 output),
# about 2^-9 relative per rounding, some ten roundings per layer, as a
# random walk over 32 layers: sqrt(320) * 2^-9 ~ 0.035 of the logits'
# standard deviation in RMS, ~0.15 at the maximum over 64000 logits. A
# wrong kernel lands at or above 1.
LOGITS_TOL = 0.25
# The prompt is one token long. The schema's fan-in rule (layers.py) scales
# the q/k/v weights [d, heads, hd] by 1/sqrt(heads), so random-weight
# attention logits have a standard deviation in the hundreds and softmax
# picks its key by margins below bf16 rounding: from position 1 on, any
# two bf16 paths (and each against float32) differ by O(1) of the logits'
# standard deviation. A lone token attends only to itself.
LOGITS_PROMPT_LEN = 1


def say(phase: str, ok: bool, msg: str) -> bool:
    print(f"[{phase}] {'PASS' if ok else 'FAIL'} {msg}", flush=True)
    return ok


def phase_device() -> tuple[bool, jax.Device]:
    import importlib.metadata as md

    import jaxlib
    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    stats = dev.memory_stats() or {}
    say("a", True,
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
        f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={jax.device_count()} "
        f"hbm_bytes_limit={stats.get('bytes_limit', 'not reported')}")
    return dev.platform == "tpu", dev


def phase_kernels(cfg, on_tpu: bool) -> bool:
    from repro.kernels.systolic_gemm.ops import systolic_gemm
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    kv = cfg.n_kv_heads * cfg.resolved_head_dim
    prefill_m = SLOTS * 512
    cases = [("decode up", SLOTS, d, f), ("decode down", SLOTS, f, d),
             ("decode kv", SLOTS, d, kv), ("lm head", SLOTS, d, v),
             ("prefill up", prefill_m, d, f),
             ("prefill down", prefill_m, f, d)]
    ok = True
    key = jax.random.PRNGKey(SEED)
    for name, m, k, n in cases:
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (m, k), jnp.bfloat16)
        w = jax.random.normal(kw, (k, n), jnp.bfloat16)
        compiled = systolic_gemm.lower(x, w, out_dtype=jnp.float32).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        out = compiled(x, w)
        ref = jnp.dot(x, w, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
        xn = jnp.linalg.norm(x.astype(jnp.float32), axis=1)
        wn = jnp.linalg.norm(w.astype(jnp.float32), axis=0)
        err = float(jnp.max(jnp.abs(out - ref) / (xn[:, None] * wn[None, :])))
        good = bool(np.isfinite(err)) and err <= KERNEL_TOL and \
            (mosaic or not on_tpu)
        ok &= say("b", good,
                  f"systolic_gemm {name} [{m},{k}]x[{k},{n}] f32 out: "
                  f"max|out-ref|/(|x_i||w_j|)={err:.3e} (bound "
                  f"{KERNEL_TOL:.0e}) tpu_custom_call={mosaic}")
    return ok


def phase_serve(reduced: bool, on_tpu: bool):
    t0 = time.perf_counter()
    engine = build_engine(ARCH, reduced=reduced, slots=SLOTS,
                          max_len=MAX_LEN, seed=SEED)
    cfg = engine.model.cfg
    jax.block_until_ready(engine.params)
    say("c", True,
        f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, {engine.model.param_count() / 1e9:.3f} B "
        f"params; use_pallas={engine.model.use_pallas}, {SLOTS} slots, "
        f"max_len {MAX_LEN}; built in {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                               dtype=np.int32),
                    max_new_tokens=MAX_NEW)
            for i, n in enumerate(PROMPT_LENS)]
    steps, wall = serve(engine, reqs)
    tokens = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"  req {r.rid}: prompt_len={len(r.prompt)} state={r.state}"
              f"{' (' + r.reason + ')' if r.reason else ''} "
              f"tokens={len(r.out)} first={r.out[:4]}", flush=True)
    done = all(r.state == "done" and len(r.out) == MAX_NEW for r in reqs)
    counts = engine.admission.counts
    clean = not counts["rejected"] and not counts["expired"] \
        and not non_finite(reqs)
    stats = jax.devices()[0].memory_stats() or {}
    say("c", done and clean,
        f"{len(reqs)} requests, {tokens} tokens (budget "
        f"{len(reqs) * MAX_NEW}), {steps} engine steps, smoke wall "
        f"{wall:.1f}s with compiles (not a benchmark); prefill jit entries "
        f"{engine.prefill_compiles}, decode jit entries "
        f"{engine._decode_fn._cache_size()}; admission {counts}; "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")

    # the served programs themselves: Mosaic kernels in the lowered decode
    # chunk and prefill (interpret mode would lower to plain HLO instead)
    z = jnp.zeros(SLOTS, jnp.int32)
    dec = engine._decode_fn.lower(
        engine.params, engine.cache, z, z, z, jnp.zeros(SLOTS, bool),
        n=1).as_text().count("tpu_custom_call")
    pre = engine._prefill_fn.lower(
        engine.params, jnp.zeros((SLOTS, 16), jnp.int32), engine.cache,
        jnp.full(SLOTS, -1, jnp.int32), jnp.ones(SLOTS, jnp.int32)
    ).as_text().count("tpu_custom_call")
    ok = say("c", (dec > 0 and pre > 0) or not on_tpu,
             f"tpu_custom_call in served programs: decode chunk {dec}, "
             f"prefill {pre}")
    return done and clean and ok, engine, reqs


def phase_logits(engine, prompt: np.ndarray) -> bool:
    cfg, params = engine.model.cfg, engine.params
    batch = {"tokens": jnp.asarray(prompt)[None, :]}

    def last_logits(model: Model):
        cache = model.init_cache(1, len(prompt))
        logits, _ = jax.jit(model.prefill)(params, batch, cache)
        return logits[0].astype(jnp.float32)

    served = last_logits(engine.model)
    ref = last_logits(Model(cfg))
    std = float(jnp.std(ref))
    diff = float(jnp.max(jnp.abs(served - ref)))
    ratio = diff / std
    return say("d", bool(np.isfinite(ratio)) and ratio <= LOGITS_TOL,
               f"prompt_len={len(prompt)} last-position logits, pod GEMM vs "
               f"einsum: max|diff|={diff:.4g}, std(ref)={std:.4g}, "
               f"max|diff|/std={ratio:.4f} (bound {LOGITS_TOL}); argmax "
               f"{int(jnp.argmax(served))} vs {int(jnp.argmax(ref))}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at configs.reduced widths; never "
                         "passes")
    args = ap.parse_args(argv)

    use_compile_cache()
    on_tpu, dev = phase_device()
    if not on_tpu and not args.reduced:
        say("a", False, f"no TPU: JAX's first device is {dev.platform!r}")
        return 1
    from repro.configs import get_arch, reduced as reduce_cfg
    cfg = get_arch(ARCH)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    ok = phase_kernels(cfg, on_tpu)
    served_ok, engine, reqs = phase_serve(args.reduced, on_tpu)
    ok &= served_ok
    ok &= phase_logits(engine, reqs[0].prompt[:LOGITS_PROMPT_LEN])
    if not (ok and on_tpu):
        say("end", False, "smoke failed" if not ok else
            f"rehearsal on {dev.platform!r}: not a chip run")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
