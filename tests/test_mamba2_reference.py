"""mamba2-370m's path through ServeEngine against the benchmark's plain
float32 Mamba-2 reference (bench/refs/mamba2.py, loaded by path), at
CPU-test widths (bench/tests/data/configs/tiny-mamba2.json) with seeded
weights drawn by the benchmark's own rules (bench/weights.py).

The engine serves on the pod GEMM (interpret mode here): its bucketed
prefill, then decode chunks through the recurrent cache. Every logit row
the model computes on that path is recorded (a host callback in
`forward`) and compared with the reference's full forward pass over the
same tokens at the same position: logits, not tokens, since a random
model's top logit flips on rounding.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.model import Model
from repro.models.ssm import SSMCache, ssd_decode_step
from repro.serve.engine import Request, ServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
CONF = json.loads((BENCH / "tests" / "data" / "configs" /
                   "tiny-mamba2.json").read_text())
SEED = 2 ** 33 + 17
# The widest gap, |program logit - reference logit| / std of the
# reference's logits at that position, over every logit of every compared
# row. The program computes in bf16 (weights, activations, the stored
# recurrent state; float32 residual stream and state update), the
# reference in float32 on the same bf16 weights: bf16 rounding (2**-8
# relative) of each layer's activations reads 0.015-0.061 over this
# test's 38 rows, with no growth along the decode. A reference that
# leaves out one term of the mixer reads 4.7 (the gate) and 6.1 (the D
# skip). 0.15 leaves 2.5 times the widest reading above it and 30 times
# below the mutations.
TOL = 0.15


def _bench_module(name: str, path: Path):
    """A module of bench/ by path. bench/ goes last on sys.path (the
    reference imports bench/refmath.py), and what a module puts in front
    of it while it loads is taken out again."""
    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    before = list(sys.path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.path[:] = before
    return mod


@pytest.fixture(scope="module")
def bench():
    run = _bench_module("bench_run", BENCH / "run.py")
    weights = _bench_module("bench_weights", BENCH / "weights.py")
    ref = _bench_module("bench_refs_mamba2", BENCH / "refs" / "mamba2.py")
    return run, weights, ref


class Recorded(Model):
    """The program's model, recording every logit row it computes: with
    the positions each row is for (prefill: 0..S-1 of every lane; decode:
    the lane's position)."""

    rows: list

    def forward(self, params, batch, cache=None, positions=None,
                true_lens=None):
        logits, cache = super().forward(params, batch, cache=cache,
                                        positions=positions,
                                        true_lens=true_lens)
        B, S = logits.shape[:2]
        pos = (jnp.broadcast_to(jnp.arange(S), (B, S)) if positions is None
               else jnp.broadcast_to(positions, (B, S)))
        jax.debug.callback(
            lambda lg, p: self.rows.append((np.asarray(lg, np.float32),
                                            np.asarray(p))),
            logits, pos)
        return logits, cache


@pytest.fixture(scope="module")
def served(bench):
    """Two requests served by the engine (one prefill bucket, then
    decode chunks), the tokens they saw, and the logit rows recorded."""
    run, weights, _ = bench
    cfg = run.arch_config(CONF)
    model = Recorded(cfg, use_pallas=True)
    model.rows = []
    params = weights.draw(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                          SEED)
    spans = []

    class Spans:
        def on_prefill(self, *a, **k):
            pass

        def on_decode(self, *a, **k):
            pass

        def on_span(self, name, ts, dur, cat="serve", **args):
            spans.append((name, args))

    eng = ServeEngine(model, params, slots=3, max_len=64, decode_chunk=4,
                      tracer=Spans())
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, CONF["vocab_size"], n,
                                               dtype=np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(11, 9), (14, 6)])]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    jax.effects_barrier()
    assert all(r.state == "done" for r in reqs)
    return cfg, params, eng, reqs, list(model.rows), spans


def _program_rows(rows, reqs):
    """For each request (lane = its slot: both start in one prefill on
    lanes 0 and 1 and keep slots 0 and 1), the program's logits at every
    position of prompt + served tokens but the last, from the prefill's
    rows (positions under the prompt length) and the decode steps'."""
    out = []
    for lane, r in enumerate(reqs):
        n = len(r.prompt) + len(r.out) - 1
        got = {}
        for lg, pos in rows:
            for s in range(lg.shape[1]):
                p = int(pos[lane, s])
                last = len(r.prompt) - 1
                prefill = lg.shape[1] > 1
                if (prefill and p <= last) or (not prefill and last < p < n):
                    got.setdefault(p, lg[lane, s])
        assert sorted(got) == list(range(n)), (lane, sorted(got))
        out.append(np.stack([got[p] for p in range(n)]))
    return out


def _ref_logits(ref, params, r):
    conf = CONF
    toks = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
    with jax.default_matmul_precision("highest"):
        h = ref.embed(conf, params, jnp.asarray(toks)[None])
        for i in range(ref.n_layers(conf)):
            h = ref.layer(conf, params, i, h)
        return np.asarray(ref.head(conf, params, h)[0])


def _widest_gap(prog, refl):
    return max(float((np.abs(p - q) / q.std(axis=-1, keepdims=True)).max())
               for p, q in zip(prog, refl))


def test_engine_logits_match_the_reference(bench, served):
    _, _, ref = bench
    _, params, _, reqs, rows, _ = served
    prog = _program_rows(rows, reqs)
    refl = [_ref_logits(ref, params, r) for r in reqs]
    gap = _widest_gap(prog, refl)
    assert gap < TOL, gap


@pytest.mark.parametrize("mutation", ["no_d_skip", "no_gate"])
def test_a_mutated_reference_fails_the_tolerance(bench, served, monkeypatch,
                                                 mutation):
    """The tolerance is tight enough to see one term of the mixer go: the
    D skip, or the gate silu(z) before the norm."""
    _, _, ref = bench
    _, params, _, reqs, rows, _ = served
    if mutation == "no_d_skip":
        orig = ref._recurrence
        monkeypatch.setattr(ref, "_recurrence",
                            lambda x, dt, A, B, C, D: orig(x, dt, A, B, C,
                                                           0.0 * D))
    else:
        monkeypatch.setattr(ref, "_gated_rmsnorm",
                            lambda y, z, w, eps: ref.rmsnorm(y, w, eps))
    prog = _program_rows(rows, reqs)
    refl = [_ref_logits(ref, params, r) for r in reqs]
    assert _widest_gap(prog, refl) > TOL


def _bf16_state_recurrence(x, dt, A, B, C, D):
    """The reference's recurrence with its state rounded to bf16 after
    every position, as a state stored in bf16 between steps is."""
    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        decay = jnp.exp(dt_t * A)[..., None, None]
        h = decay * h + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None]
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
        y = jnp.sum(h * C_t[:, :, None], axis=-1) + D[:, None] * x_t
        return h, y

    b, _, H, P = x.shape
    h0 = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    _, y = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(y, 0, 1)


def test_bf16_state_storage_is_within_the_tolerance(bench, served,
                                                    monkeypatch):
    """Rounding the state to bf16 at every position is the rounding the
    published model makes (it stores its state in bf16), and the logit
    comparison cannot tell it from the float32 recurrence: it reads
    0.0603 against the plain reference's 0.0607 here. The precision of
    the state update is held by the decode-step test below instead."""
    _, _, ref = bench
    _, params, _, reqs, rows, _ = served
    monkeypatch.setattr(ref, "_recurrence", _bf16_state_recurrence)
    prog = _program_rows(rows, reqs)
    refl = [_ref_logits(ref, params, r) for r in reqs]
    assert _widest_gap(prog, refl) < TOL


def _bf16_multiply_add_step(x, dt, A, B, C, D, h):
    """A decode step that multiplies and adds the state in bf16: the decay
    exp(dt A) is rounded to bf16 first, so a head whose dt A is above
    -2**-9 never decays."""
    rep = x.shape[1] // B.shape[1]
    Bh, Ch = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A[None, :])[..., None, None].astype(h.dtype)
    h_new = h * dA + jnp.einsum("bH,bHn,bHp->bHpn", dtf.astype(x.dtype),
                                Bh, x)
    y = jnp.einsum("bHn,bHpn->bHp", Ch, h_new) + x * D[None, :, None]
    return y, h_new


# Relative error (max |h - h64| / max |h64|) of the bf16-stored state after
# up to 512 decode steps of slowly decaying heads (dt A in [-0.004,
# -0.0005], as Mamba-2's init gives its slowest heads), against a float64
# recurrence over the same bf16 inputs. The float32 update stored in bf16
# reads 0.051: rounding noise that adds up like a random walk. The bf16
# multiply-add reads 0.24, since its decay rounds to 1. 0.1 lies 2.0 times
# above the one and 2.4 times below the other.
STEP_TOL = 0.1


@pytest.mark.parametrize("step", ["float32_update", "bf16_multiply_add"])
def test_decode_step_keeps_slow_heads_decaying(step):
    fn = jax.jit(ssd_decode_step if step == "float32_update"
                 else _bf16_multiply_add_step)
    rng = np.random.default_rng(7)
    b, H, P, N, T = 2, 4, 16, 32, 512
    A = -np.ones(H, np.float32)
    D = np.ones(H, np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    h64 = np.zeros((b, H, P, N))
    h = bf(h64)
    worst = 0.0
    for _ in range(T):
        x, B, C = (bf(rng.standard_normal(s)) for s in
                   ((b, H, P), (b, 1, N), (b, 1, N)))
        dt = rng.uniform(0.0005, 0.004, (b, H)).astype(np.float32)
        _, h = fn(x, jnp.asarray(dt), jnp.asarray(A), B, C, jnp.asarray(D), h)
        x64, B64 = np.asarray(x, np.float64), np.asarray(B, np.float64)
        h64 = (h64 * np.exp(dt * A)[..., None, None]
               + (dt[..., None] * x64)[..., None] * B64[:, :, None, :])
        worst = max(worst, float(np.abs(np.asarray(h, np.float64) - h64).max()
                                 / np.abs(h64).max()))
    if step == "float32_update":
        assert worst < STEP_TOL, worst
    else:
        assert worst > STEP_TOL, worst


def test_spans_carry_the_lanes_recurrent_state(served):
    cfg, _, eng, _, _, spans = served
    lane = sum(leaf.lane_bytes() for leaf in jax.tree.leaves(
        eng.cache, is_leaf=lambda x: isinstance(x, SSMCache))
        if isinstance(leaf, SSMCache))
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    assert lane == cfg.n_layers * 2 * (
        s.n_heads(cfg.d_model) * s.head_dim * s.d_state
        + (s.conv_kernel - 1) * (di + 2 * s.n_groups * s.d_state))
    assert eng.lane_state_bytes == lane
    calls = [(n, a) for n, a in spans if n.startswith(("prefill/",
                                                       "decode/"))]
    assert calls and all(a["state_bytes"] == lane * a["lanes"]
                         for _, a in calls)
