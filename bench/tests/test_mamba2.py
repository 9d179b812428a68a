"""mamba2-370m in the harness: the program's ArchConfig derived from the
published keys of bench/configs/mamba2-370m.json, the reference's counts,
the two readers of the recurrent state's device time (ssd_state_share,
ssd_state_roofline) on hand-made op texts and spans, the cell's traffic
pinned by a digest, and a CPU rehearsal of the correctness check at tiny
widths (bench/tests/data/configs/tiny-mamba2.json): the program passes
its limit and the float8 control fails it."""

import dataclasses
import hashlib
import types
from pathlib import Path

import jax
import pytest

import cells
import devtrace
import loadgen
import run

DATA = Path(__file__).resolve().parent / "data"
SHARE = cells.load_module("metrics", "ssd_state_share")
ROOF = cells.load_module("metrics", "ssd_state_roofline")
LANE_BYTES = 48 * (32 * 64 * 128 + 3 * 2304) * 2        # 25,829,376


@pytest.fixture(scope="module")
def mamba():
    conf = cells.load_json("configs", "mamba2-370m")
    return conf, cells.load_module("refs", conf["ref"])


def test_arch_config_from_published_keys(mamba):
    from repro.configs import get_arch
    from repro.models.model import Model
    conf, _ = mamba
    cfg = run.arch_config(conf)
    s = cfg.ssm
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.vocab) == \
        ("ssm", 48, 1024, 50288)
    assert (s.n_heads(1024), s.head_dim, s.d_state, s.conv_kernel,
            s.n_groups, s.chunk_size) == (32, 64, 128, 4, 1, 256)
    assert 2 * s.d_inner(1024) + 2 * s.n_groups * s.d_state + 32 == 4384
    assert cfg.tie_embeddings and cfg.residual_in_fp32
    # vocab_size padded to pad_vocab_size_multiple, as MambaLMHeadModel
    m = conf["pad_vocab_size_multiple"]
    assert cfg.vocab == -(-conf["vocab_size"] // m) * m
    assert dataclasses.replace(cfg, name="mamba2-370m") == \
        get_arch("mamba2-370m")
    assert Model(cfg).param_count() == 368_346_624


def test_yi6b_arch_config_gains_only_the_residual_flag():
    """yi-6b's ArchConfig is the one test_weights.py pins (PR 14's), but
    for the field this configuration added, which it leaves off."""
    cfg = run.arch_config(cells.load_json("configs", "yi-6b"))
    assert cfg.residual_in_fp32 is False
    text = repr(cfg).replace(", residual_in_fp32=False", "")
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "67170f37a6a931a6e72d1ccfcad147ed99e04623dd6758b3d3b453f14f2261ce"


def test_reference_counts(mamba):
    from repro.models.ssm import SSMCache
    conf, ref = mamba
    cfg = run.arch_config(conf)
    assert ref.gemm_shapes(conf) == [(1024, 4384), (2048, 1024),
                                     (1024, 50288)]
    lane = jax.eval_shape(lambda: SSMCache.zeros(cfg, 1, layers=48))
    assert ref.state_bytes(conf, 1) == LANE_BYTES == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(lane))
    assert ref.state_shapes(conf, 64) == [(48, 64, 32, 64, 128),
                                          (48, 64, 3, 2304)]
    assert ref.body_params(conf) == 48 * (1024 * 4384 + 2048 * 1024)


STATE = "bf16[48,64,32,64,128]{4,3,2,1,0:T(8,128)(2,1)}"
CONV = "bf16[48,64,3,2304]{3,2,1,0}"
TEXTS = {
    "update": (f"%select_dynamic-update-slice_fusion.63 = {STATE} fusion("
               f"{STATE} %copy.402, bf16[1,64,32,64,128]{{4,3,2,1,0}} %b, "
               "s32[] %i), kind=kLoop"),
    "read": ("%fusion.12 = bf16[64,32,64,128]{3,2,1,0} fusion("
             f"{STATE} %p, s32[] %i), kind=kLoop"),
    "conv": (f"%dynamic-update-slice_fusion.2 = {CONV} fusion({CONV} %c, "
             "bf16[1,64,3,2304]{3,2,1,0} %n, s32[] %i), kind=kLoop"),
    "gemm": ("%systolic_gemm.3 = bf16[64,4384]{1,0} custom-call(s32[1]{0} "
             "%b, bf16[64,1024]{1,0} %x, bf16[48,1024,4384]{2,1,0} %w), "
             'custom_call_target="tpu_custom_call"'),
    "loop": (f"%while.5 = (s32[], {STATE}) while((s32[], {STATE}) %t), "
             "condition=%c, body=%b"),
    "copy_out": (f"%copy.402 = {STATE} copy(bf16[48,64,2048,128]"
                 "{3,2,1,0} %q)"),
    "slice": ("%fusion.9 = bf16[64,32,64,128]{3,2,1,0} fusion("
              "bf16[64,32,64,128]{3,2,1,0} %p), kind=kLoop"),
}


def _ops():
    return [devtrace.device_op(t, 100 * i, 10 * (i + 1))
            for i, t in enumerate(TEXTS.values())]


def test_state_ops_are_the_readers_of_the_stacks(mamba):
    conf, ref = mamba
    found = SHARE.state_ops(_ops(), conf, ref, 64)
    # the stack copy reads a bitcast of the stack ([48, 64, 2048, 128],
    # as many elements): it moves the state too
    assert [o.hlo for o in found] == [TEXTS[k] for k in
                                      ("update", "read", "conv", "copy_out")]


def _ctx(conf, ref, ops, spans, busy_s=0.5, window_s=1.0):
    return types.SimpleNamespace(
        conf=conf, ref=ref, dep=conf["deployment"], notes={}, spans=spans,
        trace={"ops": ops, "busy_s": busy_s, "window_s": window_s},
        window=types.SimpleNamespace(t_close=10.0),
        peak={"hbm_bytes_s": 819e9, "bf16_flops": 197e12})


def test_share_divides_by_busy_time(mamba):
    conf, ref = mamba
    ctx = _ctx(conf, ref, _ops(), [])
    # update 10 ns, read 20, conv 30, copy 60
    assert SHARE.read(ctx) == pytest.approx(100 * 120e-9 / 0.5)
    assert "4 ops" in ctx.notes["ssd_state_share"]
    ctx.trace = None
    assert SHARE.read(ctx) is None


def test_roofline_counts_the_calls_inside_the_traced_window(mamba):
    conf, ref = mamba
    lane = ref.state_bytes(conf, 1)
    spans = [
        # wholly inside [9, 10): 4 steps x 2 x 3 lanes
        ("decode/chunk4", 9.2, 0.2, {"steps": 4, "lanes": 3,
                                     "state_bytes": 3 * lane}),
        # half inside: a prefill of 2 lanes writes them once
        ("prefill/bucket256", 8.9, 0.2, {"lanes": 2,
                                         "state_bytes": 2 * lane}),
        # outside, and a span without the arg
        ("decode/chunk8", 5.0, 0.2, {"steps": 8, "lanes": 3,
                                     "state_bytes": 3 * lane}),
        ("decode/chunk8", 9.5, 0.2, {"steps": 8, "lanes": 3}),
    ]
    moved, odd = ROOF.moved_bytes(spans, conf, ref, 9.0, 10.0)
    assert moved == pytest.approx((4 * 2 * 3 + 0.5 * 2) * lane)
    assert odd == 0
    ops = [devtrace.device_op(TEXTS["update"], 0, 1_000_000_000)]
    ctx = _ctx(conf, ref, ops, spans)
    assert ROOF.read(ctx) == pytest.approx(100 * moved / 819e9 / 1.0)
    # a span whose state_bytes is not the reference's count is noted
    spans[0][3]["state_bytes"] += 1
    assert ROOF.moved_bytes(spans, conf, ref, 9.0, 10.0)[1] == 1
    # nothing to read without the arg, or without a state
    ctx.spans = [s for s in spans if "state_bytes" not in s[3]]
    assert ROOF.read(ctx) is None


def test_families_without_state_report_nothing():
    conf = cells.load_json("configs", "yi-6b")
    ref = cells.load_module("refs", conf["ref"])
    ctx = _ctx(conf, ref, _ops(), [])
    assert SHARE.read(ctx) is None and ROOF.read(ctx) is None


def _digest(specs) -> str:
    text = ";".join(f"{s.idx},{s.due!r},{s.prompt_len},{s.out_len}"
                    for s in specs)
    return hashlib.sha256(text.encode()).hexdigest()


# the cell's schedule at its rate over the benchmark's 51 s window
SCHEDULES = {
    1: "dfd7a60c7606763a93c6a1175ccf6dc5e2318878623396e1e6bca8668c826e01",
    2_147_483_659:
        "9c55f6b457d9cdf8b08dfcf15dd9151cf0625b4b8787ab9f9ea486a0b1892a93",
}


@pytest.mark.parametrize("seed", sorted(SCHEDULES))
def test_traffic_schedule_is_pinned(seed):
    mix = cells.load_json("traffic", "mamba2_burst_chat")
    assert mix["arrivals"]["process"] == "gamma" and \
        mix["arrivals"]["cv"] == 3.0
    specs = loadgen.open_loop(mix, seed, 51.0)
    assert _digest(specs) == SCHEDULES[seed]


def test_check_passes_the_program_and_fails_the_control():
    conf = cells.load_json("configs", "tiny-mamba2", root=DATA)
    mix = cells.load_json("traffic", "rehearsal_burst", root=DATA)
    ref = cells.load_module("refs", conf["ref"])
    limit = conf["check"]["gap_limit"]
    st = run.Setup(conf, mix, 61, trace=False)
    for seed in (61, 62):
        if seed != 61:
            st.reseed(seed)
        win = run.serve(st.loop(seed, False), mix, seed, 3.0)
        widest, n_req, _ = run.compare(conf, ref, st.params, win, seed,
                                       control=True)
        assert n_req > 0
        assert widest["program"] <= limit < widest["control"], widest
