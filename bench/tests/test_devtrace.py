"""The trace reduction on a small trace recorded on a TPU v5e: a 45 ms
slice of a `--trace 1` run of yi6b_chat (yi-6b, 8 slots) around the start
of a prefill, with the decode chunk before it (data/, op texts as the
profiler names them; the longest are cut, keeping their start and kind)."""

import gzip
import json
from pathlib import Path

import pytest

import cells
import devtrace

DATA = Path(__file__).parent / "data" / "yi6b_trace_slice.json.gz"


@pytest.fixture(scope="module")
def slice_():
    d = json.loads(gzip.decompress(DATA.read_bytes()))
    ops = sorted((devtrace.device_op(h, s, dur) for h, s, dur in d["ops"]),
                 key=lambda o: o.start_ns)
    host = [devtrace.HostSpan(*h) for h in d["host"]]
    return devtrace.Trace({d["plane"]: ops}, host, d["mark_ns"])


def test_busy_and_idle_partition_the_window(slice_):
    ops = slice_.ops["/device:TPU:0"]
    lo = min(o.start_ns for o in ops)
    hi = max(o.start_ns + o.dur_ns for o in ops)
    busy = devtrace.busy_ns(ops, lo, hi)
    idle = sum(e - s for s, e in devtrace.idle_gaps(ops, lo, hi))
    assert busy + idle == hi - lo
    # a layer scan's while loop holds its body's ops: counted once
    assert 0 < busy < sum(o.dur_ns for o in ops)
    assert busy / (hi - lo) > 0.9


def test_merge_and_gaps_by_hand():
    assert devtrace.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]
    ops = [devtrace.device_op("%a.1 = f32[2]{0} add(f32[2]{0} %x)", s, d)
           for s, d in ((10, 5), (12, 2), (30, 10))]
    assert devtrace.busy_ns(ops, 0, 50) == 15
    assert devtrace.idle_gaps(ops, 0, 50) == [(15, 30), (0, 10), (40, 50)]


def test_ops_are_named_and_containers_left_out(slice_):
    ops = slice_.ops["/device:TPU:0"]
    assert any(devtrace.is_container(o) for o in ops)
    top = devtrace.top_ops(ops)
    assert len(top) == 10
    assert all("contains other ops" not in n for n, _ in top)
    assert top == sorted(top, key=lambda x: -x[1])
    names = {o.name for o in ops}
    assert "systolic_gemm [8,4096]x[4096,11008]" in names


def test_pod_gemm_calls_map_to_the_model(slice_):
    conf = cells.load_json("configs", "yi-6b")
    ref = cells.load_module("refs", conf["ref"])
    roof = cells.load_module("metrics", "pod_gemm_roofline")
    ops = slice_.ops["/device:TPU:0"]
    calls = [o for o in ops if devtrace.is_pod_gemm(o)]
    slots = conf["deployment"]["slots"]
    cands_m = [slots] + [slots * b for b in (16, 32, 64, 128, 256, 512)]
    shapes = [roof.model_shape(devtrace.gemm_operands(o),
                               ref.gemm_shapes(conf), cands_m)
              for o in calls]
    # every projection of both the decode chunk (M = 8 lanes) and the
    # prefill at bucket 512 (M = 8 x 512) runs on the pod GEMM
    assert {(k, n) for _, k, n in shapes} == set(ref.gemm_shapes(conf))
    assert {m for m, _, _ in shapes} == {8, 4096}

    class Ctx:
        pass
    ctx = Ctx()
    ctx.trace = {"ops": ops}
    ctx.peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    ctx.ref, ctx.conf, ctx.dep = ref, conf, conf["deployment"]
    ctx.buckets, ctx.notes = [16, 32, 64, 128, 256, 512], {}
    share = roof.read(ctx)
    assert 0 < share <= 100
    assert "by bytes" in ctx.notes["pod_gemm_roofline"]
    assert "unmapped 0 " in ctx.notes["pod_gemm_roofline"]


def test_unmapped_pod_gemm_time_withholds_the_roofline(slice_):
    """A call at a shape the model never asks for (a projection of 3000
    columns) is counted, and the share is not reported."""
    conf = cells.load_json("configs", "yi-6b")
    roof = cells.load_module("metrics", "pod_gemm_roofline")
    ops = list(slice_.ops["/device:TPU:0"])
    call = next(o for o in ops if devtrace.is_pod_gemm(o))
    odd = call.hlo.replace("11008", "3000").replace("4096]", "3000]")
    ops.append(devtrace.device_op(odd, call.start_ns, call.dur_ns))
    assert roof.model_shape(devtrace.gemm_operands(ops[-1]),
                            cells.load_module("refs", "dense")
                            .gemm_shapes(conf), [8, 4096]) is None

    class Ctx:
        pass
    ctx = Ctx()
    ctx.trace = {"ops": ops}
    ctx.peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    ctx.ref, ctx.conf, ctx.dep = cells.load_module("refs", "dense"), conf, \
        conf["deployment"]
    ctx.buckets, ctx.notes = [16, 32, 64, 128, 256, 512], {}
    assert roof.read(ctx) is None
    assert "unmapped 1 " in ctx.notes["pod_gemm_roofline"]


def test_summary_names_idle_gaps_by_host_activity(slice_):
    ops = slice_.ops["/device:TPU:0"]
    lo = min(o.start_ns for o in ops)
    hi = max(o.start_ns + o.dur_ns for o in ops)
    s = devtrace.summarize(slice_, lo, hi, [])
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    gaps = s["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(name == "engine.step" for name, _ in gaps)
    assert len(s["breakdown"]["device_ops"]) <= 10
