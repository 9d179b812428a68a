"""weight_copy_share on the recorded yi6b_chat slice (data/; TPU v5e, the
layer scan copying each layer's weights out of the stacks) and on
hand-made op texts."""

import gzip
import json
from pathlib import Path

import pytest

import cells
import devtrace

DATA = Path(__file__).parent / "data" / "yi6b_trace_slice.json.gz"


@pytest.fixture(scope="module")
def yi():
    conf = cells.load_json("configs", "yi-6b")
    return conf, cells.load_module("refs", conf["ref"])


@pytest.fixture(scope="module")
def metric():
    return cells.load_module("metrics", "weight_copy_share")


@pytest.fixture(scope="module")
def ops():
    d = json.loads(gzip.decompress(DATA.read_bytes()))
    return sorted((devtrace.device_op(h, s, dur) for h, s, dur in d["ops"]),
                  key=lambda o: o.start_ns)


def test_counts_the_slices_of_the_stacks(ops, yi, metric):
    conf, ref = yi
    found = metric.copies(ops, conf, ref)
    names = {o.name for o in found}
    # gate/up, down, q, k/v and o: each layer's slice out of its stack
    assert names == {"dynamic-slice_bitcast_fusion [4096,11008]",
                     "dynamic-slice_bitcast_fusion [11008,4096]",
                     "constant_dynamic-slice_fusion [1,4096,32,128]",
                     "constant_dynamic-slice_fusion [1,4096,4,128]",
                     "constant_dynamic-slice_fusion [1,32,128,4096]"}
    assert len(found) == 96
    assert sum(o.dur_ns for o in found) == 7_713_210


def test_leaves_out_the_prefill_product_of_the_same_size(ops, yi, metric):
    """The prefill's gate x up product [4096, 11008] has as many elements
    as one layer's weight, but no stack among its operands."""
    conf, ref = yi
    mul = [o for o in ops if o.name == "mul [4096,11008]"]
    assert len(mul) == 1
    assert mul[0] not in metric.copies(ops, conf, ref)


def test_leaves_out_pod_gemm_calls_and_loops(yi, metric):
    conf, ref = yi
    stack = "bf16[32,4096,11008]{2,1,0:T(8,128)(2,1)}"
    texts = {
        "gemm": ("%systolic_gemm.3 = bf16[8,11008]{1,0} custom-call(s32[1]{0} "
                 f"%b, bf16[8,4096]{{1,0}} %x, {stack} %w, f32[1,11008]{{1,0}}"
                 ' %s), custom_call_target="tpu_custom_call"'),
        "loop": (f"%while.5 = (s32[], {stack}) while((s32[], {stack}) %t),"
                 " condition=%c, body=%b"),
        "copy": (f"%dynamic-slice_bitcast_fusion.1 = bf16[4096,11008]{{1,0}} "
                 f"fusion({stack} %p, s32[] %i), kind=kLoop"),
        "cache": ("%fusion.2 = bf16[8,1024,4,128]{3,2,1,0} fusion("
                  "bf16[32,8,1024,4,128]{4,3,2,1,0} %k, s32[] %i), "
                  "kind=kLoop"),
    }
    ops = [devtrace.device_op(t, 10 * i, 5) for i, t in enumerate(texts.values())]
    assert [o.hlo for o in metric.copies(ops, conf, ref)] == [texts["copy"]]


def test_read_divides_by_busy_time(ops, yi, metric):
    conf, ref = yi

    class Ctx:
        notes: dict = {}
    ctx = Ctx()
    ctx.conf, ctx.ref = conf, ref
    ctx.trace = {"ops": ops, "busy_s": 0.040}
    assert metric.read(ctx) == pytest.approx(100 * 0.00771321 / 0.040)
    assert "96 ops" in ctx.notes["weight_copy_share"]
    ctx.trace = None
    assert metric.read(ctx) is None
