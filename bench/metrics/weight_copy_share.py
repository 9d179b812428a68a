"""Kernels: share of the device's busy time spent reading stacked layer
weights outside the pod GEMM, which is copying them.

The model keeps each projection's weights of all layers in one stacked
array. A layer scan that hands the pod GEMM a layer's weight as an array
of its own makes XLA copy that layer out of the stack first (a
dynamic-slice fusion), because a Pallas call cannot fuse the slice into
its operand. This metric sums the device time of every op that takes a
stacked projection weight as an operand, other than the pod GEMM calls
and ops that only contain others (a scan's while loop): an operand whose
leading dimension is the model's layer count and whose elements number
layers x K x N for a projection (K, N) of the model (refs/<family>.py
gemm_shapes). It is divided by the traced window's busy time. Moves
tpot_p90_ms."""

import re

import devtrace

_KIND = re.compile(r"[\]\})] ([a-z][a-z\-]*)\(")
_DIMS = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


def operand_dims(hlo: str) -> list[tuple[int, ...]]:
    """Dims of the array operands in an op's HLO text (after its kind)."""
    k = _KIND.search(hlo)
    if k is None:
        return []
    return [tuple(int(x) for x in m.group(1).split(",") if x)
            for m in _DIMS.finditer(hlo, k.end())]


def stacked(dims: tuple[int, ...], layers: int, sizes: set[int]) -> bool:
    n = 1
    for d in dims:
        n *= d
    return len(dims) >= 3 and dims[0] == layers and n in sizes


def copies(ops, conf, ref):
    """The ops of a trace that read a stacked projection weight outside
    the pod GEMM."""
    layers = conf["num_hidden_layers"]
    sizes = {layers * k * n for k, n in ref.gemm_shapes(conf)}
    return [o for o in ops
            if not devtrace.is_container(o) and not devtrace.is_pod_gemm(o)
            and any(stacked(d, layers, sizes) for d in operand_dims(o.hlo))]


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] <= 0:
        return None
    found = copies(t["ops"], ctx.conf, ctx.ref)
    dur = sum(o.dur_ns for o in found) / 1e9
    ctx.notes["weight_copy_share"] = (
        f"{len(found)} ops read a stacked weight outside the pod GEMM, "
        f"{dur:.6f}s of {t['busy_s']:.6f}s busy")
    return 100.0 * dur / t["busy_s"]
