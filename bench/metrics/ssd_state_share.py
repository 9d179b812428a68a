"""Kernels: share of the device's busy time spent in the ops that read or
write the stacked recurrent state of a state-space model.

The engine keeps every lane's SSD state and conv window of all layers in
two stacked arrays (refs/<family>.py state_shapes: [layers, slots, ...]).
A decode step reads and writes each layer's slice of both, and a prefill
writes the lanes it admits. This metric sums the device time of every op
that takes one of those stacks as an operand (an array whose leading
dimension is the layer count and whose elements number those of a state
shape), other than pod GEMM calls and ops that only contain others (a
scan's while loop), and divides it by the traced window's busy time. A
family without recurrent state (no `state_shapes` in its reference)
reports nothing. Moves tpot_p90_ms."""

import cells
import devtrace

_COPY = cells.load_module("metrics", "weight_copy_share")


def state_ops(ops, conf, ref, slots: int):
    """The ops of a trace that take a stack of the recurrent state."""
    shapes = ref.state_shapes(conf, slots)
    layers = shapes[0][0]
    sizes = set()
    for s in shapes:
        n = 1
        for d in s:
            n *= d
        sizes.add(n)
    return [o for o in ops
            if not devtrace.is_container(o) and not devtrace.is_pod_gemm(o)
            and any(_COPY.stacked(d, layers, sizes)
                    for d in _COPY.operand_dims(o.hlo))]


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] <= 0 or not hasattr(ctx.ref, "state_shapes"):
        return None
    found = state_ops(t["ops"], ctx.conf, ctx.ref, ctx.dep["slots"])
    dur = sum(o.dur_ns for o in found) / 1e9
    ctx.notes["ssd_state_share"] = (
        f"{len(found)} ops take the stacked recurrent state, {dur:.6f}s of "
        f"{t['busy_s']:.6f}s busy")
    return 100.0 * dur / t["busy_s"]
