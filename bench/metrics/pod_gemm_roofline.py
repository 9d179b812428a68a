"""Kernels: the pod GEMM's share of its roofline in the traced window.

For each pod GEMM call in the device trace, the least time the chip could
take is the larger of 2MNK / peak FLOP/s and (MK + KN + MN) * 2 bytes /
peak bytes/s (bf16 operands and result), with M, N and K the shapes the
model asked for, before the wrapper padded them to blocks: the padded
operand shapes read from the trace are mapped back to the model's
projections (refs/<family>.py gemm_shapes) and to the cell's rows (decode
lanes, or lanes times a prefill bucket). The metric is the sum of least
times over the sum of the calls' device durations. Moves tpot_p90_ms.

A call whose padded shape maps to no projection and row count (each
dimension at most PAD_MAX above the model's) is not left out quietly: the
note counts such calls and their device time, and the metric is not
reported, so a change of the program's shapes cannot move it by dropping
calls from both sides of the share."""

import devtrace

BYTES = 2
PAD_MAX = 1024      # the wrapper pads a dimension by less than one block


def _fits(c: int, padded: int) -> bool:
    return c <= padded < c + PAD_MAX


def model_shape(dims, cands_kn, cands_m):
    """(M, K, N) the model asked for, from padded operand dims, or None
    where no projection and row count fit them."""
    (mp, kp), (_, np_), _ = dims
    fits = [(k, n) for k, n in cands_kn if _fits(k, kp) and _fits(n, np_)]
    ms = [m for m in cands_m if _fits(m, mp)]
    if not fits or not ms:
        return None
    k, n = min(fits, key=lambda kn: (kp - kn[0]) + (np_ - kn[1]))
    return max(ms), k, n


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peak is None:
        return None
    cands_kn = ctx.ref.gemm_shapes(ctx.conf)
    slots = ctx.dep["slots"]
    cands_m = [slots] + [slots * b for b in ctx.buckets]
    least = dur = lost = 0.0
    bound = {"flops": 0, "bytes": 0, "unmapped": 0}
    for op in t["ops"]:
        if not devtrace.is_pod_gemm(op):
            continue
        dims = devtrace.gemm_operands(op)
        shape = model_shape(dims, cands_kn, cands_m) if dims else None
        if shape is None:
            bound["unmapped"] += 1
            lost += op.dur_ns / 1e9
            continue
        m, k, n = shape
        tf = 2.0 * m * n * k / ctx.peak["bf16_flops"]
        tb = (m * k + k * n + m * n) * BYTES / ctx.peak["hbm_bytes_s"]
        least += max(tf, tb)
        bound["flops" if tf >= tb else "bytes"] += 1
        dur += op.dur_ns / 1e9
    ctx.notes["pod_gemm_roofline"] = (
        f"calls bound by bytes {bound['bytes']}, by flops {bound['flops']}, "
        f"unmapped {bound['unmapped']} ({lost:.6f}s); kernel time "
        f"{dur:.6f}s, least time {least:.6f}s")
    if bound["unmapped"] or dur <= 0:
        return None
    return 100.0 * least / dur
