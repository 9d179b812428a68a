"""Kernels: the recurrent state's traffic as a share of its roofline.

The least time is the state the traced window's device calls must move,
at its unpadded shapes, over the chip's HBM peak: a decode chunk reads and
writes the state of each lane it serves once a step (steps x 2 x bytes),
a prefill writes the state of the lanes it admits once. The bytes of a
call's lanes are the reference's count (refs/<family>.py state_bytes);
the calls are the engine's `prefill/*` and `decode/*` spans that carry a
`state_bytes` arg, each weighted by the part of it inside the traced
window (which ends when the window closes). The time is the device time
of the ops that take the stacked state (ssd_state_share's ops). Those
ops move at least those bytes, so the share stays at or under 100%. A
family without recurrent state, or a program whose spans carry no
`state_bytes`, reports nothing. Moves tpot_p90_ms."""

import cells

_SHARE = cells.load_module("metrics", "ssd_state_share")


def moved_bytes(spans, conf, ref, lo: float, hi: float) -> tuple[float, int]:
    """State bytes the calls in [lo, hi) must move, and how many calls'
    `state_bytes` differ from the reference's count."""
    total, odd = 0.0, 0
    for name, t, d, args in spans:
        if "state_bytes" not in args or d <= 0:
            continue
        frac = max(0.0, min(t + d, hi) - max(t, lo)) / d
        lane = ref.state_bytes(conf, args["lanes"])
        odd += args["state_bytes"] != lane
        if name.startswith("decode/"):
            total += frac * args["steps"] * 2 * lane
        elif name.startswith("prefill/"):
            total += frac * lane
    return total, odd


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peak is None or not hasattr(ctx.ref, "state_bytes"):
        return None
    found = _SHARE.state_ops(t["ops"], ctx.conf, ctx.ref, ctx.dep["slots"])
    dur = sum(o.dur_ns for o in found) / 1e9
    hi = ctx.window.t_close
    moved, odd = moved_bytes(ctx.spans, ctx.conf, ctx.ref,
                             hi - t["window_s"], hi)
    least = moved / ctx.peak["hbm_bytes_s"]
    ctx.notes["ssd_state_roofline"] = (
        f"{moved:.0f} state bytes to move, least time {least:.6f}s, device "
        f"time {dur:.6f}s; spans whose state_bytes differ from the "
        f"reference's count: {odd}")
    if dur <= 0 or moved <= 0:
        return None
    return 100.0 * least / dur
