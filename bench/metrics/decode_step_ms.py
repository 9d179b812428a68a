"""Engine loop: wall time per decode step, the sum of the window's
decode/chunk{n} spans over the sum of their n. Moves tpot_p90_ms."""


def read(ctx):
    w = ctx.window
    dur = steps = 0
    for name, t, d, args in ctx.spans:
        if name.startswith("decode/") and w.t0 <= t and t + d <= w.t_close:
            dur += d
            steps += args["steps"]
    return dur / steps * 1e3 if steps else None
