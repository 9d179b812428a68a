"""Engine loop: mean wall time of a prefill call in the window (the
engine's prefill/* spans: host clock around the jitted prefill and the
sync on its first tokens). Moves ttft_p90_ms in the chat cells."""


def read(ctx):
    w = ctx.window
    durs = [d for name, t, d, _ in ctx.spans
            if name.startswith("prefill/") and w.t0 <= t and t + d <= w.t_close]
    return sum(durs) / len(durs) * 1e3 if durs else None
