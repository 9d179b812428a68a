"""Device: share of the traced window (the window's last seconds) in
which no operation ran on the chip, from the profiler trace. Moves
tpot_p90_ms in the chat cells."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
