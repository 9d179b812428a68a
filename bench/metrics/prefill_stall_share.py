"""Engine loop: share of the window's decode-lane time that prefill calls
stall. Every prefill call holds up each decode lane live when it starts
(its span's `stalled_lanes`): the sum over the window's prefill spans of
duration x stalled lanes, over that plus the sum over its decode spans of
duration x lanes. Moves tpot_p90_ms."""

import spantree


def read(ctx):
    if not spantree.index(ctx.spans):
        return None
    stalled = decoding = 0.0
    for name, t, dur, args in ctx.spans:
        if not spantree.in_window(ctx.window, t, dur):
            continue
        if name.startswith("prefill/"):
            stalled += dur * args["stalled_lanes"]
        elif name.startswith("decode/"):
            decoding += dur * args["lanes"]
    total = stalled + decoding
    return 100.0 * stalled / total if total > 0 else None
