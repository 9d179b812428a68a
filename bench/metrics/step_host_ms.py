"""Engine loop (host): mean host time of an engine step that ran a decode
chunk, over the window's `step` spans with decode steps: the step's
duration minus that of the prefill/* and decode/* spans under it (the
device calls with their syncs). What remains is admission, packing, lane
preparation and the work on a synced chunk (retire, tracer replay, expiry):
time in which no device call is in flight. Moves tpot_p90_ms."""

import spantree


def read(ctx):
    tree = spantree.index(ctx.spans)
    calls = {}
    for span in tree.values():
        name, _, dur, _ = span
        if name.startswith(spantree.CALLS):
            sid = spantree.root(tree, span)[3]["id"]
            calls[sid] = calls.get(sid, 0.0) + dur
    host = [dur - calls.get(args["id"], 0.0)
            for name, t, dur, args in tree.values()
            if name == "step" and args["decode_steps"] > 0
            and spantree.in_window(ctx.window, t, dur)]
    return sum(host) / len(host) * 1e3 if host else None
