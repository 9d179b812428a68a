"""Admission: 90th percentile over the requests due in the window of the
time from the due time to the start of the prefill call that carries the
request (the engine's prefill/* span lists its rids). A request never
prefilled counts as infinite. Moves ttft_p90_ms."""

import math

from stats import percentile


def read(ctx):
    start = {}
    for name, t, dur, args in ctx.spans:
        if name.startswith("prefill/"):
            for rid in args.get("rids", ()):
                start.setdefault(rid, t)
    waits = [start[r.req.rid] - r.due if r.req.rid in start else math.inf
             for r in ctx.window.records if r.due is not None]
    if not waits:
        return None
    v = percentile(waits, 90)
    return None if math.isinf(v) else v * 1e3
