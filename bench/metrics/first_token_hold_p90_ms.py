"""Engine loop: 90th percentile over the requests due in the window of how
long a first token is held in the engine after its prefill call has synced
it: the end of the `step` span that holds the request's prefill span minus
that prefill span's end (the engine hands tokens back only when step()
returns, after the same step's decode chunk). A request never prefilled
counts as infinite. Moves ttft_p90_ms."""

import math

import spantree
from stats import percentile


def read(ctx):
    tree = spantree.index(ctx.spans)
    held = {}
    for span in tree.values():
        name, t, dur, args = span
        if name.startswith("prefill/"):
            _, t_step, d_step, _ = spantree.root(tree, span)
            for rid in args["rids"]:
                held.setdefault(rid, t_step + d_step - (t + dur))
    if not held:
        return None
    holds = [held.get(r.req.rid, math.inf) for r in ctx.window.records
             if r.due is not None]
    if not holds:
        return None
    v = percentile(holds, 90)
    return None if math.isinf(v) else v * 1e3
