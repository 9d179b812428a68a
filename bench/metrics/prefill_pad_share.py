"""Engine loop: share of the lanes the window's prefill calls computed
that carried no request, 100 x sum(width - lanes) / sum(width) over the
window's prefill/* spans. Withheld where the spans carry no `width` (a
program that does not say how many lanes its prefill calls compute).
Moves ttft_p90_ms."""

import spantree


def read(ctx):
    width = pad = 0
    for name, t, dur, args in ctx.spans:
        if not (name.startswith("prefill/")
                and spantree.in_window(ctx.window, t, dur)):
            continue
        if "width" not in args:
            return None
        width += args["width"]
        pad += args["width"] - args["lanes"]
    return 100.0 * pad / width if width else None
