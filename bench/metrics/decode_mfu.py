"""Model step: the decode chunks' share of the chip's bf16 peak. FLOPs
that the decode-produced tokens returned in the window need (the model's
count, refs/<family>.py: layers at the token's context plus the LM head),
over the window's decode span time times the peak. Padded lanes and
positions do not count. Moves tpot_p90_ms."""


def read(ctx):
    if ctx.peak is None:
        return None
    w, conf, ref = ctx.window, ctx.conf, ctx.ref
    dur = sum(d for name, t, d, _ in ctx.spans
              if name.startswith("decode/") and w.t0 <= t
              and t + d <= w.t_close)
    if dur <= 0:
        return None
    head = ref.head_flops(conf)
    flops = 0.0
    for r in w.records:
        plen = len(r.req.prompt)
        for j, t in enumerate(r.times):
            if j >= 1 and w.t0 <= t <= w.t_close:
                flops += ref.token_flops(conf, plen + j) + head
    return 100.0 * flops / (dur * ctx.peak["bf16_flops"])
