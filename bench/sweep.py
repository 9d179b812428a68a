"""Knee sweep of an open-loop cell, on the chip.

    python3 bench/sweep.py --workload yi6b_chat --rates 0.5,1,1.5,2 \
        --seconds 30 --seed 1

One set-up, then the cell's mix at each rate in turn for --seconds, each
followed by its drain. For each rate it prints the offered and served
tokens per second, the latency tails, and the backlog (requests submitted
and still waiting for their first token) averaged over the window's
thirds. The knee is the highest rate at which the backlog does not grow
over the window; the cell runs at about four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402


def backlog_thirds(samples, t0: float, t1: float) -> list[float]:
    """Time-weighted mean backlog over each third of [t0, t1): a sample
    (t, n) holds until the next one."""
    out = []
    for k in range(3):
        lo, hi = t0 + k * (t1 - t0) / 3, t0 + (k + 1) * (t1 - t0) / 3
        area = 0.0
        for (t, n), (t_next, _) in zip(samples, samples[1:] + [(t1, 0)]):
            area += n * max(0.0, min(t_next, hi) - max(t, lo))
        out.append(area / (hi - lo))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC",
                    help="a configuration and mix no cell uses (CPU)")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.cell_for(args.workload, args.rehearse)
    conf = cells.load_json("configs", cell["config"])
    mix = cells.load_json("traffic", cell["traffic"])
    st = run.Setup(conf, mix, args.seed, trace=False)
    for rate in [float(r) for r in args.rates.split(",")]:
        samples = []
        loop = None

        def tick(now):
            if loop is not None:
                samples.append((now, sum(1 for r in loop.inflight
                                         if not r.times)))
        loop = st.loop(args.seed, False, tick=tick)
        specs = loadgen.open_loop(mix, args.seed, args.seconds, rate)
        offered = sum(s.prompt_len + s.out_len for s in specs) / args.seconds
        win = loop.run_open(specs, args.seconds, mix["drain_cap_s"])
        values = run.report_window(win)
        thirds = backlog_thirds(samples, win.t0, win.t_close)
        print(json.dumps({"workload": cell["name"], "rate": rate,
                          "requests": len(specs), "offered_tok_s": offered,
                          **values, "backlog_thirds": thirds,
                          "drain_s": win.drain_s,
                          "failed": sum(not r.done for r in win.records)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
