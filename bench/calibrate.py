"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload yi6b_chat --seeds 101-112 \
        --control-seeds 101-103 --seconds 10

One set-up, then for each seed: the seed's weights, a short window of the
cell's own traffic at its own load, the drain, and the widest gap of the
sampled served tokens against the float32 reference (the program's
reading, bench/check.py). On the control seeds, also the widest gap of
the tokens that the float8 (e4m3) reference puts first at the same
positions (the control's reading). One JSON line per seed, each reading
judged against the configuration's `gap_limit` as a run judges it
(`correct` is gap <= limit): the program should read true, the control
false. The limit goes between the largest program reading and the
smallest control reading. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import run  # noqa: E402


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part[1:]:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC",
                    help="a configuration and mix no cell uses (CPU)")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.cell_for(args.workload, args.rehearse)
    conf = cells.load_json("configs", cell["config"])
    mix = cells.load_json("traffic", cell["traffic"])
    ref = cells.load_module("refs", conf["ref"])
    seeds = seed_list(args.seeds)
    controls = set(seed_list(args.control_seeds))
    st = None
    rows = []
    for seed in seeds:
        t = time.perf_counter()
        if st is None:
            st = run.Setup(conf, mix, seed, trace=False)
        else:
            st.reseed(seed)
        win = run.serve(st.loop(seed, False), mix, seed, args.seconds)
        run.report_window(win)
        widest, n_req, n_tok = run.compare(conf, ref, st.params, win, seed,
                                           control=seed in controls)
        limit = conf["check"]["gap_limit"]
        judged = {f"correct_{k}": n_req > 0 and v <= limit
                  for k, v in widest.items()}
        row = {"workload": cell["name"], "seed": seed, **widest, **judged,
               "gap_limit": limit,
               "requests": n_req, "tokens": n_tok,
               "attempted": len(win.records),
               "failed": sum(not r.done for r in win.records),
               "platform": jax.devices()[0].platform,
               "seconds": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if "control" in r]
    print(json.dumps({"workload": cell["name"], "lower": max(prog),
                      "upper": min(ctrl) if ctrl else None,
                      "seeds": len(prog), "control_seeds": len(ctrl),
                      "program_correct": sum(r["correct_program"]
                                             for r in rows),
                      "control_correct": sum(r["correct_control"]
                                             for r in rows
                                             if "control" in r)}),
          flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
