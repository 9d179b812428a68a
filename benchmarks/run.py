"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only granularity,...]
                                            [--json BENCH_foo.json]
                                            [--check]

The ``dse`` suite emits a ``dse/engine_speedup`` row comparing the batched
analytical engine (core.dse.sweep -> simulator.analyze_batch) against the
original scalar loop (core.dse.sweep_scalar) on the Fig-5 mixed grid; the
``serving`` suite compares the bucketed + fused ServeEngine hot loop
against the seed per-token engine (compile counts, tokens/s, p50/p99);
the ``obs`` suite reports the serving telemetry layer (effective-TOPS,
predicted-vs-measured drift, trace-export timing — src/repro/obs/).

``--json`` additionally writes the rows as a machine-readable
``BENCH_*.json`` (schema ``sosa-bench-v1``) so the perf trajectory is
recorded across PRs.

``--check`` is the CI smoke mode (part of the documented fast gate): it
runs every suite at tiny shapes (suites read ``benchmarks._check.
check_mode()``), then asserts that each selected suite emitted its
``_total`` row and no ``ERROR`` rows, exiting non-zero otherwise.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SCHEMA = "sosa-bench-v1"
ROW_FIELDS = ("suite", "name", "us_per_call", "derived")


def parse_row(line: str) -> dict:
    """One CSV row -> record. `derived` may itself contain ';'-separated
    key=value pairs; it is kept verbatim (strings stay greppable, commas
    included) and the row is split on the first two commas only."""
    name, us, derived = line.split(",", 2)
    suite = name.split("/", 1)[0]
    try:
        us_val = float(us)
    except ValueError:
        us_val = 0.0
    return {"suite": suite, "name": name, "us_per_call": us_val,
            "derived": derived}


def error_row(suite: str, exc: BaseException) -> str:
    """The ``SUITE/ERROR`` row: exception type and message as greppable
    ``derived`` key=value pairs (newlines flattened; commas survive —
    parse_row keeps everything past the second comma verbatim)."""
    msg = " ".join(str(exc).split()) or "<no message>"
    return (f"{suite}/ERROR,0,"
            f"error_type={type(exc).__name__};error_msg={msg}")


def validate_doc(doc: dict) -> list[str]:
    """Validate a BENCH_*.json document against the sosa-bench-v1 schema
    (BENCH.md); returns a list of problems (empty = valid)."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    if doc.get("schema") != SCHEMA:
        problems.append(f"schema is {doc.get('schema')!r}, want {SCHEMA!r}")
    if not isinstance(doc.get("created_unix"), (int, float)) \
            or doc.get("created_unix", 0) <= 0:
        problems.append("created_unix missing or not a positive number")
    if not isinstance(doc.get("argv"), list) \
            or not all(isinstance(a, str) for a in doc.get("argv", [])):
        problems.append("argv missing or not a list of strings")
    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        return problems + ["rows missing or empty"]
    for i, row in enumerate(rows):
        if not isinstance(row, dict) or set(ROW_FIELDS) - set(row):
            problems.append(f"rows[{i}]: missing fields "
                            f"{sorted(set(ROW_FIELDS) - set(row or {}))}")
            continue
        if not isinstance(row["name"], str) \
                or row["name"].split("/", 1)[0] != row["suite"]:
            problems.append(
                f"rows[{i}]: name {row.get('name')!r} does not start with "
                f"suite {row.get('suite')!r}")
        if not isinstance(row["us_per_call"], (int, float)) \
                or row["us_per_call"] < 0:
            problems.append(f"rows[{i}]: us_per_call not a number >= 0")
        if not isinstance(row["derived"], str):
            problems.append(f"rows[{i}]: derived not a string")
    suites = {r["suite"] for r in rows if isinstance(r, dict)
              and isinstance(r.get("suite"), str)}
    for s in sorted(suites):
        if not any(isinstance(r, dict) and r.get("name") == f"{s}/_total"
                   for r in rows):
            problems.append(f"suite {s!r} has no _total row")
    return problems


def check_rows(rows: list[dict], expected_suites: list[str]) -> list[str]:
    """The --check assertions: every selected suite emitted its ``_total``
    row and no suite emitted an ``ERROR`` row. Returns problems."""
    problems: list[str] = []
    names = {r["name"] for r in rows}
    for s in expected_suites:
        if f"{s}/_total" not in names:
            problems.append(f"suite {s!r} emitted no _total row")
    for r in rows:
        if r["name"].endswith("/ERROR"):
            problems.append(f"{r['suite']}: ERROR row — {r['derived']}")
    return problems


def write_json(rows: list[dict], path: str) -> None:
    """BENCH_*.json schema: header + the parsed rows."""
    doc = {
        "schema": SCHEMA,
        "created_unix": time.time(),
        "argv": sys.argv[1:],
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def load_suites() -> dict:
    """The suite registry, resolved at call time (suites consult
    ``benchmarks._check.check_mode()`` at import, so --check must set the
    env var first). A separate hook so the --check regression test can
    substitute a failing suite and assert the nonzero exit."""
    from benchmarks import (dse_map, granularity, interconnect, kernels_bench,
                            memory_sweep, multitenancy, obs, scaling, serving,
                            tenancy, tiling_sweep)
    return {
        "granularity": granularity.bench,       # Table 2 + Fig 9
        "interconnect": interconnect.bench,     # Table 1 + Fig 12a
        "tiling": tiling_sweep.bench,           # Fig 12b
        "dse": dse_map.bench,                   # Fig 5
        "multitenancy": multitenancy.bench,     # Fig 11
        "tenancy": tenancy.bench,               # tenant-mix DSE (repro.tenancy)
        "memory": memory_sweep.bench,           # Fig 13
        "scaling": scaling.bench,               # Fig 10
        "kernels": kernels_bench.bench,         # §4.1 pod microarchitecture
        "serving": serving.bench,               # hot-loop engine vs seed
        "obs": obs.bench,                       # telemetry: eff-TOPS, drift
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as a BENCH_*.json record")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke mode: tiny shapes, assert every suite "
                         "emits _total and no ERROR rows (exit 1 on "
                         "failure)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.check:
        # suites consult benchmarks._check.check_mode(); set before import
        import os
        os.environ["SOSA_BENCH_CHECK"] = "1"

    suites = load_suites()
    only = set(args.only.split(",")) if args.only else None
    selected = [n for n in suites if not only or n in only]
    rows: list[dict] = []
    print("name,us_per_call,derived")
    for name in selected:
        fn = suites[name]
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
                rows.append(parse_row(line))
        except Exception as e:  # noqa: BLE001
            err = error_row(name, e)
            print(err, flush=True)
            rows.append(parse_row(err))
        total = f"{name}/_total,{(time.time() - t0) * 1e6:.0f},done"
        print(total, flush=True)
        rows.append(parse_row(total))
    if args.json:
        write_json(rows, args.json)
    if args.check:
        problems = check_rows(rows, selected)
        for p in problems:
            print(f"CHECK FAIL: {p}", file=sys.stderr)
        print(f"--check: {len(selected)} suites, "
              f"{'FAIL' if problems else 'OK'}", file=sys.stderr)
        if problems:
            sys.exit(1)


if __name__ == "__main__":
    main()
