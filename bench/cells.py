"""Find a cell's parts by name: BENCHMARK.json at the checkout's root
names them, and each lives in a file of its own under bench/:

    configs/<name>.json    a model configuration as it is run
    traffic/<name>.json    a traffic mix (read by loadgen.py)
    metrics/<name>.py      a per-layer metric: read(ctx) -> float | None
    refs/<family>.py       the plain float32 reference of a model family

A later change adds a configuration, a mix or a metric as a new file and
edits none of these.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _path(root: Path, kind: str, name: str, ext: str) -> Path:
    if not NAME.match(name):
        raise LookupError(f"{kind}: {name!r} is not a valid name")
    p = root / kind / f"{name}{ext}"
    if not p.is_file():
        raise LookupError(f"{kind}: no file {p.relative_to(root)} for "
                          f"{name!r}")
    return p


def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    with open(_path(root, kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: Path = BENCH):
    p = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    """The workload entry plus the metrics it reports: end-to-end metrics
    that list it (or list no cells), and per-layer metrics that list it,
    or, listing none, move an end-to-end metric the cell reports."""
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise LookupError(f"workload: no cell named {workload!r} in "
                          f"BENCHMARK.json")
    w = dict(found[0])
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    w["end_to_end"] = e2e
    w["per_layer"] = per_layer
    return w
