"""Reduction of a profiler trace (the .xplane.pb that jax.profiler writes)
to what the per-layer metrics read.

- Device ops: the events of the "XLA Ops" line of each device plane
  (/device:TPU:n). Busy time is the union of their intervals, per chip,
  averaged over the chips used.
- Pod GEMM calls: an op's name on the chip is its HLO instruction text.
  The three pallas_calls of kernels/systolic_gemm/systolic_gemm.py show
  up as `tpu_custom_call`s named after the jitted wrappers that launch
  them (POD_GEMM_OPS: systolic_gemm, systolic_gemm_t, grouped_gemm), and
  the shapes of their result and operands are read from that text.
- Host activity: the benchmark's own TraceAnnotations on the host plane,
  and the engine's spans, moved onto the trace's clock by a marker
  annotation whose host-clock time is known. An idle gap on the device is
  named by the innermost host activity that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

# The names the pod GEMM's pallas_calls carry in a TPU trace (seen in a
# trace of yi-6b on a v5e: "%systolic_gemm.75 = bf16[8,11008]{...}
# custom-call(bf16[8,4096]{...} %fusion.81, bf16[4096,11008]{...} ...),
# custom_call_target="tpu_custom_call"").
POD_GEMM_OPS = ("systolic_gemm", "systolic_gemm_t", "grouped_gemm")
_POD = re.compile(r"^%(" + "|".join(POD_GEMM_OPS) + r")\.\d+ = ")
_NAME = re.compile(r"^%([A-Za-z0-9_\-]+?)(?:\.\d+)* = ")
_KIND = re.compile(r"[\]\})] ([a-z][a-z\-]*)\(")
CONTAINERS = ("while", "conditional", "call")
MARK = "bench.clock_mark"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 1000      # shorter idle gaps count as idle but go unnamed

_SHAPE = re.compile(r"(bf16|f32|f16|s8|s32|u32|s16|u8|f8e4m3fn|f8e5m2|pred)"
                    r"\[([0-9,]*)\]")


@dataclasses.dataclass
class DeviceOp:
    name: str
    start_ns: int
    dur_ns: int
    hlo: str          # the op's HLO instruction text (its name on a TPU)


@dataclasses.dataclass
class HostSpan:
    name: str
    start_ns: int
    dur_ns: int


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[DeviceOp]]      # per device plane, sorted by start
    host: list[HostSpan]                 # benchmark annotations
    mark_ns: int | None                  # trace-clock time of the marker


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(str(files[-1]))
    ops: dict[str, list[DeviceOp]] = {}
    host: list[HostSpan] = []
    mark = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = [device_op(e.name, int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
                evs.sort(key=lambda e: e.start_ns)
                ops.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = int(e.start_ns)
                    elif e.name.startswith(("bench.", "engine.")):
                        host.append(HostSpan(e.name, int(e.start_ns),
                                             int(e.duration_ns)))
    return Trace(ops, host, mark)


def device_op(hlo: str, start_ns: int, dur_ns: int) -> DeviceOp:
    return DeviceOp(_label(hlo), start_ns, dur_ns, hlo)


def merge(intervals) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: list[DeviceOp], lo: int, hi: int) -> int:
    """Nanoseconds in [lo, hi) in which some op ran."""
    tot = 0
    for s, e in merge((o.start_ns, o.start_ns + o.dur_ns) for o in ops):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            tot += e - s
    return tot


def idle_gaps(ops: list[DeviceOp], lo: int, hi: int
              ) -> list[tuple[int, int]]:
    """Intervals in [lo, hi) with no op running, longest first."""
    gaps, t = [], lo
    for s, e in merge((o.start_ns, o.start_ns + o.dur_ns) for o in ops):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    gaps = [(s, e) for s, e in gaps if e > s]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def name_at(spans: list[HostSpan], t: int) -> str:
    """The innermost (shortest) host span covering t, or 'no host span'."""
    best = None
    for s in spans:
        if s.start_ns <= t < s.start_ns + s.dur_ns and \
                (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best else "no host span"


def operand_shapes(hlo: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of every array shape in an op's HLO text, in order:
    the result first, then the operands."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(hlo)]


def _label(hlo: str) -> str:
    """A short name for an op: its instruction name without the number,
    and for a pod GEMM its operand shapes, for anything else its result
    shape ("systolic_gemm [8,4096]x[4096,11008]", "fusion [8,4096]")."""
    m = _NAME.match(hlo)
    name = m.group(1) if m else hlo[:80]
    k = _KIND.search(hlo)
    if k and k.group(1) in CONTAINERS:
        kind = k.group(1)
        return f"{kind} (contains other ops)"
    if _POD.match(hlo):
        dims = [d for _, d in operand_shapes(hlo) if len(d) == 2]
        if len(dims) >= 3:
            x, w = dims[1], dims[2]
            return (f"{name} [{x[0]},{x[1]}]x[{w[0]},{w[1]}]")
    res = operand_shapes(hlo)
    return f"{name} [{','.join(map(str, res[0][1]))}]" if res else name


def is_container(op: DeviceOp) -> bool:
    return op.name.endswith("(contains other ops)")


def is_pod_gemm(op: DeviceOp) -> bool:
    return bool(_POD.match(op.hlo)) and "tpu_custom_call" in op.hlo


def summarize(t: Trace, lo: int, hi: int, engine_spans) -> dict:
    """Busy and traced seconds, the window's ops, and the breakdown the
    result line carries. `engine_spans` are (name, start_ns, dur_ns) on the
    trace's clock; they and the benchmark's annotations name idle gaps."""
    per_chip = [busy_ns(ops, lo, hi) for ops in t.ops.values()]
    ops0 = [o for o in next(iter(t.ops.values())) if lo <= o.start_ns < hi]
    host = list(t.host) + [HostSpan(*s) for s in engine_spans]
    gaps = [g for g in idle_gaps(ops0, lo, hi) if g[1] - g[0] >= MIN_GAP_NS]
    by_host: dict[str, float] = {}
    for s, e in gaps:
        k = name_at(host, (s + e) // 2)
        by_host[k] = by_host.get(k, 0.0) + (e - s) / 1e9
    return {
        "busy_s": sum(per_chip) / len(per_chip) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "ops": ops0,
        "idle_by_host": by_host,
        "breakdown": {
            "device_ops": top_ops(ops0),
            "idle_gaps": [[name_at(host, (s + e) // 2), (e - s) / 1e9]
                          for s, e in gaps[:10]],
        },
    }


def top_ops(ops: list[DeviceOp], k: int = 10) -> list[list]:
    """The k op names that took most device time (ops that only contain
    others, such as a layer scan's while loop, left out)."""
    tot: dict[str, int] = {}
    for o in ops:
        if not is_container(o):
            tot[o.name] = tot.get(o.name, 0) + o.dur_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]


def gemm_operands(op: DeviceOp):
    """((Mp, Kp), (Kp, Np), transposed) of a pod GEMM call, from the
    result [Mp, Np] and first operand x [Mp, Kp] in its HLO text."""
    shapes = [d for _, d in operand_shapes(op.hlo) if len(d) == 2]
    if len(shapes) < 2:
        return None
    (mp, np_), (mx, kp) = shapes[0], shapes[1]
    if mx != mp:
        return None
    transposed = op.hlo.startswith("%systolic_gemm_t.")
    return (mp, kp), (kp, np_), transposed
