"""From the JAX profiler's trace of a window to device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``, read with ``ProfileData``.  Device
planes are named ``/device:TPU:<n>``; the operations that ran on a
chip are the events of their ``XLA Ops`` line.  Host planes carry the
harness's ``bench.*`` annotations (``TraceAnnotation``), which say what
the host was doing while the device sat idle.

  * busy: the union of a device's op intervals; ``busy_s`` is its
    length averaged over the chips used;
  * kernel time: the summed durations of the op events of one name;
  * idle gaps: the stretches between busy intervals, each put down to
    the innermost ``bench.*`` annotation open at its midpoint.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."

Interval = Tuple[float, float]

_HLO = re.compile(
    r"^(%[\w.\-]+) = (?:\((?:[^()]|\([^()]*\))*\)|\S+) ([\w\-]+)\("
    r"([a-z0-9]+\[[\d,]*\])?")


def short_name(name: str) -> str:
    """An op event's HLO text cut to its instruction, opcode and first
    operand's shape: ``%rfr_sweep_op.1 custom-call f32[31,8,32,128]``."""
    m = _HLO.match(name)
    if m is None:
        return name[:120]
    return " ".join(g for g in m.groups() if g)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def labels_at(points: List[float],
              annotations: List[Tuple[float, float, str]]) -> List[str]:
    """For each time in ``points``, the innermost annotation open then
    ("host" where none is).  Annotations nest, as the spans of one
    thread do, so one sweep with a stack finds them all."""
    order = sorted(range(len(points)), key=lambda i: points[i])
    anns = sorted(annotations, key=lambda a: (a[0], -a[1]))
    out = ["host"] * len(points)
    stack: List[Tuple[float, float, str]] = []
    j = 0
    for i in order:
        t = points[i]
        while j < len(anns) and anns[j][0] <= t:
            while stack and stack[-1][1] <= anns[j][0]:
                stack.pop()
            stack.append(anns[j])
            j += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        if stack:
            out[i] = stack[-1][2]
    return out


def collect(planes, chips: int):
    """Op events per device plane, and the host's bench annotations:
    ``({plane: [(name, start_ns, end_ns)]}, [(start, end, name)])``."""
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    annotations: List[Tuple[float, float, str]] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((short_name(ev.name), float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
            devices[plane.name] = evs
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        annotations.append(
                            (float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns), ev.name))
    used = sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):]
                                            .split()[0])
                  if n[len(DEVICE_PREFIX):].split()[0].isdigit() else 1 << 30)
    return {n: devices[n] for n in used[:chips]}, annotations


def reduce(devices: Dict[str, List[Tuple[str, float, float]]],
           annotations: List[Tuple[float, float, str]], window_s: float,
           top: int = 10) -> Dict:
    """Busy time, per-op device time and idle gaps of one traced window.

    The window's span on the trace clock is that of the harness's
    ``bench.*`` annotations where there are any, else that of the ops."""
    all_ops = [ev for evs in devices.values() for ev in evs]
    if annotations:
        lo = min(a[0] for a in annotations)
        hi = max(a[1] for a in annotations)
    elif all_ops:
        lo = min(ev[1] for ev in all_ops)
        hi = max(ev[2] for ev in all_ops)
    else:
        lo = hi = 0.0
    busy_ns, per_op = [], defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    idle_by = defaultdict(float)
    for evs in devices.values():
        merged = union((s, e) for _n, s, e in evs)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            per_op[name] += e - s
            op_count[name] += 1
        idle = gaps(merged, lo, hi)
        names = labels_at([(s + e) / 2 for s, e in idle], annotations)
        for (s, e), name in zip(idle, names):
            idle_by[name] += e - s
    n_dev = max(len(devices), 1)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": window_s,
        "op_s": {k: v / 1e9 for k, v in per_op.items()},
        "op_count": dict(op_count),
        "breakdown": {
            "device_ops": [[k, v / n_dev / 1e9] for k, v in ops[:top]],
            "idle_gaps": [[k, v / n_dev / 1e9] for k, v in idle[:top]],
        },
    }


def reduce_dir(trace_dir: Path, window_s: float, chips: int = 1) -> Dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, annotations = collect(data.planes, chips)
    return reduce(devices, annotations, window_s)
