"""Readings of single layers from a run's spans and its device trace,
shared by the per-layer metric readers in ``bench/metrics/``.

Spans are the program's (``schedule``, ``capacity_solve``,
``device_sweep``) as the window closed them: ``(name, start_s, ms,
depth, attrs)``.  Each reading returns None when the run holds nothing
for it to read.
"""
from __future__ import annotations

import bisect
from typing import Optional

from . import peaks, workcount

#: the op events of the capacity sweep's Pallas kernel in a v5e trace
#: are named by their HLO instruction, ``%rfr_sweep_op.<n> = s32[1,128]
#: custom-call(...)`` with ``custom_call_target="tpu_custom_call"``
#: (``trace_reduce.short_name`` keeps the part before the operands)
SWEEP_KERNEL_PREFIX = "%rfr_sweep_op"


def _named(run, name):
    return [s for s in run.spans if s[0] == name]


def per_fleet_second(run, ms: float) -> Optional[float]:
    return ms / run.fleet_s if run.fleet_s else None


def loop_other_ms(run) -> Optional[float]:
    """Window wall time outside the ``schedule`` spans, per fleet
    second: routing, measurement, accounting of the simulation loop."""
    sched = _named(run, "schedule")
    if not sched or not run.fleet_s:
        return None
    return (run.window_s * 1e3 - sum(s[2] for s in sched)) / run.fleet_s


def sched_self_ms(run) -> Optional[float]:
    """``schedule`` spans less their direct child spans (capacity solves
    and device drains), per fleet second: the scheduler's and the
    autoscaler's own host time."""
    sched = sorted(_named(run, "schedule"), key=lambda s: s[1])
    if not sched or not run.fleet_s:
        return None
    starts = [s[1] for s in sched]
    child_ms = 0.0
    for sp in run.spans:
        i = bisect.bisect_right(starts, sp[1]) - 1
        if i < 0 or sp is sched[i]:
            continue
        parent = sched[i]
        if sp[3] == parent[3] + 1 and \
                sp[1] + sp[2] / 1e3 <= parent[1] + parent[2] / 1e3 + 1e-9:
            child_ms += sp[2]
    return (sum(s[2] for s in sched) - child_ms) / run.fleet_s


def drain_ms(run) -> Optional[float]:
    """Mean ``device_sweep`` span: row assembly, upload, kernel and the
    blocking read-back of one device drain."""
    d = _named(run, "device_sweep")
    return sum(s[2] for s in d) / len(d) if d else None


def lane_fill(run) -> Optional[float]:
    """Share of launched scenario lanes that held an asked scenario."""
    d = _named(run, "device_sweep")
    launched = sum(s[4].get("launches", 0) * s[4]["launch_shape"][0]
                   for s in d)
    if not launched:
        return None
    return 100.0 * sum(s[4].get("scenarios", 0) for s in d) / launched


def kernel_seconds(run) -> float:
    ops = (run.device or {}).get("op_s", {})
    return sum(v for k, v in ops.items()
               if k.startswith(SWEEP_KERNEL_PREFIX) and "custom-call" in k)


def sweep_roofline(run) -> Optional[float]:
    """Least time the asked scenarios need (``workcount``) over the
    sweep kernel's device time in the trace."""
    d = _named(run, "device_sweep")
    k = kernel_seconds(run)
    if not d or k <= 0 or run.forest is None:
        return None
    peak = peaks.peaks(run.device_kind)
    least = sum(workcount.least_seconds(workcount.sweep_work(
        s[4].get("rows", 0), s[4].get("scenarios", 0), run.forest["trees"],
        run.forest["depth"], run.forest["features"]), peak) for s in d)
    return 100.0 * least / k


def device_idle(run) -> Optional[float]:
    """Share of the window in which no operation ran on the device."""
    if run.device is None or run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.window_s)
