"""Readings of the program's phase spans, shared by the per-layer metric
readers in ``bench/metrics/`` that this file's names follow.

Phase spans are the parts of a layer's work that the program times
inside its layer spans: ``autoscale`` (one autoscaler pass a tick),
``migrate`` and ``reap`` inside it, ``place`` (one scheduling decision),
``measure`` (the tick's measurement pass), ``solve.lookup`` (the
keying and cache lookups of one ``solve_many``) and a device drain's
``drain.assemble``, ``drain.launch`` and ``drain.readback``.  They
reach the run as ``(name, start_s, ms, depth, attrs)`` with ``depth``
None; a span's children are found by time containment.  Each reading
returns None when the run holds none of the spans it reads.
"""
from __future__ import annotations

import bisect
from typing import List, Optional


def _named(run, name: str) -> List[tuple]:
    return [s for s in run.spans if s[0] == name]


def _end(span) -> float:
    return span[1] + span[2] / 1e3


def inside(inner: List[tuple], outer: List[tuple]) -> List[tuple]:
    """The spans of ``inner`` that lie within a span of ``outer`` (whose
    spans do not overlap one another)."""
    outer = sorted(outer, key=lambda s: s[1])
    starts = [s[1] for s in outer]
    out = []
    for sp in inner:
        i = bisect.bisect_right(starts, sp[1]) - 1
        if i >= 0 and _end(sp) <= _end(outer[i]) + 1e-9:
            out.append(sp)
    return out


def _total_per_fleet_second(run, name: str) -> Optional[float]:
    spans = _named(run, name)
    if not spans or not run.fleet_s:
        return None
    return sum(s[2] for s in spans) / run.fleet_s


def _mean_ms(run, name: str) -> Optional[float]:
    spans = _named(run, name)
    return sum(s[2] for s in spans) / len(spans) if spans else None


def autoscale_self_ms(run) -> Optional[float]:
    """``autoscale`` spans less the ``place`` spans (scheduling
    decisions) inside them, per fleet second: scale decisions,
    keep-alive eviction, migration and reaping."""
    auto = _named(run, "autoscale")
    if not auto or not run.fleet_s:
        return None
    placed = inside(_named(run, "place"), auto)
    return (sum(s[2] for s in auto) - sum(s[2] for s in placed)) \
        / run.fleet_s


def migrate_ms(run) -> Optional[float]:
    """``migrate`` spans per fleet second: the search for targets of
    cached instances over every node that holds some."""
    return _total_per_fleet_second(run, "migrate")


def measure_ms(run) -> Optional[float]:
    """``measure`` spans per fleet second: routing the tick's traffic
    and accounting its QoS on every serving node."""
    return _total_per_fleet_second(run, "measure")


def place_drains(run) -> Optional[float]:
    """Mean ``drains`` of a ``place`` span: the device or host drains
    one scheduling decision ran."""
    place = _named(run, "place")
    if not place:
        return None
    return sum(s[4].get("drains", 0) for s in place) / len(place)


def drain_assemble_ms(run) -> Optional[float]:
    """Mean ``drain.assemble``: the tree-sum limits and the padded row
    blocks of one device drain, built on the host."""
    return _mean_ms(run, "drain.assemble")


def drain_wait_ms(run) -> Optional[float]:
    """Mean ``drain.readback``: the blocking read of one drain's
    capacities, which waits for its kernels and the transfer."""
    return _mean_ms(run, "drain.readback")


def lookup_ms(run) -> Optional[float]:
    """Mean ``solve.lookup``: signatures, cache lookups and templates of
    the queries of one ``solve_many``."""
    return _mean_ms(run, "solve.lookup")
