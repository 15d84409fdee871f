"""Span-based control-plane tracing.

The simulator, the autoscaler and the prediction service wrap their
interesting sections in spans::

    with tracer.span("schedule", now=now) as sp:
        ...
        sp.attrs["decisions"] = placed

A closed span records wall-clock duration, a sequence number, the
sequence number of the span it opened inside (``parent``; None at the
top), and arbitrary attributes (counters the span site sets itself,
sim time).  Spans are emitted through the observer hub's ``on_span``
hook as they close, so ``JsonlObserver`` persists them into the same
JSONL stream as the ``DecisionTrace`` records — one artifact per run
tells the whole story.

Two kinds of span:

* **layer spans** (``tracer.span``): ``schedule``, ``admission``,
  ``capacity_solve``, ``device_sweep``, ``retrain`` — one per layer
  entered.  Their ``depth`` counts the layer spans open around them.
* **phase spans** (``tracer.phase``): the parts of a layer's work
  (``autoscale``, ``migrate``, ``reap``, ``place``, ``measure``,
  ``solve.lookup``, ``drain.assemble``, ``drain.launch``,
  ``drain.readback``).  They record no depth (None), so a reader that
  finds a layer's children by depth never counts them; ``parent`` says
  where they ran.

A ``SpanTracer`` also opens a ``jax.profiler.TraceAnnotation`` named
``ANNOTATION_PREFIX + name`` around every span, so that in a profiled
run the program's spans sit on the profiler's host plane, on the same
clock as the device's operations.

``NULL_TRACER`` is the default everywhere: its ``span()`` and
``phase()`` return one shared no-op context manager whose ``__enter__``
returns ``None``, so uninstrumented runs pay two attribute lookups per
span site, allocate nothing and never touch the profiler (the
observer-parity gates run with and without a real tracer and must
agree bit-for-bit — spans only *read* state).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

#: prefix of the profiler annotation each span opens (``cp.schedule``,
#: ``cp.drain.readback``)
ANNOTATION_PREFIX = "cp."


class Span:
    """One closed (or in-flight) control-plane section."""

    __slots__ = ("name", "seq", "depth", "parent", "t_start_s", "dur_ms",
                 "attrs")

    def __init__(self, name: str, seq: int, depth: Optional[int],
                 parent: Optional[int] = None, **attrs: Any):
        self.name = name
        self.seq = seq
        self.depth = depth
        self.parent = parent
        self.t_start_s = 0.0
        self.dur_ms = 0.0
        self.attrs: Dict[str, Any] = dict(attrs)

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "seq": self.seq, "depth": self.depth,
                "parent": self.parent, "ms": round(self.dur_ms, 4),
                **self.attrs}

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, seq={self.seq}, "
                f"ms={self.dur_ms:.3f}, {self.attrs})")


class _NullSpanCM:
    """Shared no-op ``span()`` result: enters to None, records nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullSpanCM()


class _NullTracer:
    """The do-nothing default tracer (see module docstring)."""

    enabled = False

    def span(self, name: str, **attrs: Any) -> _NullSpanCM:
        return _NULL_CM

    def phase(self, name: str, **attrs: Any) -> _NullSpanCM:
        return _NULL_CM

    def summary(self) -> List[Dict[str, Any]]:
        return []


NULL_TRACER = _NullTracer()


class _SpanCM:
    __slots__ = ("tracer", "sp", "ann")

    def __init__(self, tracer: "SpanTracer", sp: Span):
        self.tracer = tracer
        self.sp = sp
        self.ann = None

    def __enter__(self) -> Span:
        tr, sp = self.tracer, self.sp
        if tr._open:
            sp.parent = tr._open[-1].seq
        if sp.depth is not None:
            sp.depth = tr._depth
            tr._depth += 1
        tr._open.append(sp)
        self.ann = tr._annotation(ANNOTATION_PREFIX + sp.name)
        self.ann.__enter__()
        sp.t_start_s = time.perf_counter()
        return sp

    def __exit__(self, *exc) -> bool:
        tr, sp = self.tracer, self.sp
        sp.dur_ms = (time.perf_counter() - sp.t_start_s) * 1e3
        self.ann.__exit__(None, None, None)
        tr._open.pop()
        if sp.depth is not None:
            tr._depth -= 1
        tr._finish(sp)
        return False


class SpanTracer:
    """Records spans in memory (bounded) and emits each closed span to an
    optional callback — typically ``EventHub.on_span``, which fans out
    to ``JsonlObserver`` and the metrics registry's observer."""

    enabled = True

    def __init__(self, emit: Optional[Callable[[Span], None]] = None,
                 max_spans: int = 100_000):
        import jax.profiler

        self.spans: List[Span] = []
        self.dropped = 0
        self.max_spans = max_spans
        self._emit = emit
        self._depth = 0
        self._seq = 0
        self._open: List[Span] = []
        self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str, **attrs: Any) -> _SpanCM:
        """A layer span: its depth counts the layer spans around it."""
        sp = Span(name, self._seq, 0, **attrs)
        self._seq += 1
        return _SpanCM(self, sp)

    def phase(self, name: str, **attrs: Any) -> _SpanCM:
        """A phase span inside a layer: no depth, only its parent."""
        sp = Span(name, self._seq, None, **attrs)
        self._seq += 1
        return _SpanCM(self, sp)

    def _finish(self, sp: Span) -> None:
        if len(self.spans) < self.max_spans:
            self.spans.append(sp)
        else:
            self.dropped += 1
        if self._emit is not None:
            self._emit(sp)

    # -- aggregation -------------------------------------------------------

    def summary(self) -> List[Dict[str, Any]]:
        """Per-name aggregate rows (count / total / mean / max ms),
        sorted by total wall time descending — the dashboard's
        flamegraph-style span table."""
        agg: Dict[str, Dict[str, Any]] = {}
        for sp in self.spans:
            row = agg.setdefault(sp.name, {
                "name": sp.name, "count": 0, "total_ms": 0.0,
                "max_ms": 0.0})
            row["count"] += 1
            row["total_ms"] += sp.dur_ms
            row["max_ms"] = max(row["max_ms"], sp.dur_ms)
        out = sorted(agg.values(), key=lambda r: -r["total_ms"])
        for row in out:
            row["mean_ms"] = row["total_ms"] / row["count"]
            row["total_ms"] = round(row["total_ms"], 4)
            row["mean_ms"] = round(row["mean_ms"], 4)
            row["max_ms"] = round(row["max_ms"], 4)
        return out
