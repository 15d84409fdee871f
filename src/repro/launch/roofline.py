"""Roofline terms from a compiled dry-run artifact.

Three terms per (arch x shape x mesh) cell, all in seconds-per-step on
the peaks of the target chip (``mesh.PEAKS``, keyed by device kind):

    compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s
    memory     = HLO_bytes_per_device / HBM bytes/s
    collective = wire_bytes_per_device / ICI bytes/s per link

``cost_analysis()`` runs on the SPMD-partitioned module, so its FLOPs /
bytes are already per-device.  Collective bytes are NOT in cost_analysis:
we parse the optimized HLO text and sum operand/result sizes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
(all-reduce counts 2x for the reduce+broadcast halves of a ring).

``useful_ratio`` = MODEL_FLOPS / (HLO_FLOPs x chips) — how much of the
compiled compute is the 6·N·D (train) / 2·N·D (inference) model math;
remat recompute, GShard dispatch one-hots and padding all push it down.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from ..configs.base import InputShape, ModelConfig
from .mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returned a one-element list of dicts
    in older JAX and a plain dict in newer releases (and may be None for
    some backends).  Normalize every variant to a dict."""
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        return dict(cost[0]) if cost else {}
    return dict(cost)


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_DOT_OPERANDS_RE = re.compile(r"dot\(([^)]*)\)")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_SKIP_TRAFFIC = ("parameter(", "constant(", "tuple(", "get-tuple-element(",
                 "bitcast(", "after-all(", "partition-id(",
                 # loop-carried state is aliased, not moved, per iteration
                 "while(", "conditional(", "optimization-barrier(")
# ops whose large buffers are aliased in-place / read only a slice
_SLICE_FAMILY = ("dynamic-update-slice", "dynamic-slice", " gather(",
                 " scatter(", "wrapped_scatter", "wrapped_gather",
                 "_scatter", "_gather")


def _parse_computations(hlo_text: str):
    """-> {comp_name: [lines]}, entry_name."""
    comps: Dict[str, list] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _COMP_RE.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
                continue
        if cur is not None and line.strip() and line.strip() != "}":
            comps[cur].append(line.strip())
    return comps, entry


def _trip_count(while_line: str, cond_lines) -> int:
    """Trip count of one while site: XLA annotates
    backend_config known_trip_count; fall back to the largest integer
    constant in the loop condition computation (scan bounds)."""
    m = _TRIP_RE.search(while_line)
    if m:
        return max(int(m.group(1)), 1)
    best = 1
    for line in cond_lines:
        for c in _CONST_RE.finditer(line):
            best = max(best, int(c.group(1)))
    return best


def _multipliers(comps: Dict[str, list], entry) -> Dict[str, float]:
    """Execution-count multiplier per computation: product of enclosing
    while-loop trip counts; fusion bodies / reducers inherit the caller's
    multiplier."""
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    if entry not in comps:
        return {c: 1.0 for c in comps}
    mult[entry] = 1.0
    for _ in range(16):  # fixpoint over (shallow) nesting
        changed = False
        for comp, lines in comps.items():
            m = mult.get(comp, 0.0)
            if m <= 0:
                continue
            for line in lines:
                wm = _WHILE_RE.search(line)
                if wm:
                    cond, body = wm.group(1), wm.group(2)
                    trips = _trip_count(line, comps.get(cond, []))
                    if m * trips > mult.get(body, 0.0):
                        mult[body] = m * trips
                        changed = True
                    if m > mult.get(cond, 0.0):
                        mult[cond] = m
                        changed = True
                for cm in _CALL_RE.finditer(line):
                    callee = cm.group(1)
                    if m > mult.get(callee, 0.0):
                        mult[callee] = m
                        changed = True
        if not changed:
            break
    return {c: (v if v > 0 else 1.0) for c, v in mult.items()}


def hlo_stats(hlo_text: str) -> Dict[str, float]:
    """Loop-corrected per-device FLOPs and HBM traffic from optimized HLO.

    XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE
    (verified empirically), which under-reports every scan-over-layers
    model by ~n_periods.  This walk multiplies each computation's cost by
    the product of enclosing loop trip counts.

    * FLOPs: every ``dot`` (2 x result elems x contracted elems), counted
      in all computations (incl. fusion bodies).
    * traffic: operand+result bytes of ops in non-fusion-body computations
      (fusion interiors never touch HBM; the fusion call site is counted).
    """
    comps, entry = _parse_computations(hlo_text)
    mult = _multipliers(comps, entry)
    interior = set()
    for lines in comps.values():
        for line in lines:
            for m in _CALL_RE.finditer(line):
                interior.add(m.group(1))
    flops = 0.0
    traffic = 0.0
    for comp, lines in comps.items():
        m = mult.get(comp, 1.0)
        # local symbol table: defined name -> (dtype, dims)
        sym = {}
        for line in lines:
            dm = _DEF_RE.match(line)
            if dm:
                sym[dm.group(1)] = (dm.group(2), dm.group(3))
        for line in lines:
            if " dot(" in line or line.startswith("dot("):
                dm = _DEF_RE.match(line)
                out_elems = 1
                if dm:
                    for d in dm.group(3).split(","):
                        if d:
                            out_elems *= int(d)
                contracted = 1
                om = _DOT_OPERANDS_RE.search(line)
                cm = _LHS_CONTRACT_RE.search(line)
                if om and cm:
                    names = _NAME_RE.findall(om.group(1))
                    if names and names[0] in sym:
                        dims = [int(x) for x in sym[names[0]][1].split(",")
                                if x]
                        for ci in cm.group(1).split(","):
                            if ci and int(ci) < len(dims):
                                contracted *= dims[int(ci)]
                flops += 2.0 * out_elems * contracted * m
            if comp in interior and comp != entry:
                continue
            s = line.lstrip("%")
            if any(s.startswith(k) or f" {k}" in s for k in _SKIP_TRAFFIC):
                continue
            sizes = [_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(line)]
            if not sizes:
                continue
            if any(t in line for t in _SLICE_FAMILY):
                # in-place update / slice ops touch only the slice bytes:
                # the full buffer appears as operand AND result (aliased),
                # so count 2x everything except the max-sized shapes
                mx = max(sizes)
                traffic += 2.0 * sum(x for x in sizes if x < mx) * m
            else:
                traffic += sum(sizes) * m
    return {"flops": flops, "bytes accessed": traffic}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-kind wire bytes (per device) from optimized HLO.

    While-loop aware: a collective inside a scan body counts once per trip
    (matching how cost_analysis scales FLOPs)."""
    comps, entry = _parse_computations(hlo_text)
    mult = _multipliers(comps, entry)
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    top: list = []
    for comp, lines in comps.items():
        m = mult.get(comp, 1.0)
        for line in lines:
            for kind in _COLLECTIVES:
                if f" {kind}(" not in line and f" {kind}-start(" not in \
                        line and not line.startswith(f"{kind}("):
                    continue
                shapes = _SHAPE_RE.findall(line)
                if not shapes:
                    continue
                sizes = [_shape_bytes(dt, dims) for dt, dims in shapes]
                wire = max(sizes)
                if kind == "all-reduce":
                    wire *= 2
                # XLA:CPU promotes sub-f32 collectives to f32 (reducer
                # named *.clone_promoted, convert fusions around the op);
                # TPU moves the original 16-bit tensor — count that.
                if "promot" in line:
                    wire /= 2
                out[kind] += float(wire) * m
                counts[kind] += m
                top.append((float(wire) * m, kind, m,
                            line[:140]))
                break
    top.sort(reverse=True)
    out["_counts"] = counts           # type: ignore[assignment]
    out["_top"] = [                   # type: ignore[assignment]
        {"bytes": b, "kind": k, "mult": m, "op": op}
        for b, k, m, op in top[:12]]
    return out


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """6·N·D (train) / 2·N·D (inference); N = active params for MoE."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per batch element
    return 2.0 * n * shape.global_batch


def roofline_terms(cost: dict, coll: Dict[str, float], n_devices: int,
                   cfg: Optional[ModelConfig] = None,
                   shape: Optional[InputShape] = None, *,
                   device_kind: str) -> dict:
    """Roofline terms on ``device_kind``'s peaks (``ValueError`` for a
    chip without published peaks)."""
    peaks = chip_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    wire = float(sum(v for k, v in coll.items() if not k.startswith("_")))
    terms = {
        "compute_s": flops / peaks["flops_bf16"],
        "memory_s": bytes_hbm / peaks["hbm_bw"],
        "collective_s": wire / peaks["ici_bw"],
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_hbm,
        "wire_bytes_per_device": wire,
        "n_devices": n_devices,
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom.replace("_s", "")
    if cfg is not None and shape is not None:
        mf = model_flops(cfg, shape)
        terms["model_flops"] = mf
        terms["useful_ratio"] = mf / max(flops * n_devices, 1.0)
        # roofline fraction: useful model FLOPs per device-second achievable
        # given the *dominant* term as the step time.
        step_s = max(terms["compute_s"], terms["memory_s"],
                     terms["collective_s"])
        terms["roofline_frac"] = (mf / n_devices / max(step_s, 1e-30)
                                  / peaks["flops_bf16"])
    return terms
