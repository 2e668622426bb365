"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps,
time per Pallas kernel and the breakdown a result line carries.

The reduction works on plain event lists — ``Op(name, start_ns, end_ns,
detail)`` for device operations and ``Span(name, start_ns, end_ns)`` for the
harness's own host spans — so that it can be checked on a synthetic trace.
``load`` turns one trace file into those lists.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HLO_NAME = re.compile(r"^%?([^\s=%]+) = ")
HLO_CHARS = 300
WINDOW_SPAN = "window"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    devices: list          # per device: list[Op], sorted by start
    spans: list            # list[Span] of the harness's host annotations


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _detail(event) -> str:
    parts = []
    for key, value in event.stats:
        if key in ("long_name", "tf_op", "hlo_op", "name", "source",
                   "hlo_module", "kernel_details"):
            parts.append(f"{key}={value}")
    return " ".join(parts)


def op_name(text: str) -> str:
    """The HLO instruction's name of a TPU op event. The profiler names
    each event by its whole HLO line (``%distance_argmin.2 = (f32[...])
    custom-call(...), ...``); the name is what precedes `` = ``, without
    the ``%``."""
    m = HLO_NAME.match(text)
    return m.group(1) if m else text


def load(path: str, span_names) -> Trace:
    """Device ops of every TPU plane, each named by its HLO instruction
    with the whole HLO line in its detail, and the host spans named in
    ``span_names``, on the profiler's common clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [Op(op_name(ev.name), ev.start_ns,
                      ev.start_ns + ev.duration_ns,
                      f"hlo={ev.name[:HLO_CHARS]} {_detail(ev)}".rstrip())
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices.append(sorted(ops, key=lambda o: o.start_ns))
        elif plane.name.startswith("/host:"):
            spans.extend(Span(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                         for line in plane.lines for ev in line.events
                         if ev.name in wanted)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    return Trace(devices, sorted(spans, key=lambda s: s.start_ns))


def window_of(trace: Trace) -> tuple[float, float]:
    """The measured window, as the harness's ``window`` span marks it."""
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' span, found "
                         f"{len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def clip(ops, lo: float, hi: float) -> list:
    """The ops that overlap [lo, hi], cut to it."""
    return [dataclasses.replace(o, start_ns=max(o.start_ns, lo),
                                end_ns=min(o.end_ns, hi))
            for o in ops if o.end_ns > lo and o.start_ns < hi]


def leaves(ops) -> list:
    """The ops that hold no other op: a control-flow op (a ``while`` of a
    scan, a conditional) spans the ops it runs, and would otherwise count
    the idle time between them as busy and its own span as work."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.end_ns))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt.start_ns < o.end_ns
                                   and nxt.end_ns <= o.end_ns)]


def merged(ops) -> list[tuple[float, float]]:
    """The union of the ops' intervals, as disjoint sorted intervals."""
    out: list[list[float]] = []
    for o in sorted(ops, key=lambda o: o.start_ns):
        if out and o.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end_ns)
        else:
            out.append([o.start_ns, o.end_ns])
    return [(a, b) for a, b in out]


def busy_ns(ops) -> float:
    return sum(b - a for a, b in merged(ops))


def idle_gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for a, b in merged(ops):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_of(gap: tuple[float, float], spans) -> str:
    """The innermost harness span (other than the window) that covers the
    gap's midpoint, or ``none``."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [s for s in spans if s.name != WINDOW_SPAN
                and s.start_ns <= mid < s.end_ns]
    if not covering:
        return "none"
    return min(covering, key=lambda s: s.end_ns - s.start_ns).name


def match_kernels(ops, costs: dict) -> tuple[dict, float]:
    """Device time and launches per kernel, matched by each cost model's
    ``PATTERN`` against an op's name and detail; and the busy time of the
    device outside those kernels. ``costs`` maps a kernel name to its
    cost module."""
    per = {name: {"launches": 0, "seconds": 0.0} for name in costs}
    pats = {name: re.compile(mod.PATTERN) for name, mod in costs.items()}
    hits = []
    for o in ops:
        text = f"{o.name} {o.detail}"
        hit = next((n for n, p in pats.items() if p.search(text)), None)
        if hit is not None:
            per[hit]["launches"] += 1
            per[hit]["seconds"] += (o.end_ns - o.start_ns) * 1e-9
            hits.append(o)
    return per, (busy_ns(ops) - busy_ns(hits)) * 1e-9


def breakdown(ops, gaps, spans) -> dict:
    """The device ops that took most time and the longest idle gaps, each
    by what the harness was doing in it; and the idle time summed by what
    the harness was doing."""
    by_op: dict[str, float] = defaultdict(float)
    for o in ops:
        by_op[o.name] += (o.end_ns - o.start_ns) * 1e-9
    by_gap: dict[str, float] = defaultdict(float)
    for g in gaps:
        by_gap[label_of(g, spans)] += (g[1] - g[0]) * 1e-9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "device_ops": [[n, s] for n, s in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label_of(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in longest],
    }, [[n, s] for n, s in sorted(by_gap.items(), key=lambda kv: -kv[1])]


def reduce(trace: Trace, costs: dict) -> dict:
    """Everything the per-layer readers take from one trace, averaged over
    the devices: window and busy seconds, kernel times, other device time,
    and the breakdown of the first device."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) * 1e-9
    busy, other, kernels, first, idle = [], [], None, None, None
    for ops in trace.devices:
        ops = clip(leaves(ops), lo, hi)
        busy.append(busy_ns(ops) * 1e-9)
        per, rest = match_kernels(ops, costs)
        other.append(rest)
        if kernels is None:
            kernels = per
            first, idle = breakdown(ops, idle_gaps(ops, lo, hi),
                                    trace.spans)
        else:
            for name, rec in per.items():
                kernels[name]["launches"] += rec["launches"]
                kernels[name]["seconds"] += rec["seconds"]
    n = len(trace.devices)
    for rec in kernels.values():
        rec["seconds"] /= n
        rec["launches"] /= n
    return {"window_s": window_s, "busy_s": sum(busy) / n,
            "other_s": sum(other) / n, "kernels": kernels,
            "breakdown": first, "idle_by_span": idle}


def op_table(trace: Trace) -> list:
    """Every distinct device op of the first device in the window, with its
    launches, seconds and detail: the table from which kernel patterns are
    read by hand. Ops that hold others are listed apart, with a ``*``."""
    lo, hi = window_of(trace)
    ops = trace.devices[0]
    held = set(leaves(ops))
    table: dict[str, list] = {}
    for o, orig in zip(clip(ops, lo, hi), [o for o in ops
                                           if o.end_ns > lo and o.start_ns < hi]):
        name = o.name if orig in held else "*" + o.name
        rec = table.setdefault(name, [name, 0, 0.0, o.detail])
        rec[1] += 1
        rec[2] += (o.end_ns - o.start_ns) * 1e-9
    return sorted(table.values(), key=lambda r: -r[2])
