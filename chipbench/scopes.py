"""Read the program's own tracing from a profiler trace: the ``kmeans.*``
device scopes, which name the ops of a fit loop, and the ``kmeans.*`` host
spans, which say what the host was doing while the device sat idle.

A scope is a component of an op's name-stack path, which the TPU profiler
keeps in the ``tf_op`` stat of the op's event *metadata*.
``jax.profiler.ProfileData`` gives only each event's own stats, so
``op_scopes`` reads the metadata from the XSpace proto itself (the field
numbers of ``xplane.proto``). ``load_program`` adds the program executions
of each device's ``XLA Modules`` line and the host spans; ``reduce_program``
splits the window's device time by scope and its idle time by span.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace as tr

PREFIX = "kmeans."
# a scope is a component of an op's name-stack path, with the op below it
SCOPE = re.compile(r"(?<![\w.])(kmeans\.\w+)/")
MODULES_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
OUTSIDE = "none"
CHUNK = re.compile(r"chunk")


def scope_of(path: str) -> str | None:
    """The innermost ``kmeans.*`` scope of a name-stack path, or None."""
    found = SCOPE.findall(path)
    return found[-1] if found else None


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    (no field read here has one) are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _plane_scopes(plane) -> dict[str, str | None]:
    # XPlane: 4 event_metadata and 5 stat_metadata, maps (1 key, 2 value);
    # XEventMetadata: 2 name, 5 stats; XStatMetadata: 2 name;
    # XStat: 1 metadata_id, 5 str_value, 7 ref_value (an interned string)
    entries = [(f, dict(_fields(v))) for f, v in _fields(plane) if f in (4, 5)]
    names = {e.get(1, 0): _text(dict(_fields(e.get(2, b""))).get(2, b""))
             for f, e in entries if f == 5}
    stat = next((k for k, n in names.items() if n == SCOPE_STAT), None)
    seen: dict[str, set] = defaultdict(set)
    for f, e in entries:
        if f != 4:
            continue
        path, name = "", ""
        for g, v in _fields(e.get(2, b"")):
            if g == 2:
                name = _text(v)
            elif g == 5:
                s = dict(_fields(v))
                if s.get(1, 0) == stat:
                    path = _text(s[5]) if 5 in s else names.get(s.get(7), "")
        seen[tr.op_name(name)].add(scope_of(path))
    # an instruction name that two programs give different scopes is
    # left unscoped rather than guessed
    return {n: next(iter(s)) if len(s) == 1 else None
            for n, s in seen.items()}


def op_scopes(path: str) -> dict[str, str | None]:
    """The innermost ``kmeans.*`` scope of each op of the TPU planes, by
    the op's HLO instruction name (``trace.op_name``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str | None] = {}
    for field, plane in _fields(space):               # XSpace: 1 planes
        if field != 1:
            continue
        name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
        if tr.DEVICE_PLANE.match(name):
            out.update(_plane_scopes(plane))
    return out


def load_program(path: str) -> tuple[list, list, dict]:
    """From one trace file: the program executions of each TPU plane
    (``XLA Modules``) as ``trace.Op`` lists, the host spans named
    ``kmeans.*``, and the scope of each op (``op_scopes``)."""
    from jax.profiler import ProfileData
    modules, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            modules.append(sorted(
                (tr.Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for line in plane.lines if line.name == MODULES_LINE
                 for ev in line.events), key=lambda o: o.start_ns))
        elif plane.name.startswith("/host:"):
            spans.extend(tr.Span(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(PREFIX))
    return (modules, sorted(spans, key=lambda s: s.start_ns),
            op_scopes(path))


def scope_seconds(ops, scopes: dict, costs: dict) -> dict | None:
    """Device seconds per innermost scope, and ``unscoped``; the ops of a
    costed kernel are left out. None when no op carries a scope (a
    program without them)."""
    pats = [re.compile(mod.PATTERN) for mod in costs.values()]
    out: dict[str, float] = defaultdict(float)
    for o in ops:
        if any(p.search(f"{o.name} {o.detail}") for p in pats):
            continue
        out[scopes.get(o.name) or UNSCOPED] += (o.end_ns - o.start_ns) * 1e-9
    if not set(out) - {UNSCOPED}:
        return None
    return dict(out)


def _innermost(t: float, spans) -> str:
    covering = [s for s in spans if s.start_ns <= t < s.end_ns]
    if not covering:
        return OUTSIDE
    return min(covering, key=lambda s: s.end_ns - s.start_ns).name


def idle_by_program_span(gaps, spans) -> dict:
    """Idle seconds by the innermost program span over each stretch of
    each gap (by overlap: a gap that two spans share is split between
    them); ``none`` where no program span was open."""
    out: dict[str, float] = defaultdict(float)
    for lo, hi in gaps:
        near = [s for s in spans if s.start_ns < hi and s.end_ns > lo]
        cuts = sorted({lo, hi} | {t for s in near
                                  for t in (s.start_ns, s.end_ns)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            out[_innermost(0.5 * (a + b), near)] += (b - a) * 1e-9
    return dict(out)


def overlap_ns(gaps, intervals) -> float:
    """The length of the gaps that the intervals cover (each interval
    counted once: pass them disjoint, as ``trace.merged`` gives them)."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for lo, hi in gaps for a, b in intervals)


def one_clock(modules, spans) -> dict:
    """Whether host and device share one clock, chunk by chunk: each
    chunk program starts after its ``dispatch`` span starts, and the
    first ``sync`` span after that dispatch ends after the program
    ends."""
    chunks = [m for m in modules if CHUNK.search(m.name)]
    dispatch = [s for s in spans if s.name == PREFIX + "dispatch"]
    sync = [s for s in spans if s.name == PREFIX + "sync"]
    held, faults = 0, []
    for m, d in zip(chunks, dispatch):
        s = next((s for s in sync if s.start_ns >= d.end_ns), None)
        if m.start_ns >= d.start_ns and s is not None \
                and s.end_ns >= m.end_ns:
            held += 1
        else:
            faults.append([m.name, m.start_ns, d.start_ns])
    return {"chunks": len(chunks), "dispatches": len(dispatch),
            "held": held, "faults": faults[:5]}


def reduce_program(trace, modules, spans, scopes: dict,
                   costs: dict) -> dict:
    """On the first device, in the harness's window: device seconds per
    scope (costed kernels left out), idle seconds by program span, idle
    inside a running program and between programs, the idle between
    programs inside the program's fits (``fit_host_s``), and the
    one-clock check of every chunk program."""
    lo, hi = tr.window_of(trace)
    ops = tr.clip(tr.leaves(trace.devices[0]), lo, hi)
    mods = tr.clip(modules[0], lo, hi)
    gaps = tr.idle_gaps(ops, lo, hi)
    inside = overlap_ns(gaps, tr.merged(mods)) * 1e-9
    between = tr.idle_gaps(list(ops) + list(mods), lo, hi)
    fits = tr.merged(tr.Op(s.name, s.start_ns, s.end_ns) for s in spans
                     if s.name == PREFIX + "fit")
    return {
        "scopes": scope_seconds(ops, scopes, costs),
        "idle_by_program_span": idle_by_program_span(gaps, spans),
        "idle_in_programs_s": inside,
        "idle_between_programs_s": sum(b - a for a, b in between) * 1e-9,
        "fit_host_s": overlap_ns(between, fits) * 1e-9,
        "one_clock": one_clock(mods, [s for s in spans
                                      if lo <= s.start_ns < hi]),
    }
