"""Reduction of a profiler trace to the per-layer metrics' inputs.

A trace (``.xplane.pb``) holds one plane per device with a line of XLA
op events (``XLA Ops``) and a line of whole-program events (``XLA
Modules``), and a host plane whose thread lines hold the benchmark's
``bench.*`` annotations.  All timestamps are on one clock, in ns.

``Trace`` keeps only what the metrics read:

- ``rounds``: the ``bench.round`` spans, whose union from the first start
  to the last end is the traced window;
- ``spans``: the other ``bench.*`` spans by name, with their arguments;
- ``ops`` / ``modules``: device events per device plane, each
  ``(start, end, name, stats)``.

and offers the arithmetic on them: interval unions, busy time, the idle
gaps of a device and the host span each gap fell in.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]


@dataclass
class Event:
    start: int
    end: int
    name: str
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Trace:
    rounds: List[Event] = field(default_factory=list)
    spans: Dict[str, List[Event]] = field(default_factory=dict)
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)

    @property
    def window(self) -> Optional[Interval]:
        if not self.rounds:
            return None
        return (min(e.start for e in self.rounds),
                max(e.end for e in self.rounds))


def _events(line) -> Iterable[Event]:
    for e in line.events:
        start = int(e.start_ns)
        yield Event(start, start + int(e.duration_ns), e.name,
                    dict(e.stats))


def from_profile(profile) -> Trace:
    """Build a :class:`Trace` from ``jax.profiler.ProfileData``."""
    t = Trace()
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    t.ops.setdefault(plane.name, []).extend(_events(line))
                elif line.name == "XLA Modules":
                    t.modules.setdefault(plane.name, []).extend(
                        _events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name == "bench.round":
                        t.rounds.append(e)
                    elif e.name.startswith("bench."):
                        t.spans.setdefault(e.name, []).append(e)
    for evs in (*t.ops.values(), *t.modules.values(), t.rounds,
                *t.spans.values()):
        evs.sort(key=lambda e: (e.start, e.end))
    return t


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in union(intervals))


def busy(t: Trace, lo: int, hi: int) -> Dict[str, int]:
    """Per device, the ns inside [lo, hi) in which some op ran."""
    return {dev: length(clip(((e.start, e.end) for e in evs), lo, hi))
            for dev, evs in t.ops.items()}


def gaps(t: Trace, device: str, lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``device`` inside [lo, hi), longest first."""
    on = union(clip(((e.start, e.end) for e in t.ops.get(device, [])),
                    lo, hi))
    out, cur = [], lo
    for a, b in on:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def span_label(e: Event) -> str:
    args = [str(e.stats[k]) for k in ("site", "op") if k in e.stats]
    return ":".join([e.name.split(".", 1)[1], *args])


def label(t: Trace, gap: Interval, names: Sequence[str]) -> str:
    """What the host was doing in ``gap``: the labels of the two ``names``
    spans that cover most of it, longest overlap first, where the part no
    span covers counts as ``relay_codec`` (the wire codec, FLARE relay
    and SuperLink, which carry no span of their own yet)."""
    cover: Dict[str, int] = {}
    inside: List[Interval] = []
    for n in names:
        for e in t.spans.get(n, []):
            ov = min(e.end, gap[1]) - max(e.start, gap[0])
            if ov > 0:
                key = span_label(e)
                cover[key] = cover.get(key, 0) + ov
                inside.append((max(e.start, gap[0]), min(e.end, gap[1])))
    rest = (gap[1] - gap[0]) - length(inside)
    if rest > 0:
        cover["relay_codec"] = rest
    ranked = sorted(cover.items(), key=lambda kv: -kv[1])
    return "+".join(k for k, _ in ranked[:2])


def op_name(e: Event) -> str:
    """``%fusion.1 = bf16[...] fusion(...)`` -> ``fusion.1``."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def op_shapes(e: Event) -> str:
    """The result and operand shapes of an op's HLO text, without its
    attributes (``custom_call_target=...``, ``backend_config=...``)."""
    text = e.name.split(" = ", 1)[-1]
    return text.split("), ", 1)[0] if "), " in text else text


def program_of(t: Trace, device: str, e: Event) -> str:
    """The program (``XLA Modules`` event) an op ran in, without its
    fingerprint: ``jit_train_step(7884...)`` -> ``jit_train_step``."""
    mods = t.modules.get(device, [])
    i = bisect.bisect_right([m.start for m in mods], e.start) - 1
    if i >= 0 and mods[i].end >= e.start:
        return mods[i].name.split("(", 1)[0]
    return "?"


def in_window(evs: Iterable[Event], lo: int, hi: int) -> List[Event]:
    """Events that start inside [lo, hi)."""
    evs = list(evs)
    starts = [e.start for e in evs]
    return evs[bisect.bisect_left(starts, lo):bisect.bisect_left(starts, hi)]
