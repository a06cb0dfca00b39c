"""The program's own spans in a traced run, for the per-layer readers.

The program wraps the work of its layers in ``repro.<layer>.<what>``
spans (``repro.utils.tracing``), which the profiler writes to the host
plane of the same trace as the benchmark's ``bench.*`` spans and the
device ops, on their clock:

- ``repro.codec.encode`` / ``.decode`` (``op``, ``codec``, ``nbytes``):
  each public encode and decode of ``fl/messages.py``, and each later
  model-size dequantize; they do not nest on a thread;
- ``repro.relay.request`` (``method``, ``nbytes``, ``hit`` for pulls): a
  SuperNode's whole six-hop fleet call through the FLARE relay;
- ``repro.superlink.serve`` (``method``, ``queued_s`` of a returned
  task): the SuperLink answering a fleet call; ``repro.superlink
  .deliver`` (``queued_s``): a result handed to the ServerApp;
- ``repro.xfer.h2d`` / ``.d2h`` (``nbytes``): model copies to and from
  the device around a client's fit and evaluate;
- ``repro.fold.stage`` / ``.kernel`` / ``.unstage`` (``nbytes``,
  ``clients``): the fold's host staging, its device call, and the base
  add and cast after it.

The trace reduction (``trace_reduce.from_profile``) keeps only the
benchmark's spans, so this module reads the ``repro.*`` host events from
the same trace file, once per run (``program``), and offers what the
readers share and the gap labels that count the program's spans
(``label``).  On a program without these spans every reader finds
nothing and returns ``None``.
"""
from __future__ import annotations

import pathlib
from typing import (Dict, Iterable, Iterator, List, Optional, Sequence,
                    Tuple)

import trace_reduce as tr
from readings import NS

#: where ``run.py`` has the profiler write a ``--trace 1`` run's trace
TRACE_DIR = pathlib.Path(__file__).resolve().parent / ".out" / "trace"
PREFIX = "repro."
#: the SuperLink's side of a relayed call, labelled only where no other
#: span covers
HOPS = ("repro.superlink.serve",)


def host_events(profile) -> Iterator[Tuple[Tuple[str, int], tr.Event]]:
    """``((plane, line index), event)`` for each ``repro.*`` host event of
    ``jax.profiler.ProfileData``; a line is one thread."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    yield (plane.name, li), tr.Event(
                        start, start + int(e.duration_ns), e.name,
                        dict(e.stats))


def from_profile(profile) -> Dict[str, List[tr.Event]]:
    """The ``repro.*`` host events of ``jax.profiler.ProfileData``, by
    name, each list sorted by start."""
    out: Dict[str, List[tr.Event]] = {}
    for _, e in host_events(profile):
        out.setdefault(e.name, []).append(e)
    for evs in out.values():
        evs.sort(key=lambda e: (e.start, e.end))
    return out


def program(t: tr.Trace) -> Dict[str, List[tr.Event]]:
    """The program spans of the run ``t`` was reduced from, read from its
    trace file on first use and kept on ``t`` (``t.program``)."""
    got = getattr(t, "program", None)
    if got is None:
        got = {}
        path = next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"), None)
        if path is not None:
            from jax.profiler import ProfileData

            got = from_profile(ProfileData.from_file(str(path)))
        t.program = got
    return got


def events(ctx, *names: str) -> List[tr.Event]:
    """Events of ``names`` that start inside the window."""
    lo, hi = ctx.window
    spans = program(ctx.trace)
    return [e for n in names for e in tr.in_window(spans.get(n, []), lo, hi)]


def duration_s(evs: Iterable[tr.Event]) -> float:
    return sum(e.end - e.start for e in evs) * NS


def s_per_round(ctx, *names: str) -> Optional[float]:
    """Seconds per round in ``names`` spans, summed over threads."""
    evs = events(ctx, *names)
    if not evs or not ctx.rounds:
        return None
    return duration_s(evs) / ctx.rounds


def is_pull(e: tr.Event) -> bool:
    return e.stats.get("method") == "pull_task_ins"


def carried(e: tr.Event) -> bool:
    """A relayed call that moved a task or a result: a pull that found a
    task, or any result push."""
    m = e.stats.get("method")
    return (m == "pull_task_ins" and int(e.stats.get("hit", 0)) == 1) \
        or m == "push_task_res"


def program_label(e: tr.Event) -> str:
    """``repro.codec.encode`` with op ``fit_res`` -> ``codec.encode:
    fit_res``; an empty pull is ``relay:poll``."""
    if e.name == "repro.relay.request" and is_pull(e) and not carried(e):
        return "relay:poll"
    args = [str(e.stats[k]) for k in ("method", "op") if k in e.stats]
    return ":".join([e.name[len(PREFIX):], *args[:1]])


def _rank(e: tr.Event) -> int:
    """2: a program span that works; 0: a SuperLink serve or an empty
    pull."""
    if e.name in HOPS or program_label(e) == "relay:poll":
        return 0
    return 2


def cover(t: tr.Trace, gap: tr.Interval, names: Sequence[str],
          spans: Optional[Dict[str, List[tr.Event]]] = None
          ) -> Dict[str, int]:
    """Who the host's time in ``gap`` goes to, in ns by label: each
    instant to the innermost (latest-started) program span that works
    there; where none does, to every ``names`` span covering it (as
    ``trace_reduce.label`` counts them); then to the innermost serve
    or empty pull (``relay:poll``: four SuperNodes poll all through a
    round); else to ``relay_codec``, time no span covers.  Labels come
    in ``trace_reduce.label``'s order: the benchmark's, the program's,
    then ``relay_codec``."""
    lo, hi = gap
    spans = program(t) if spans is None else spans
    order: Dict[str, None] = {}
    marks = []                          # (span, rank); bench spans 1
    for n in names:
        for e in t.spans.get(n, []):
            if min(e.end, hi) > max(e.start, lo):
                order.setdefault(tr.span_label(e))
                marks.append((e, 1))
    for evs in spans.values():
        for e in evs:
            if min(e.end, hi) > max(e.start, lo):
                marks.append((e, _rank(e)))
    points = sorted({lo, hi, *(max(e.start, lo) for e, _ in marks),
                     *(min(e.end, hi) for e, _ in marks)})
    marks.sort(key=lambda m: m[0].start)
    got: Dict[str, int] = {}
    active: List = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(marks) and max(marks[i][0].start, lo) <= a:
            active.append(marks[i])
            i += 1
        active = [m for m in active if min(m[0].end, hi) > a]
        work = [e for e, r in active if r == 2]
        bench = [e for e, r in active if r == 1]
        hops = [e for e, r in active if r == 0]
        if work or (hops and not bench):
            inner = max(work or hops, key=lambda e: (e.start, -e.end))
            keys = [program_label(inner)]
        elif bench:
            keys = [tr.span_label(e) for e in bench]
        else:
            keys = ["relay_codec"]
        for k in keys:
            if k != "relay_codec":
                order.setdefault(k)
            got[k] = got.get(k, 0) + (b - a)
    order.setdefault("relay_codec")
    return {k: got[k] for k in order if got.get(k, 0) > 0}


def label(t: tr.Trace, gap: tr.Interval, names: Sequence[str],
          spans: Optional[Dict[str, List[tr.Event]]] = None) -> str:
    """What the host was doing in ``gap``: the two labels that
    :func:`cover` gives the most time, longest first.  A trace without
    program spans gets ``trace_reduce.label``'s answer."""
    got = cover(t, gap, names, spans)
    return "+".join(sorted(got, key=lambda k: -got[k])[:2])
