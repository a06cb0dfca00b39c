"""Program spans on the device trace's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` (XLA's
``TraceMe``): inside a ``jax.profiler`` trace it lands on the host plane
of that trace, on the same clock as the device ops.  Outside one it is a
single shared no-op context whose ``as`` target is ``None``, so

    with tracing.span("repro.xfer.d2h") as s:
        out = work()
        if s:
            tracing.annotate(s, nbytes=out.nbytes)

computes no argument and syncs nothing unless a trace is being taken.
``annotate`` adds arguments known only at the span's end.
``outermost`` is a span that does not nest: while one is open on a
thread, the next ``outermost`` on that thread is the no-op, so a codec
function that calls another codec function is counted once.
``annotate_outermost`` lets the code under such a span add arguments to
it without holding it.

There is no sink or setting: the profiler is the one exporter.
"""
from __future__ import annotations

import contextlib
import threading

from jax.profiler import TraceAnnotation

OFF = contextlib.nullcontext()
_local = threading.local()


def enabled() -> bool:
    """Whether a profiler trace is being taken."""
    return TraceAnnotation.is_enabled()


def span(name: str, **args):
    if not TraceAnnotation.is_enabled():
        return OFF
    return TraceAnnotation(name, **args)


def annotate(s, **args) -> None:
    """Set ``args`` on an open span (``s`` from ``with span(...) as s``)."""
    if s is not None:
        s.set_metadata(**args)


class _Outermost(TraceAnnotation):
    def __enter__(self):
        _local.open = self
        return super().__enter__()

    def __exit__(self, *exc):
        _local.open = None
        return super().__exit__(*exc)


def outermost(name: str, **args):
    """``span``, unless an ``outermost`` span is open on this thread."""
    if not TraceAnnotation.is_enabled() or \
            getattr(_local, "open", None) is not None:
        return OFF
    return _Outermost(name, **args)


def annotate_outermost(**args) -> None:
    """Set ``args`` on this thread's open ``outermost`` span, if any."""
    s = getattr(_local, "open", None)
    if s is not None:
        s.set_metadata(**args)
