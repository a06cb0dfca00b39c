"""What a per-layer metric reader is handed, and the reader registry.

Each per-layer metric named in ``BENCHMARK.json`` is a module
``metrics/<name>.py`` with ``read(ctx) -> float | None``.  A reader that
finds nothing to read returns ``None`` and the metric is left out.
"""
from __future__ import annotations

import importlib.util
import pathlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr

METRICS = pathlib.Path(__file__).resolve().parent / "metrics"
NS = 1e-9


@dataclass
class Context:
    trace: tr.Trace
    cfg: Dict                     # configuration file
    mix: Dict                     # traffic file
    peaks: Dict[str, float]       # flops.peaks(device_kind)
    fit_flops_per_round: float    # train steps of every site
    eval_flops_per_round: float   # eval forwards of every site
    chips: int = 1                # the cell's; each step runs on all

    @property
    def window(self) -> Optional[Tuple[int, int]]:
        return self.trace.window

    @property
    def rounds(self) -> int:
        return len(self.trace.rounds)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * NS

    def spans(self, name: str) -> List[tr.Event]:
        lo, hi = self.window
        return tr.in_window(self.trace.spans.get(name, []), lo, hi)

    def span_s_per_round(self, name: str) -> Optional[float]:
        evs = self.spans(name)
        if not evs or not self.rounds:
            return None
        return sum(e.end - e.start for e in evs) * NS / self.rounds

    def device_events(self, kind: str, match) -> List[tr.Event]:
        """Op (``kind="ops"``) or program (``"modules"``) events of every
        device that start inside the window and satisfy ``match``."""
        lo, hi = self.window
        out = []
        for evs in getattr(self.trace, kind).values():
            out += [e for e in tr.in_window(evs, lo, hi) if match(e)]
        return out


def read(name: str, ctx: Context) -> Optional[float]:
    if ctx.window is None:
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)
