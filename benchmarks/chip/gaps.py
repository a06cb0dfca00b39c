#!/usr/bin/env python3
"""Where the host's time went in the last traced run, by the program's
spans.

    python3 benchmarks/chip/run.py --workload W --seed N --seconds 51 \\
        --trace 1
    python3 benchmarks/chip/gaps.py

reads the trace that run left in ``program_spans.TRACE_DIR`` and prints
one JSON line:

- ``window_s``, ``rounds``: the traced window;
- ``split``: the window's seconds by ``program_spans.cover`` label (an
  instant goes to the innermost working program span, else to each
  ``bench.*`` span over it, so four sites' fits count four times, else
  to a SuperLink serve or empty pull, else to ``relay_codec``, time no
  span covers), and ``by_layer``, those summed by layer;
- ``device_ops``, ``idle_gaps``: ``run.py``'s breakdown, the ten device
  ops that took most time and the ten longest idle gaps over the chips
  as ``[label, seconds]``, labelled by ``program_spans.label``.

Exits 1 if there is no trace.
"""
import json
import sys
from typing import Dict

import program_spans as ps
import run
import trace_reduce as tr

NAMES = ("bench.fit", "bench.eval", "bench.fold")
#: the first word of a cover label -> its layer in ``by_layer``
LAYERS = {"codec": "codec", "relay": "relay", "superlink": "relay",
          "fold.stage": "fold_staging", "fold.unstage": "fold_staging",
          "fold.kernel": "fold_call", "xfer": "copies", "fit": "fit",
          "eval": "eval", "fold": "bench_fold_only",
          "relay_codec": "uncovered"}


def layer(label: str) -> str:
    head = label.split(":", 1)[0]
    return LAYERS.get(head, LAYERS.get(head.split(".", 1)[0], head))


def report(t: tr.Trace) -> dict:
    """The JSON object above for the reduced trace ``t``."""
    lo, hi = t.window
    split = {k: v * 1e-9 for k, v in ps.cover(t, (lo, hi), NAMES).items()}
    by_layer: Dict[str, float] = {}
    for k, v in split.items():
        by_layer[layer(k)] = by_layer.get(layer(k), 0.0) + v
    return {"window_s": (hi - lo) * 1e-9, "rounds": len(t.rounds),
            "split": dict(sorted(split.items(), key=lambda kv: -kv[1])),
            "by_layer": dict(sorted(by_layer.items(),
                                    key=lambda kv: -kv[1])),
            **run.breakdown(t, lo, hi)}


def main() -> int:
    path = next(ps.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"), None)
    if path is None:
        print(f"no trace under {ps.TRACE_DIR}", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(path))
    t = tr.from_profile(profile)
    t.program = ps.from_profile(profile)
    print(json.dumps(report(t)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
