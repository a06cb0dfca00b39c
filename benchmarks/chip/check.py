"""The comparisons that decide ``correct``, all of the window's first
round.

Fit steps (site 1's first steps, through the program's own compiled step
and feed, from the round's weights and the optimizer state the site
carried out of set-up) against the plain float32 reference run from the
reference's own decode of the round's downlink, the same carried state
and the same batches:

- ``fit_loss_gap``: the largest ``|loss - ref| / |ref|`` over the steps;
- ``fit_grad_gap``: over the leaves, the largest gap between the norm of
  the program's first gradient (as its optimizer received it) and the
  reference's, over the larger of the reference leaf's norm and the
  median leaf's norm;
- ``fit_change_gap``: the same for the norm of each leaf's change over
  the recorded steps;
- ``fit_grad_median_gap`` / ``fit_change_median_gap``: the median leaf's
  gap, steadier from seed to seed where one small leaf (an MoE router)
  is noisy by nature.

Leaves whose reference first gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of the leaf gaps.
A cell's limits file says which of these numbers it compares.

- ``eval_loss_gap``: ``|loss - ref| / |ref|`` of site 1's evaluate, on
  the reference's decode of the evaluate downlink and the same batch.
- ``uplink_q8_err``: site 1's uplink frame against the delta it encodes
  (the site's fit output less the reference's decode of the round's
  downlink): the largest ``|fp32(q * scale) - delta|`` over the
  reference's window scale ``max|delta| / 127``.  The codec's stated
  bound is half of it.

The fold (the last window round): the round's new global model against
an fp64 fold of the same wire frames done here (each frame's transmitted
int8 values times its fp32 window scales, rounded to fp32, plus the round
base decoded the same way; weights ``n_i / sum n``):

- ``fold_err_over_bound``: the largest ``|out - ref|`` over what
  ``docs/INVARIANTS.md`` §1 allows a coordinate, half an fp32 ULP of the
  output's rounding plus ``C * 2^-42 * sum_i s_i (|d_i| + |b|)``.
"""
from __future__ import annotations

import statistics
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

QCHUNK = 1024
FOLD_REL_ERR = 2.0 ** -42
EXCLUDE_BELOW = 1e-3


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return sorted(p for p, g in ref_grad.items() if g >= EXCLUDE_BELOW * med)


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Sequence[str]) -> Dict[str, float]:
    """Each leaf's ``|prog - ref| / max(ref, median ref)``."""
    med = statistics.median(ref[p] for p in leaves)
    return {p: abs(prog[p] - ref[p]) / max(ref[p], med) for p in leaves}


def fit_readings(rec: Dict, ref: Dict) -> Dict[str, float]:
    """``rec`` and ``ref`` each hold ``losses``, ``grad_norms`` (first
    step) and ``change_norms`` (after the recorded steps).  Beside each
    worst leaf, the median leaf's gap, the steadier of the two."""
    leaves = moving_leaves(ref["grad_norms"])
    g = leaf_gaps(rec["grad_norms"], ref["grad_norms"], leaves)
    c = leaf_gaps(rec["change_norms"], ref["change_norms"], leaves)
    return {"fit_loss_gap": loss_gap(rec["losses"], ref["losses"]),
            "fit_grad_gap": max(g.values()),
            "fit_grad_median_gap": statistics.median(g.values()),
            "fit_change_gap": max(c.values()),
            "fit_change_median_gap": statistics.median(c.values()),
            "_grad_leaf": max(g, key=g.get),
            "_change_leaf": max(c, key=c.get),
            "_excluded": sorted(set(ref["grad_norms"]) - set(leaves))}


def _decode(q: np.ndarray, scales: np.ndarray, lo: int, hi: int
            ) -> np.ndarray:
    """fp32(q * scale) of elements [lo, hi), as fp64; ``lo`` is a
    multiple of the window."""
    out = q[lo:hi].astype(np.float64)
    full = (hi - lo) // QCHUNK * QCHUNK
    c0 = lo // QCHUNK
    out[:full].reshape(-1, QCHUNK)[...] *= scales[
        c0:c0 + full // QCHUNK].astype(np.float64)[:, None]
    if full < hi - lo:
        out[full:] *= np.float64(scales[c0 + full // QCHUNK])
    return out.astype(np.float32).astype(np.float64)


def uplink_reading(delta: np.ndarray, q: np.ndarray, scales: np.ndarray,
                   block: int = 1 << 22) -> float:
    """The largest error of a q8 frame ``(q, scales)`` against the flat
    fp32 ``delta`` it encodes, in units of each window's ``max|delta| /
    127`` (1 for an all-zero window)."""
    n = delta.size
    if q.size != n:
        return float("inf")
    worst = 0.0
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        x = delta[lo:hi].astype(np.float64)
        err = np.abs(_decode(q, scales, lo, hi) - x)
        full = (hi - lo) // QCHUNK * QCHUNK
        w = [np.abs(x[:full]).reshape(-1, QCHUNK).max(axis=1)]
        if full < hi - lo:
            w.append(np.abs(x[full:]).max(keepdims=True))
        s = np.concatenate(w).astype(np.float32) / np.float32(127.0)
        s = np.where(s == 0, np.float32(1.0), s).astype(np.float64)
        e = np.concatenate([err[:full].reshape(-1, QCHUNK).max(axis=1),
                            err[full:].max(keepdims=True)
                            if full < hi - lo else np.empty(0)])
        worst = max(worst, float(np.max(e / s)))
    return worst


def _fold_block(arrivals, s, base, out, precision, lo, hi) -> float:
    b = _decode(base[0], base[1], lo, hi)
    ref = np.zeros(hi - lo, np.float64)
    mag = np.zeros(hi - lo, np.float64)
    got32 = np.zeros(hi - lo, np.float32)
    for si, (_, q, sc) in zip(s, arrivals):
        d = _decode(q, sc, lo, hi)
        ref += si * (d + b)
        mag += abs(si) * (np.abs(d) + np.abs(b))
        if precision == "float32":
            got32 += np.float32(si) * (d.astype(np.float32)
                                       + b.astype(np.float32))
    got = got32 if precision == "float32" else out[lo:hi]
    ulp = np.spacing(np.maximum(np.abs(got), np.abs(ref)).astype(
        np.float32)).astype(np.float64)
    allowed = 0.5 * ulp + len(arrivals) * FOLD_REL_ERR * mag
    return float(np.max(np.abs(got.astype(np.float64) - ref) / allowed))


def fold_reading(arrivals: Sequence[Tuple[float, np.ndarray, np.ndarray]],
                 base: Tuple[np.ndarray, np.ndarray], out: np.ndarray,
                 precision: str = "float64",
                 block: int = 1 << 21) -> float:
    """``arrivals``: ``(weight, int8 delta, fp32 scales)`` per site;
    ``base``: the round base's ``(int8, scales)``; ``out``: the program's
    new global model, flat fp32.  ``precision="float32"`` is the control:
    the same fold with fp32 weights and an fp32 accumulator."""
    if precision not in ("float64", "float32"):
        raise ValueError(precision)
    total = float(sum(w for w, _, _ in arrivals))
    s = [w / total for w, _, _ in arrivals]
    n = out.size
    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = [pool.submit(_fold_block, arrivals, s, base, out, precision,
                             lo, min(lo + block, n))
                 for lo in range(0, n, block)]
        return max(p.result() for p in parts)


def verdict(readings: Dict[str, float], limits: Dict[str, Optional[float]]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number that has a limit, beside it; correct when each is at
    or under its limit."""
    table, ok = {}, True
    for name, lim in limits.items():
        if lim is None:
            continue
        v = readings.get(name)
        if v is None or not np.isfinite(v) or v > lim:
            ok = False
        table[name] = {"value": v, "limit": lim}
    return ok, table
