"""Vectorized aggregation kernels over flat parameter buffers.

Every strategy's per-layer Python loop reduces to one of four kernels over
the (clients x total_params) logical matrix, all cache-blocked on a
``CHUNK``-element window so the float64 accumulator and scratch stay
resident in L2 while the loop streams each client's fp32 view exactly once:

- :func:`weighted_mean` — FedAvg's sum((w_i/W) * x_i).  The per-client
  weight is folded to ``np.float64(w_i / W)`` up front, which both removes
  the final rescale pass and (because the ops and their order match the
  legacy per-layer loop elementwise) keeps the result **bitwise identical**
  to the legacy implementation.
- :class:`StreamingWeightedSum` — the same reduction, but folding each
  client in as it arrives and releasing the payload; peak memory is one
  float64 accumulator instead of every client's update. sum(w_i x_i)/W
  differs from the fold by <=1 ULP of the fp64 accumulator (invisible
  after the fp32 cast).  With ``shards=N`` (or a mesh) the accumulator
  splits into N qchunk-aligned ranges — per-shard Pallas folds, decode/
  reduce overlap, deferred delta bases; see the class docstring.
- :func:`median` / :func:`trimmed_mean` — coordinate-wise robust
  aggregation on a chunk-stacked (n, CHUNK) float64 tile (peak extra
  memory O(n * CHUNK), not O(n * total)).
- :func:`krum_distances` — all pairwise squared L2 distances via a
  chunk-accumulated Gram matrix: ||a-b||^2 = ||a||^2 + ||b||^2 - 2<a,b>,
  one dgemm per chunk instead of the O(n^2) Python loop over full vectors.

Every kernel reads its inputs through the chunked ``f64_chunk(lo, hi,
out)`` protocol, which both :class:`~repro.fl.flat.FlatParams` (raw
buffers) and :class:`~repro.fl.flat.QuantParams` (int8/bf16 compressed
wire payloads) implement.  For quantized inputs the dequantize + scale
(+ delta-base add) is **fused into the per-chunk read**, so accumulators
consume compressed buffers directly — peak extra memory stays one
CHUNK-sized fp64 scratch, never a model-size fp32 copy of the payload.

NB (numpy>=2 / NEP 50): scalar weights MUST be ``np.float64`` — a bare
python float is "weak" and would demote the multiply to the fp32 loop,
silently breaking the exactness guarantee.

Backend dispatch
----------------
Every public kernel takes ``backend="numpy" | "pallas" | None`` (None /
"auto" resolves to :func:`default_backend`: the Pallas path on TPU hosts,
numpy everywhere else — overridable with ``REPRO_AGG_BACKEND`` or
:func:`set_default_backend`).  The contract:

- the numpy path is the reference and the default off-TPU; its arithmetic
  is frozen (the fig. 5 bitwise-repro claim rides on it);
- the Pallas path (:mod:`repro.kernels.agg_reduce`) is fp32-native (the
  chip has no fp64 vector unit) with a compensated accumulator, and stays
  within the error bound of ``docs/INVARIANTS.md`` §1 of the numpy fold:
  <=1 ULP of the fp32 output leaves except under extreme cancellation;
  `tests/test_agg_pallas.py` enforces it across layouts, codecs
  (0xF1/0xF2/0xF3 incl. int8 deltas) and client counts.  The median is
  exact.  Krum's Gram matrix is an fp32 matmul, so its *distances* carry
  an fp32 relative tolerance while the selection and the aggregate stay
  within the fold's bound;
- off-TPU the Pallas kernels run in interpret mode, so CI exercises the
  real kernel bodies on CPU (:mod:`repro.kernels.platform` decides);
- payloads the Pallas kernels cannot express go to the numpy kernels by
  one of the written rules in :data:`FALLBACK_RULES`, and every such call
  is counted (:func:`fallback_counts`) — nothing falls back unseen.
  Fallback is per-call, so a single odd client never aborts a round.
"""
from __future__ import annotations

import collections
import contextlib
import os
import queue
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.fl.flat import FlatParams, Layout, memo_token, np_dtype
from repro.kernels.platform import on_tpu

# 16K elements: chunk fp64 accumulator + scratch = 256 KiB, L2-resident.
# QCHUNK (int8 scale window) divides CHUNK, so quantized reads stay aligned.
CHUNK = 1 << 14

_FLOATS = {"float16", "float32", "float64"}

# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------
BACKENDS = ("numpy", "pallas")
_DEFAULT_BACKEND: Optional[str] = None


def default_backend() -> str:
    """Resolved process default: ``REPRO_AGG_BACKEND`` if set, else
    "pallas" when a TPU is attached, else "numpy"."""
    global _DEFAULT_BACKEND
    if _DEFAULT_BACKEND is None:
        env = os.environ.get("REPRO_AGG_BACKEND", "").strip().lower()
        if env:
            if env not in BACKENDS:
                raise ValueError(
                    f"REPRO_AGG_BACKEND={env!r}; expected one of {BACKENDS}")
            _DEFAULT_BACKEND = env
        else:
            _DEFAULT_BACKEND = "pallas" if on_tpu() else "numpy"
    return _DEFAULT_BACKEND


def set_default_backend(name: Optional[str]) -> None:
    """Override (or with ``None`` re-derive) the process default."""
    global _DEFAULT_BACKEND
    if name is not None and name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; have {BACKENDS}")
    _DEFAULT_BACKEND = name


def resolve_backend(backend: Optional[str]) -> str:
    if backend in (None, "auto"):
        return default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    return backend


# ---------------------------------------------------------------------------
# numpy fallback rules of the Pallas backend (counted, never silent)
# ---------------------------------------------------------------------------
FALLBACK_RULES = {
    "float64": "fp64 payloads — fp64 or mixed-dtype layouts and 0xF4 "
               "partial sums: the chip has no fp64 vector unit, and fp64 "
               "in must stay fp64-exact",
    "no_tile": "payloads without a device tile: SecAgg uint64 shares and "
               "other integer leaves, a delta whose base is not attached, "
               "an int8 range off a scale-window boundary",
    "qchunk": "an int8 scale window that is not a multiple of 128 lanes",
    "mixed": "clients with different codecs or dtypes in one batch call",
    "multi_base": "delta payloads against more than one base in one "
                  "batch call",
}
_FALLBACK_LOCK = threading.Lock()
_FALLBACKS: "collections.Counter[str]" = collections.Counter()  # guarded-by: _FALLBACK_LOCK


def _fallback(reason: str) -> None:
    with _FALLBACK_LOCK:
        _FALLBACKS[reason] += 1


def fallback_counts() -> Dict[str, int]:
    """Process-wide count of Pallas-backend calls that ran on numpy, by
    :data:`FALLBACK_RULES` reason."""
    with _FALLBACK_LOCK:
        return dict(_FALLBACKS)


def reset_fallback_counts() -> None:
    with _FALLBACK_LOCK:
        _FALLBACKS.clear()


def _device_source(fp, lo: int = 0, hi: Optional[int] = None):
    """``(TileSource, None)`` for a payload the Pallas kernels can fold,
    else ``(None, reason)`` — a :data:`FALLBACK_RULES` key."""
    if isinstance(fp, FlatParams) \
            and fp.layout.uniform_dtype in (None, "float64"):
        # raw fp64 frames, and mixed layouts (which read as fp64)
        return None, "float64"
    ts = getattr(fp, "tile_source", None)
    src = ts(lo, hi) if ts is not None else None
    if src is None:
        return None, "no_tile"
    if src.kind == "float" and src.data.dtype == np.float64:
        return None, "float64"
    if src.kind == "q8":
        from repro.kernels import agg_reduce

        if agg_reduce.lanes_for(src.qchunk) is None:
            return None, "qchunk"
    return src, None


def _host_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def resolve_shards(shards: Optional[int], mesh=None) -> int:
    """Shard count for the server aggregation state: an explicit count
    wins; otherwise the mesh's "data" axis size (total device count for
    meshes without one).  0 means single-host (legacy) state."""
    if shards:
        if shards < 0:
            raise ValueError(f"shards must be >= 1, got {shards}")
        return int(shards)
    if mesh is None:
        return 0
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("data", mesh.devices.size))


def _tile_stack(flats: Sequence) -> Optional[Dict[str, Any]]:
    """Stack per-client :class:`~repro.fl.flat.TileSource` adapters into
    the (C, N) host arrays the Pallas kernels consume, or count the
    :data:`FALLBACK_RULES` reason and return ``None`` (numpy path).  A
    delta stack's shared base comes back as fp64 for the caller to add
    (the kernels never see it)."""
    sources = []
    for fp in flats:
        src, reason = _device_source(fp)
        if src is None:
            _fallback(reason)
            return None
        sources.append(src)
    first = sources[0]
    if any(s.kind != first.kind or s.qchunk != first.qchunk
           or s.data.dtype != first.data.dtype for s in sources):
        _fallback("mixed")
        return None
    bases = {id(s.base): s.base for s in sources}
    if len(bases) > 1:
        _fallback("multi_base")
        return None
    base_obj = next(iter(bases.values()))
    q8 = first.kind == "q8"
    return {"data": np.stack([s.data for s in sources]),
            "scales": np.stack([s.scales for s in sources]) if q8 else None,
            "qchunk": first.qchunk,
            "base": base_obj.to_f64() if base_obj is not None else None}


def _scatter_leaves(vec: np.ndarray, layout: Layout,
                    out: FlatParams) -> None:
    """Write a full math vector into ``out`` leaf by leaf, casting to each
    leaf's dtype — the one shared rounding path for every kernel's
    non-uniform (or vector-producing) output."""
    for i, spec in enumerate(layout.leaves):
        out.leaf(i)[...] = vec[spec.eoffset:spec.eoffset + spec.size] \
            .reshape(spec.shape).astype(np_dtype(spec.dtype))


def _vec_to_flat(vec: np.ndarray, layout: Layout) -> FlatParams:
    """fp64 math vector -> FlatParams, with the same per-element rounding
    the numpy kernels apply when writing their output chunks."""
    out = FlatParams.zeros(layout)
    if layout.uniform_dtype in _FLOATS:
        out.math_view()[...] = vec
    else:
        _scatter_leaves(vec, layout, out)
    return out


def weighted_mean(pairs: Sequence[Tuple[FlatParams, float]],
                  layout: Layout, backend: Optional[str] = None,
                  block: Optional[int] = None) -> FlatParams:
    """sum((w_i / W) x_i) over flat buffers -> FlatParams of ``layout``.

    Chunk-outer / client-inner: the fp64 accumulator chunk is reused across
    clients and cast straight into the output buffer, so no total-size fp64
    array is ever materialized.
    """
    out = FlatParams.zeros(layout)
    if layout.total_size == 0 or not pairs:
        return out
    vec = _weighted_mean_pallas(pairs, backend, block)
    if vec is not None:
        return _vec_to_flat(vec, layout)
    uniform = layout.uniform_dtype in _FLOATS
    ovec = out.math_view() if uniform else np.empty(layout.total_size,
                                                    np.float64)
    _weighted_mean_numpy(pairs, ovec)
    if not uniform:
        _scatter_leaves(ovec, layout, out)
    return out


def weighted_mean_f64(pairs: Sequence[Tuple[FlatParams, float]],
                      layout: Layout,
                      backend: Optional[str] = None) -> np.ndarray:
    """The fp64 math vector :func:`weighted_mean` rounds into its output
    leaves — what the on-chip fold is held to against the numpy fold
    (``docs/INVARIANTS.md`` §1).  The Pallas backend does not fall back
    here: payloads it cannot fold raise, so a comparison of the two
    backends never compares numpy with itself."""
    vec = _weighted_mean_pallas(pairs, backend, None)
    if vec is None:
        if resolve_backend(backend) == "pallas":
            raise ValueError("these payloads have no Pallas fold; see "
                             "fallback_counts() for the rule")
        vec = np.empty(layout.total_size, np.float64)
        _weighted_mean_numpy(pairs, vec)
    return vec


def _scaled_weights(pairs) -> List[np.float64]:
    total_w = float(sum(w for _, w in pairs))
    return [np.float64(w / total_w) for _, w in pairs]


def _weighted_mean_pallas(pairs, backend: Optional[str],
                          block: Optional[int]) -> Optional[np.ndarray]:
    if resolve_backend(backend) != "pallas":
        return None
    stack = _tile_stack([fp for fp, _ in pairs])
    if stack is None:
        return None
    from repro.kernels import agg_reduce

    scaled = _scaled_weights(pairs)
    vec = agg_reduce.weighted_sum(
        stack["data"], np.array(scaled, np.float64),
        scales=stack["scales"], qchunk=stack["qchunk"], block=block)
    if stack["base"] is not None:
        # deferred delta base: sum s_i (d_i + b) == sum s_i d_i + S b
        vec += np.float64(sum(scaled)) * stack["base"]
    return vec


def fold_error_bound(pairs: Sequence[Tuple[FlatParams, float]],
                     layout: Layout) -> np.ndarray:
    """Per-coordinate bound on how far the Pallas fold's fp64 vector may
    sit from the numpy fold's (``docs/INVARIANTS.md`` §1):
    ``C * FOLD_REL_ERR * sum_i |s_i| (|d_i| + |b_i|)`` with ``s_i`` the
    normalized weights, ``d_i`` the decoded payloads and ``b_i`` a delta's
    base (0 for a full payload)."""
    from repro.kernels.agg_reduce import FOLD_REL_ERR

    n = layout.total_size
    mag = np.zeros(n, np.float64)
    tmp = np.empty(n, np.float64)
    bases: Dict[str, list] = {}
    for (fp, _), s in zip(pairs, _scaled_weights(pairs)):
        mag += abs(s) * np.abs(fp.decode_chunk(0, n, tmp))
        base = getattr(fp, "base", None)
        if getattr(fp, "is_delta", False) and base is not None:
            bases.setdefault(memo_token(base), [base, 0.0])[1] += abs(s)
    for base, sw in bases.values():
        mag += sw * np.abs(base.to_f64())
    return len(pairs) * FOLD_REL_ERR * mag


def _weighted_mean_numpy(pairs, ovec: np.ndarray) -> None:
    scaled = _scaled_weights(pairs)
    n = ovec.size
    acc = np.empty(CHUNK, np.float64)
    scratch = np.empty(CHUNK, np.float64)
    tmp = np.empty(CHUNK, np.float64)
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        a = acc[:hi - lo]
        x0 = pairs[0][0].f64_chunk(lo, hi, tmp)
        np.multiply(x0, scaled[0], out=a)
        for (fp, _), sw in zip(pairs[1:], scaled[1:]):
            x = fp.f64_chunk(lo, hi, tmp)
            np.multiply(x, sw, out=scratch[:hi - lo])
            a += scratch[:hi - lo]
        ovec[lo:hi] = a


class _DecodePipeline:
    """Decode/reduce overlap for the sharded streaming fold.

    One decoder thread pulls arrivals off a depth-1 job queue, streams
    each shard's range through the payload's ``decode_chunk`` into a
    slot from a small ring of reusable shard-size fp64 buffers, scales
    by the arrival weight, and hands (shard, buffer) to the caller's
    thread, which folds it into the per-shard accumulator — so the codec
    decode of arrival k+1 runs while arrival k is being reduced.  The
    job queue bounds live payload references at two (the one decoding
    plus the one queued: double buffering); the ring bounds decoded-but-
    unfolded data at ``nslots`` shard ranges.

    Ordering: one decoder + FIFO queues keep the (arrival, shard) fold
    order identical to the serial loop, so the result is bitwise equal
    to the non-overlapped fold.  A decoder exception is re-raised on the
    caller's thread at the next submit/drain and kills the pipeline (and
    so the round) — payload validation (shape checks, delta-base attach)
    happens before submit, so this path is reserved for genuinely
    malformed buffers.
    """

    def __init__(self, bounds: Sequence[Tuple[int, int]], nslots: int = 3):
        self._shards = [(si, lo, hi)
                        for si, (lo, hi) in enumerate(bounds) if hi > lo]
        maxm = max((hi - lo for _, lo, hi in self._shards), default=0)
        self._pool: "queue.Queue[np.ndarray]" = queue.Queue()
        for _ in range(nslots):
            self._pool.put(np.empty(maxm, np.float64))
        self._jobs: "queue.Queue" = queue.Queue(maxsize=1)
        self._out: "queue.Queue" = queue.Queue()
        self._failed = False
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="agg-decode", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                self._out.put(None)
                return
            dec, sw = job
            try:
                for si, lo, hi in self._shards:
                    buf = self._pool.get()
                    for a in range(lo, hi, CHUNK):
                        b = min(a + CHUNK, hi)
                        o = buf[a - lo:b - lo]
                        dec(a, b, o)
                        o *= sw     # rounds like multiply-into-scratch
                    self._out.put((si, buf, hi - lo))
            except BaseException as e:  # noqa: BLE001 — forwarded to caller
                self._out.put(e)
                return

    def submit(self, dec, sw: np.float64, fold) -> None:
        if self._failed or self._closed:
            raise RuntimeError("aggregation decode pipeline is closed")
        while True:
            try:
                self._jobs.put_nowait((dec, sw))
                break
            except queue.Full:
                self._fold_next(fold, block=True)
        while self._fold_next(fold, block=False):
            pass

    def _fold_next(self, fold, block: bool) -> bool:
        try:
            item = self._out.get(block=block)
        except queue.Empty:
            return False
        if item is None:            # close sentinel: keep it for drain()
            self._out.put(None)
            return False
        if isinstance(item, BaseException):
            self._failed = True
            raise item
        si, buf, m = item
        try:
            fold(si, buf, m)
        finally:
            self._pool.put(buf)
        return True

    def drain(self, fold) -> None:
        """Close the job stream and fold everything still in flight."""
        if self._failed:
            raise RuntimeError("aggregation decode pipeline failed")
        if not self._closed:
            self._closed = True
            while True:
                # a plain blocking put could deadlock: the decoder may be
                # waiting on a ring slot only this thread can return
                try:
                    self._jobs.put_nowait(None)
                    break
                except queue.Full:
                    self._fold_next(fold, block=True)
        while True:
            item = self._out.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                self._failed = True
                self._thread.join(timeout=10.0)
                raise item
            si, buf, m = item
            fold(si, buf, m)
            self._pool.put(buf)
        self._thread.join(timeout=10.0)


class StreamingWeightedSum:
    """Incremental sum(w_i x_i); finalize() divides by W and casts.

    Two modes:

    **Single-host (default, ``shards=None``)** — the frozen reference
    semantics: one fp64 accumulator, every arrival folded through
    ``f64_chunk`` (delta payloads reconstructed per arrival).  On the
    Pallas backend each arrival is one fused dequantize+scale+accumulate
    kernel launch into a compensated fp32 ``(hi, lo)`` device pair, with
    delta bases deferred to finalize; the padded device pair is **cached
    across arrivals keyed by the round's tile geometry** (the common,
    codec-homogeneous case after PR 3 negotiation keeps one padded
    pair and one async dispatch chain for the whole round), and only a
    mixed arrival with different geometry pays the retire + re-pad.

    **Sharded (``shards=N`` or ``mesh=...``)** — the round's accumulator
    splits into N contiguous qchunk-aligned ranges
    (:func:`repro.sharding.shard_bounds` over the mesh "data" axis), so
    per-shard memory is ~1/N of the single-host fp64 footprint and each
    range folds through its own per-shard Pallas call (pinned to the
    matching mesh device when a mesh is given); the all-gather into the
    output buffer happens once, at :meth:`finalize`.  Delta payloads are
    folded **base-deferred**: sum_k w_k (d_k + b) == sum_k w_k d_k +
    W b, so the fold streams only the compressed delta and the fp64 base
    is read once per round at finalize instead of once per arrival —
    measurably faster single-core and the enabler for both overlap
    modes.  Decode/reduce overlap: on the numpy backend a decoder thread
    (:class:`_DecodePipeline`) decodes arrival k+1 while the caller's
    thread reduces arrival k (auto-enabled on multi-core hosts;
    ``overlap`` forces it); on the Pallas backend the same overlap falls
    out of async dispatch — ``out_padded`` accumulator chaining means
    kernel launches return before the device folds, so the host decodes
    the next arrival while shard kernels run.

    Numerics: the sharded fold is bitwise-invariant across shard counts
    and overlap on/off (pure elementwise ops in arrival order, on either
    backend).  The Pallas fold stays within ``docs/INVARIANTS.md`` §1 of
    the numpy fold.  The numpy sharded fold is
    bitwise-equal to the single-host mode for non-delta payloads, and
    within ~1 ULP of the fp64 accumulator for delta payloads (the
    deferred base changes the summation grouping) — the same order of
    difference the arrival-order fold already carries vs the deferred
    batch kernel, invisible after the fp32 output cast.
    """

    def __init__(self, layout: Layout, backend: Optional[str] = None,
                 block: Optional[int] = None, *,
                 shards: Optional[int] = None, mesh=None,
                 overlap: Optional[bool] = None):
        self.layout = layout
        self.backend = resolve_backend(backend)
        self._block = block
        self._scratch = np.empty(min(CHUNK, max(layout.total_size, 1)),
                                 np.float64)
        self._tmp = np.empty_like(self._scratch)
        self.total_w = 0.0
        self.count = 0
        self.shards = resolve_shards(shards, mesh)
        self.mesh = mesh
        # single-host padded device (hi, lo) pair (geometry-keyed cache)
        self._acc_padded = None
        self._pad_geom: Optional[Tuple[int, int, int]] = None
        # deferred delta bases: token -> [base object, summed weight].
        # Sharded dense deltas, Pallas dense deltas and sparse deltas fold
        # base-deferred: sum_k w_k (d_k + b) == sum_k w_k d_k + W b.
        # Tokens are process-unique (never recycled, unlike id()), and
        # the entry holds the base object, so no entry can alias another
        self._deferred: Dict[str, list] = {}
        if self.shards:
            from repro.fl.flat import QCHUNK
            from repro.sharding import shard_bounds

            self._bounds = shard_bounds(layout.total_size, self.shards,
                                        align=QCHUNK)
            self._sacc: List[Optional[np.ndarray]] = [
                np.zeros(hi - lo, np.float64) for lo, hi in self._bounds]
            self._spad: List[Any] = [None] * self.shards
            self._sgeom: List[Optional[Tuple[int, int, int]]] = \
                [None] * self.shards
            self._devices = (list(mesh.devices.flat)
                             if mesh is not None else None)
            use_pipe = (self.backend == "numpy" and layout.total_size > 0
                        and (overlap if overlap is not None
                             else _host_cores() > 1))
            self._pipe = _DecodePipeline(self._bounds) if use_pipe else None
            self._acc = None
        else:
            self._acc = np.zeros(layout.total_size, np.float64)
            self._pipe = None
        self.overlap = self._pipe is not None

    # ------------------------------------------------------------ shared
    def add(self, fp: FlatParams, w: float) -> None:
        if getattr(fp, "is_sparse", False):
            # 0xF5 structured-sparse delta: O(nnz) scatter fold — routed
            # here so edge pre-reduce and FedBuff call sites fold sparse
            # payloads without knowing about them
            self.add_sparse(fp, w)
            return
        if self.shards:
            self._add_sharded(fp, w)
            self.total_w += float(w)
            self.count += 1
            return
        if self.backend == "pallas" and self.layout.total_size \
                and self._add_pallas(fp, w):
            self.total_w += float(w)
            self.count += 1
            return
        sw = np.float64(w)
        n = self.layout.total_size
        acc = self._acc_vec()
        for lo in range(0, n, CHUNK):
            hi = min(lo + CHUNK, n)
            x = fp.f64_chunk(lo, hi, self._tmp)
            np.multiply(x, sw, out=self._scratch[:hi - lo])
            acc[lo:hi] += self._scratch[:hi - lo]
        self.total_w += float(w)
        self.count += 1

    def add_partial(self, ps, scale: float = 1.0) -> None:
        """Fold a pre-reduced subtree sum (:class:`~repro.fl.flat
        .PartialSum`): ``acc += scale * S_e`` — no per-client weight
        multiply, the edge already applied them.  The edge computed
        ``S_e`` with this class's own chunk arithmetic, so root-folding
        partials continues the flat fold's accumulation exactly (bitwise
        for a single edge on any data; regrouped-sum ULP otherwise).
        ``scale`` (async staleness discount) also multiplies the
        contributed weight: ``total_w += scale * W_e``."""
        sw = np.float64(scale)
        if self.backend == "pallas":
            _fallback("float64")        # a raw fp64 subtree sum
        if self.shards:
            if self._pipe is not None:
                # ride the decode pipeline so the (arrival, shard) fold
                # order stays the serial order
                self._pipe.submit(ps.decode_chunk, sw, self._fold_item)
            else:
                for si, (lo, hi) in enumerate(self._bounds):
                    if hi <= lo:
                        continue
                    acc = self._shard_acc(si)
                    for a in range(lo, hi, CHUNK):
                        b = min(a + CHUNK, hi)
                        x = ps.decode_chunk(a, b, self._tmp)
                        np.multiply(x, sw, out=self._scratch[:b - a])
                        acc[a - lo:b - lo] += self._scratch[:b - a]
        else:
            acc = self._acc_vec()
            n = self.layout.total_size
            for lo in range(0, n, CHUNK):
                hi = min(lo + CHUNK, n)
                x = ps.f64_chunk(lo, hi, self._tmp)
                np.multiply(x, sw, out=self._scratch[:hi - lo])
                acc[lo:hi] += self._scratch[:hi - lo]
        self.total_w += float(scale) * float(ps.total_w)
        self.count += int(ps.count)

    def add_sparse(self, sp, w: float) -> None:
        """Fold a structured-sparse delta (0xF5,
        :class:`~repro.fl.flat.SparseDelta`): ``acc[traveled] += w *
        dequant(values)`` — O(nnz) per arrival, never a model-size
        densify.  The round base is **deferred** (recorded at its summed
        weight and applied chunk-streamed at :meth:`finalize` /
        :meth:`raw_sum`), exactly like the sharded dense-delta fold.  On
        the Pallas backend the dequantize+scale chain runs as a jitted
        device graph (``kernels.agg_reduce.scatter_wsum``, bitwise the
        numpy chain); the scatter-add itself stays host-side — unique
        indices, so there is no reduction-order ambiguity."""
        self._record_base(sp, w)
        sw = np.float64(w)
        if self.shards:
            if self._pipe is not None:
                # keep the (arrival, shard) fold order serial: queued
                # dense decodes fold before this sparse arrival
                self._pipe.drain(self._fold_item)
            for si, (lo, hi) in enumerate(self._bounds):
                if hi <= lo:
                    continue
                self._scatter_spans(sp, lo, hi, self._shard_acc(si), sw)
        else:
            self._scatter_spans(sp, 0, self.layout.total_size,
                                self._acc_vec(), sw)
        self.total_w += float(w)
        self.count += 1

    def _scatter_spans(self, sp, lo: int, hi: int, acc: np.ndarray,
                       sw: np.float64) -> None:
        """Scatter ``sp``'s traveled coordinates inside [lo, hi) into
        ``acc`` (indexed relative to ``lo``), sub-chunked to the scratch
        size so a whole-model adapter range never allocates O(range)."""
        use_dev = self.backend == "pallas" and self.layout.total_size
        if use_dev:
            from repro.kernels import agg_reduce
        for p0, p1, dest in sp.iter_spans(lo, hi):
            for q0 in range(p0, p1, CHUNK):
                q1 = min(q0 + CHUNK, p1)
                if isinstance(dest, slice):
                    d = slice(dest.start + (q0 - p0),
                              dest.start + (q1 - p0))
                else:
                    d = dest[q0 - p0:q1 - p0]
                if use_dev:
                    agg_reduce.scatter_wsum(
                        acc, d, sp.values[q0:q1], float(sw),
                        scales=sp.scales, qchunk=sp.qchunk, pos0=q0)
                else:
                    buf = sp.dequant_packed(q0, q1, self._tmp)
                    np.multiply(buf, sw, out=self._scratch[:q1 - q0])
                    acc[d] += self._scratch[:q1 - q0]

    def _apply_deferred(self, acc: np.ndarray, denom: float) -> None:
        """Add every deferred round base at ``summed_weight / denom``,
        chunk-streamed in canonical token order (arrival-order
        invariant; no model-size fp64 base materializes)."""
        if not self._deferred:
            return
        defs = [(self._deferred[tok][0],
                 np.float64(self._deferred[tok][1] / denom))
                for tok in sorted(self._deferred)]
        n = acc.size
        for lo in range(0, n, CHUNK):
            hi = min(lo + CHUNK, n)
            for bobj, bw in defs:
                x = bobj.f64_chunk(lo, hi, self._tmp)
                np.multiply(x, bw, out=self._scratch[:hi - lo])
                acc[lo:hi] += self._scratch[:hi - lo]
        self._deferred.clear()

    def raw_sum(self) -> np.ndarray:
        """The unscaled fp64 accumulator ``sum_i w_i x_i`` — what an edge
        aggregator frames as a 0xF4 partial payload instead of calling
        :meth:`finalize`.  Ends the fold: the returned vector IS the
        accumulator (no copy), so neither :meth:`add` nor
        :meth:`finalize` may be called afterwards.  Single-host mode
        only (edges pre-reduce locally; sharding is root-side state).
        Deferred sparse-delta bases are applied here at their SUMMED
        weight (S_e = sum w·d + W_b·b), so the 0xF4 partial an edge
        frames from sparse arrivals is the true subtree sum."""
        if self.shards:
            raise ValueError(
                "raw_sum() is single-host only: edge pre-reduction keeps "
                "one local accumulator, sharded state is for the root")
        acc = self._acc_vec()
        self._apply_deferred(acc, 1.0)
        return acc

    def finalize(self) -> FlatParams:
        if self.shards:
            return self._finalize_sharded()
        acc = self._acc_vec()
        acc *= np.float64(1.0 / self.total_w)
        self._apply_deferred(acc, self.total_w)
        out = FlatParams.zeros(self.layout)
        _scatter_leaves(acc, self.layout, out)
        return out

    def per_shard_acc_bytes(self) -> int:
        """Largest per-shard fp64 accumulator footprint, in bytes."""
        if not self.shards:
            return self.layout.total_size * 8
        return max((hi - lo for lo, hi in self._bounds), default=0) * 8

    def _geometry(self, src, n: int) -> Tuple[int, int, int]:
        from repro.kernels import agg_reduce

        return agg_reduce.geometry(
            n, 1, src.qchunk if src.kind == "q8" else None, self._block)

    # ------------------------------------------------- single-host mode
    def _acc_vec(self) -> np.ndarray:
        """The unpadded single-host accumulator; a live padded device
        pair (geometry cache) is materialized in fp64 and retired first —
        the per-arrival pad+slice fallback for mixed arrivals."""
        if self._acc_padded is not None:
            from repro.kernels import agg_reduce

            self._acc = agg_reduce.pair_to_host(self._acc_padded,
                                                self.layout.total_size)
            self._acc_padded = None
            self._pad_geom = None
        return self._acc

    def _add_pallas(self, fp, w: float) -> bool:
        src, reason = _device_source(fp)
        if src is None:
            _fallback(reason)
            return False
        from repro.kernels import agg_reduce

        self._record_base(fp, w)        # base deferred to finalize
        geom = self._geometry(src, self.layout.total_size)
        if self._pad_geom is not None and self._pad_geom != geom:
            self._acc_vec()         # mixed arrival: retire, re-pad below
        acc = self._acc_padded if self._pad_geom == geom else self._acc
        self._acc_padded = agg_reduce.weighted_sum(
            src.data[None, :], np.array([w], np.float64),
            scales=None if src.scales is None else src.scales[None, :],
            qchunk=src.qchunk, acc=acc, block=self._block, out_padded=True)
        self._pad_geom = geom
        self._acc = None
        return True

    # ------------------------------------------------------ sharded mode
    @staticmethod
    def _decoder(fp):
        dec = getattr(fp, "decode_chunk", None)
        if dec is None:
            if getattr(fp, "is_delta", False):
                raise TypeError(
                    "sharded fold needs decode_chunk() on delta payloads "
                    f"(got {type(fp).__name__})")
            dec = fp.f64_chunk
        return dec

    def _record_base(self, fp, w: float) -> None:
        if not getattr(fp, "is_delta", False):
            return
        base = getattr(fp, "base", None)
        if base is None:
            raise ValueError(
                "delta-encoded payload needs its round base attached "
                "(QuantParams.base / SparseDelta.base) before it can "
                "be folded")
        tok = memo_token(base)
        ent = self._deferred.get(tok)
        if ent is None:
            self._deferred[tok] = [base, float(w)]
        else:
            ent[1] += float(w)

    def _shard_acc(self, si: int) -> np.ndarray:
        if self._spad[si] is not None:
            from repro.kernels import agg_reduce

            lo, hi = self._bounds[si]
            self._sacc[si] = agg_reduce.pair_to_host(self._spad[si], hi - lo)
            self._spad[si] = None
            self._sgeom[si] = None
        return self._sacc[si]

    def _fold_item(self, si: int, buf: np.ndarray, m: int) -> None:
        self._sacc[si] += buf[:m]

    def _add_sharded(self, fp, w: float) -> None:
        self._record_base(fp, w)
        if self.backend == "pallas" and self.layout.total_size \
                and self._add_sharded_pallas(fp, w):
            return
        dec = self._decoder(fp)
        sw = np.float64(w)
        if self._pipe is not None:
            self._pipe.submit(dec, sw, self._fold_item)
            return
        for si, (lo, hi) in enumerate(self._bounds):
            if hi <= lo:
                continue
            acc = self._shard_acc(si)
            for a in range(lo, hi, CHUNK):
                b = min(a + CHUNK, hi)
                x = dec(a, b, self._tmp)
                np.multiply(x, sw, out=self._scratch[:b - a])
                acc[a - lo:b - lo] += self._scratch[:b - a]

    def _add_sharded_pallas(self, fp, w: float) -> bool:
        live = [(si, lo, hi)
                for si, (lo, hi) in enumerate(self._bounds) if hi > lo]
        sources = []
        for _, lo, hi in live:
            src, reason = _device_source(fp, lo, hi)
            if src is None:
                _fallback(reason)
                return False
            sources.append(src)
        from repro.kernels import agg_reduce

        wts = np.array([w], np.float64)
        for (si, lo, hi), src in zip(live, sources):
            geom = self._geometry(src, hi - lo)
            if self._sgeom[si] is not None and self._sgeom[si] != geom:
                self._shard_acc(si)
            acc = self._spad[si] if self._sgeom[si] == geom \
                else self._sacc[si]
            if self._devices:
                import jax

                ctx = jax.default_device(
                    self._devices[si % len(self._devices)])
            else:
                ctx = contextlib.nullcontext()
            with ctx:
                # the base was recorded by _add_sharded: deferred
                self._spad[si] = agg_reduce.weighted_sum(
                    src.data[None, :], wts,
                    scales=None if src.scales is None
                    else src.scales[None, :],
                    qchunk=src.qchunk, acc=acc, block=self._block,
                    out_padded=True)
            self._sgeom[si] = geom
            self._sacc[si] = None
        return True

    def shard_devices(self) -> List[Any]:
        """The device holding each live shard's padded accumulator
        (``None`` for a shard whose accumulator is on the host)."""
        return [None if p is None else next(iter(p[0].devices()))
                for p in self._spad]

    def _finalize_sharded(self) -> FlatParams:
        if self._pipe is not None:
            self._pipe.drain(self._fold_item)
        inv = np.float64(1.0 / self.total_w)
        # canonical token order: the deferred-base add is independent of
        # which client's delta arrived first
        defs = [(self._deferred[tok][0],
                 np.float64(self._deferred[tok][1] / self.total_w))
                for tok in sorted(self._deferred)]
        out = FlatParams.zeros(self.layout)
        n = self.layout.total_size
        uniform = self.layout.uniform_dtype in _FLOATS
        ovec = out.math_view() if uniform else np.empty(n, np.float64)
        # the one all-gather: each shard's acc/W (+ deferred (w_b/W) b,
        # streamed chunk-wise so no model-size fp64 base materializes)
        # lands in the output buffer
        for si, (lo, hi) in enumerate(self._bounds):
            if hi <= lo:
                continue
            a = self._shard_acc(si)
            a *= inv
            for c0 in range(lo, hi, CHUNK):
                c1 = min(c0 + CHUNK, hi)
                seg = a[c0 - lo:c1 - lo]
                for bobj, bw in defs:
                    x = bobj.f64_chunk(c0, c1, self._tmp)
                    np.multiply(x, bw, out=self._scratch[:c1 - c0])
                    seg += self._scratch[:c1 - c0]
                ovec[c0:c1] = seg
        if not uniform:
            _scatter_leaves(ovec, self.layout, out)
        return out


def _rowstack(flats: Sequence[FlatParams], lo: int, hi: int,
              m: np.ndarray) -> np.ndarray:
    tile = m[:len(flats), :hi - lo]
    for i, fp in enumerate(flats):
        fp.f64_chunk(lo, hi, tile[i])
    return tile


def _sorted_reduce_pallas(flats, layout, kind: str, trim_k: int,
                          block: Optional[int]) -> Optional[FlatParams]:
    """Shared Pallas branch of the sort-based reductions; ``None`` means
    "fall back to numpy" (counted by :func:`_tile_stack`)."""
    stack = _tile_stack(flats)
    if stack is None:
        return None
    from repro.kernels import agg_reduce

    vec = agg_reduce.sort_reduce(
        stack["data"], kind=kind, trim_k=trim_k, scales=stack["scales"],
        qchunk=stack["qchunk"], base=stack["base"], block=block)
    if kind == "trim_sum":
        # numpy's np.mean = sum of rows, then one true divide — doing the
        # divide host-side keeps the rounding identical
        vec /= len(flats) - 2 * trim_k
    return _vec_to_flat(vec, layout)


def median(flats: Sequence[FlatParams], layout: Layout,
           backend: Optional[str] = None,
           block: Optional[int] = None) -> FlatParams:
    """Coordinate-wise median, chunk-stacked."""
    if layout.total_size and flats \
            and resolve_backend(backend) == "pallas":
        out = _sorted_reduce_pallas(flats, layout, "median", 0, block)
        if out is not None:
            return out
    return _coordinatewise(flats, layout,
                           lambda t: np.median(t, axis=0, overwrite_input=True))


def trimmed_mean(flats: Sequence[FlatParams], layout: Layout,
                 k: int, backend: Optional[str] = None,
                 block: Optional[int] = None) -> FlatParams:
    """Mean after trimming the k smallest/largest values per coordinate."""
    n = len(flats)
    if layout.total_size and flats \
            and resolve_backend(backend) == "pallas":
        k_eff = k if n > 2 * k else 0
        out = _sorted_reduce_pallas(flats, layout, "trim_sum", k_eff, block)
        if out is not None:
            return out

    def reduce(tile: np.ndarray) -> np.ndarray:
        tile.sort(axis=0)
        sl = tile[k:n - k] if n > 2 * k else tile
        return np.mean(sl, axis=0)

    return _coordinatewise(flats, layout, reduce)


def _coordinatewise(flats, layout, reduce_fn) -> FlatParams:
    out = FlatParams.zeros(layout)
    n = layout.total_size
    if n == 0 or not flats:
        return out
    uniform = layout.uniform_dtype in _FLOATS
    ovec = out.math_view() if uniform else np.empty(n, np.float64)
    m = np.empty((len(flats), CHUNK), np.float64)
    for lo in range(0, n, CHUNK):
        hi = min(lo + CHUNK, n)
        ovec[lo:hi] = reduce_fn(_rowstack(flats, lo, hi, m))
    if not uniform:
        _scatter_leaves(ovec, layout, out)
    return out


def krum_distances(flats: Sequence[FlatParams], layout: Layout,
                   backend: Optional[str] = None,
                   block: Optional[int] = None) -> np.ndarray:
    """(n, n) matrix of pairwise squared L2 distances.

    Accumulates the Gram matrix G += X_c X_c^T one (n, CHUNK) fp64 tile at
    a time, then expands ||a-b||^2 = ||a||^2 + ||b||^2 - 2<a,b>.  Each tile
    is centered on its first row before the dgemm — pairwise distances are
    translation-invariant, and removing the large common component (late
    rounds: client updates nearly identical, norms huge) keeps the
    expansion from cancelling catastrophically.  Clamped at zero for the
    residual rounding.
    """
    n_clients = len(flats)
    if layout.total_size and flats \
            and resolve_backend(backend) == "pallas":
        stack = _tile_stack(flats)
        if stack is not None:
            from repro.kernels import agg_reduce

            # a shared delta base cancels in the row-0 centering
            G = agg_reduce.gram(
                stack["data"], scales=stack["scales"],
                qchunk=stack["qchunk"], block=block)
            sq = np.diag(G).copy()
            D = sq[:, None] + sq[None, :] - 2.0 * G
            np.maximum(D, 0.0, out=D)
            return D
    G = np.zeros((n_clients, n_clients), np.float64)
    m = np.empty((n_clients, CHUNK), np.float64)
    ref = np.empty(CHUNK, np.float64)
    total = layout.total_size
    for lo in range(0, total, CHUNK):
        hi = min(lo + CHUNK, total)
        tile = _rowstack(flats, lo, hi, m)
        np.copyto(ref[:hi - lo], tile[0])
        tile -= ref[:hi - lo]
        G += tile @ tile.T
    sq = np.diag(G).copy()
    D = sq[:, None] + sq[None, :] - 2.0 * G
    np.maximum(D, 0.0, out=D)
    return D


def krum_scores(D: np.ndarray, num_byzantine: int) -> np.ndarray:
    """Multi-Krum scores: per client, the sum of its n-f-2 smallest
    distances to other clients (Blanchard et al. 2017)."""
    n = D.shape[0]
    f = min(num_byzantine, max(0, (n - 3) // 2))
    D = D.copy()
    np.fill_diagonal(D, np.inf)
    D.sort(axis=1)
    m = max(n - f - 2, 1)
    return D[:, :m].sum(axis=1)


def wrapping_sum_u64(flats: Sequence[FlatParams],
                     layout: Layout) -> np.ndarray:
    """Mod-2^64 sum of uint64 flat buffers (SecAgg mask cancellation)."""
    acc = np.zeros(layout.total_size, np.uint64)
    for fp in flats:
        acc += fp.math_view()
    return acc
