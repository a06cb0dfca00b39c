"""Pallas TPU kernels for flat-buffer FL aggregation (fp32-native).

The numpy kernels in :mod:`repro.fl.agg_kernels` stream every client's
payload through an fp64 accumulator; they are the reference and the
off-TPU path.  A TPU has no fp64 vector unit, so the kernels here run in
fp32 only — nothing traces under x64 — and keep fp64-grade accuracy with
error-free transformations.  The wire decode is **fused into the tile
read**:

- :func:`weighted_sum` — FedAvg's ``sum(w_i * x_i)``, optionally
  continuing a running accumulator (the streaming arrival-order fold).
  The accumulator is a compensated fp32 pair ``(hi, lo)``: ``x`` is split
  into 12-bit halves by a bit mask and ``w`` arrives as four 12-bit fp32
  words, so ``x * w`` is a sum of eight *exact* partial products, folded
  with TwoSum.  The fp64 value ``hi + lo`` is formed on the host only when
  the fold is read.  No rounded product ever feeds an add, so FMA
  contraction (which XLA:CPU applies per fusion) cannot change a bit.
- :func:`sort_reduce` — coordinate-wise median / trimmed sum.  A Batcher
  sorting network of min/max over the client rows sorts each tile; the
  median returns the two middle rows (the host applies numpy's own fp64
  median arithmetic), the trimmed sum returns a compensated pair.
- :func:`gram` — the Krum Gram matrix of the row-0-centered client stack,
  one fp32 MXU matmul per tile row, summed per grid step; the host adds
  the per-step partials in fp64.
- :func:`scatter_wsum` — the 0xF5 sparse fold: the device dequantizes
  ``f32(q * scale)``, the host multiplies by the fp64 weight and scatters.

Layout.  A client's (N,) vector is padded to ``rows * lanes`` and viewed
as (rows, lanes); the kernels take the stacked (C, rows, lanes) array in
(C, tr, lanes) blocks, so the last two block dimensions obey the TPU's
(8, 128) tiling rule (``tr`` is a multiple of 32, the int8 sublane packing,
or all the rows).  ``lanes`` divides the int8 scale window ``qchunk``, so
every row has one scale: the scales travel as a (C, rows, 1) array.

Delta payloads are never reconstructed here: a delta's shared round base
is added by the caller at finalize (``sum w_k (d_k + b) == sum w_k d_k +
W b``) — see ``StreamingWeightedSum``.

Error bound against the numpy fp64 fold: ``docs/INVARIANTS.md`` §1.  On a
TPU host the kernels compile; everywhere else they run the same bodies in
interpret mode (:mod:`repro.kernels.platform`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

DEFAULT_QCHUNK = 1024
#: widest tile row; an int8 payload's row width divides its scale window
LANES = 1024
#: VMEM budget of one grid step's client stack, as fp32
_TILE_BYTES = 1 << 20
#: ... and of one fp32 (rows, lanes) tile: the kernels hold ~10 such
#: temporaries next to the double-buffered operands, in 16 MiB of VMEM
_ACC_TILE_BYTES = 1 << 19
#: int8 packs (32, 128) tiles: partial blocks stay a multiple of this
_ROW_ALIGN = 32
#: Krum unrolls one matmul per tile row
_GRAM_ROWS = 32
#: clears the low 12 mantissa bits: x -> its 12-bit high half
_HI_MASK = np.int32(-4096)
#: per-client error of :func:`weighted_sum` against the fp64 fold, as a
#: multiple of ``sum_c |w_c x_c|`` (``docs/INVARIANTS.md`` §1)
FOLD_REL_ERR = 2.0 ** -42

#: (lanes, rows per block, padded rows) — one fold's tiling
Geometry = Tuple[int, int, int]


def lanes_for(qchunk: Optional[int]) -> Optional[int]:
    """Row width for a payload: ``LANES`` for float payloads; for int8 a
    lane-aligned divisor of the scale window (one scale per row), or
    ``None`` when the window is not a multiple of 128."""
    if not qchunk:
        return LANES
    lanes = math.gcd(int(qchunk), LANES)
    return lanes if lanes % 128 == 0 else None


def geometry(n: int, clients: int = 1, qchunk: Optional[int] = None,
             block: Optional[int] = None, max_rows: int = 0) -> Geometry:
    """Tiling of an (n,)-element fold over ``clients`` rows.  ``block``
    (elements per grid step) is rounded to the tiling rule."""
    lanes = lanes_for(qchunk)
    if lanes is None:
        raise ValueError(f"int8 scale window {qchunk} is not a multiple "
                         f"of 128 lanes")
    rows = max(-(-n // lanes), 1)
    tr = min(block // lanes if block
             else _TILE_BYTES // (4 * lanes * max(clients, 1)),
             _ACC_TILE_BYTES // (4 * lanes))
    if max_rows:
        tr = min(tr, max_rows)
    tr = -(-max(tr, 1) // _ROW_ALIGN) * _ROW_ALIGN
    if rows <= tr:
        return lanes, rows, rows        # one block: full-dim, always legal
    return lanes, tr, -(-rows // tr) * tr


def _as_rows(a: np.ndarray, geom: Geometry, fill=0) -> np.ndarray:
    """Pad the last axis to ``rows * lanes`` and view it as (rows, lanes)."""
    lanes, _, rows = geom
    total = rows * lanes
    if a.shape[-1] != total:
        out = np.full(a.shape[:-1] + (total,), fill, a.dtype)
        out[..., :a.shape[-1]] = a
        a = out
    return a.reshape(a.shape[:-1] + (rows, lanes))


def _data_rows(data: np.ndarray, geom: Geometry) -> np.ndarray:
    if data.dtype == np.float64:
        raise TypeError("fp64 payloads have no on-chip fold: the dispatch "
                        "layer routes them to numpy (see agg_kernels)")
    if data.dtype == np.float16:
        data = data.astype(np.float32)  # exact; Mosaic has no f16 VPU path
    return _as_rows(data, geom)


def _scale_rows(scales: np.ndarray, qchunk: int, geom: Geometry):
    """(C, nchunks) per-window scales -> one scale per tile row."""
    lanes, _, rows = geom
    per_row = np.repeat(np.asarray(scales, np.float32), qchunk // lanes,
                        axis=-1)[..., :rows]
    return _as_rows(per_row, (1, 0, geom[2]), fill=1)   # (C, rows, 1)


def _split_weights(weights: np.ndarray) -> np.ndarray:
    """fp64 weights -> (C, 4) fp32 words of <= 12 significant bits each,
    largest first, summing to ``w`` within 2^-48 relative."""
    w = np.asarray(weights, np.float64)
    wh = w.astype(np.float32)
    wl = (w - wh.astype(np.float64)).astype(np.float32)
    words = []
    for v in (wh, wl):
        h = (v.view(np.int32) & _HI_MASK).view(np.float32)
        words += [h, v - h]
    return np.stack(words, axis=1)


def _block_specs(geom: Geometry, clients: int, q8: bool):
    lanes, tr, _ = geom
    specs = [pl.BlockSpec((clients, tr, lanes), lambda i: (0, i, 0))]
    if q8:
        specs.append(pl.BlockSpec((clients, tr, 1), lambda i: (0, i, 0)))
    return specs


def _decode(d_ref, s_ref, c: int):
    """Client ``c``'s (tr, lanes) tile in fp32.  int8 dequantizes to
    ``f32(q * scale)`` — bitwise the numpy ``_dequant_q8`` chain — as the
    one rounded sum of two exact products (7-bit ``q`` times the 12-bit
    halves of the scale), which no FMA contraction can alter."""
    x = d_ref[c].astype(jnp.float32)
    if s_ref is not None:
        sh, sl = _split(s_ref[c])
        x = x * sh + x * sl
    return x


# ---------------------------------------------------------------------------
# error-free transformations (fp32)
# ---------------------------------------------------------------------------
def _split(x):
    """x == hi + lo exactly, each with <= 12 significant bits.  A bit mask
    instead of Veltkamp's ``x * 4097`` split, which FMA contraction breaks."""
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    hi = jax.lax.bitcast_convert_type(xi & _HI_MASK, jnp.float32)
    return hi, x - hi


def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


# ---------------------------------------------------------------------------
# fused weighted sum (FedAvg / streaming fold)
# ---------------------------------------------------------------------------
def _wsum_kernel(w_ref, *refs, clients: int, q8: bool, has_acc: bool):
    it = iter(refs)
    acc = (next(it), next(it)) if has_acc else None
    d_ref = next(it)
    s_ref = next(it) if q8 else None
    oh_ref, ol_ref = next(it), next(it)
    if acc is not None:
        hi, lo = acc[0][...], acc[1][...]
    else:
        hi = jnp.zeros(oh_ref.shape, jnp.float32)
        lo = jnp.zeros(oh_ref.shape, jnp.float32)
    # static loop: clients fold in row order, each exactly once
    for c in range(clients):
        xh, xl = _split(_decode(d_ref, s_ref, c))
        w0, w1, w2, w3 = (w_ref[c, k] for k in range(4))
        # the three leading partial products go through TwoSum; the rest
        # are below 2^-23 of the product and only round in the low word
        hi, e1 = _two_sum(hi, xh * w0)
        hi, e2 = _two_sum(hi, xh * w1)
        hi, e3 = _two_sum(hi, xl * w0)
        tail = (xl * w1 + xh * w2) + (xl * w2 + (xh * w3 + xl * w3))
        hi, lo = _two_sum(hi, lo + ((e1 + e2) + (e3 + tail)))
    oh_ref[...] = hi
    ol_ref[...] = lo


@functools.lru_cache(maxsize=None)
def wsum_fn(geom: Geometry, clients: int, q8: bool, has_acc: bool,
            interpret: bool):
    """The jitted fused-fold kernel for one tiling (compiled once per
    geometry).  Operands: the (C, 4) weight words, the ``(hi, lo)``
    accumulator pair (``has_acc``), the (C, rows, lanes) data and, for
    int8, the (C, rows, 1) scales."""
    lanes, tr, rows = geom
    tile = pl.BlockSpec((tr, lanes), lambda i: (i, 0))
    specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    if has_acc:
        specs += [tile, tile]
    specs += _block_specs(geom, clients, q8)
    return jax.jit(pl.pallas_call(
        functools.partial(_wsum_kernel, clients=clients, q8=q8,
                          has_acc=has_acc),
        grid=(rows // tr,), in_specs=specs, out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, lanes), jnp.float32)] * 2,
        interpret=interpret, name="agg_weighted_sum"))


def weighted_sum(data: np.ndarray, weights: np.ndarray, *,
                 scales: Optional[np.ndarray] = None,
                 qchunk: int = DEFAULT_QCHUNK,
                 acc=None,
                 block: Optional[int] = None,
                 out_padded: bool = False):
    """``(acc +) sum_c weights[c] * decode(data[c])`` as one fused pass.

    ``data``: (C, N) fp32/bf16/fp16 or int8 (with ``scales`` (C, S)).
    ``acc``: the running accumulator — an (N,) fp64 host vector, or the
    ``(hi, lo)`` device pair a previous ``out_padded=True`` call returned
    under the same :func:`geometry` (so successive arrivals form one
    asynchronous dispatch chain with no host round-trip).  Returns the
    (N,) fp64 host vector, or with ``out_padded=True`` the padded device
    pair (read it with :func:`pair_to_host`).
    """
    c, n = data.shape
    if n == 0:
        return np.zeros(0, np.float64) if acc is None else np.asarray(acc)
    q8 = data.dtype == np.int8
    geom = geometry(n, c, qchunk if q8 else None, block)
    args = [_split_weights(weights)]
    if acc is not None:
        if isinstance(acc, tuple):
            args += list(acc)
        else:
            a = np.asarray(acc, np.float64)
            hi = a.astype(np.float32)
            lo = (a - hi.astype(np.float64)).astype(np.float32)
            args += [_as_rows(hi, geom), _as_rows(lo, geom)]
    args.append(_data_rows(data, geom))
    if q8:
        args.append(_scale_rows(scales, qchunk, geom))
    out = wsum_fn(geom, c, q8, acc is not None, interpret_mode())(*args)
    if out_padded:
        return tuple(out)               # padded device pair, no sync
    return pair_to_host(out, n)


def pair_to_host(pair, n: int) -> np.ndarray:
    """The fp64 value ``hi + lo`` of a compensated accumulator (exact:
    both words widen exactly and their sum fits fp64)."""
    hi, lo = (np.asarray(a).reshape(-1)[:n] for a in pair)
    return hi.astype(np.float64) + lo.astype(np.float64)


# ---------------------------------------------------------------------------
# sorting-network reductions (median / trimmed sum)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def sort_pairs(n: int) -> Tuple[Tuple[int, int], ...]:
    """Comparators of Batcher's odd-even merge sort for ``n`` inputs (the
    power-of-two network with every comparator that touches a padding
    row dropped — equivalent to padding with +inf)."""
    out = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        out.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return tuple(out)


def _sort_kernel(*refs, clients: int, q8: bool, kind: str, trim_k: int):
    d_ref = refs[0]
    s_ref = refs[1] if q8 else None
    o_ref = refs[-1]
    t = [_decode(d_ref, s_ref, c) for c in range(clients)]
    for a, b in sort_pairs(clients):
        t[a], t[b] = jnp.minimum(t[a], t[b]), jnp.maximum(t[a], t[b])
    if kind == "median":
        o_ref[0] = t[(clients - 1) // 2]
        o_ref[1] = t[clients // 2]
        return
    hi, lo = t[trim_k], jnp.zeros_like(t[trim_k])
    for r in range(trim_k + 1, clients - trim_k):
        s, e = _two_sum(hi, t[r])
        lo = lo + e
        hi = s
    o_ref[0], o_ref[1] = _two_sum(hi, lo)


@functools.lru_cache(maxsize=None)
def sort_fn(geom: Geometry, clients: int, q8: bool, kind: str, trim_k: int,
            interpret: bool):
    """The jitted sorting-network kernel for one tiling."""
    lanes, tr, rows = geom
    return jax.jit(pl.pallas_call(
        functools.partial(_sort_kernel, clients=clients, q8=q8, kind=kind,
                          trim_k=trim_k),
        grid=(rows // tr,), in_specs=_block_specs(geom, clients, q8),
        out_specs=pl.BlockSpec((2, tr, lanes), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, rows, lanes), jnp.float32),
        interpret=interpret, name=f"agg_sort_{kind}"))


def sort_reduce(data: np.ndarray, *, kind: str = "median", trim_k: int = 0,
                scales: Optional[np.ndarray] = None,
                qchunk: int = DEFAULT_QCHUNK,
                base: Optional[np.ndarray] = None,
                block: Optional[int] = None) -> np.ndarray:
    """Coordinate-wise sorted reduction over the (C, N) stack -> (N,) fp64.

    ``kind="median"`` returns the per-coordinate median;
    ``kind="trim_sum"`` the per-coordinate SUM of the sorted rows
    ``[trim_k, C - trim_k)`` (the caller divides, as numpy's mean does).
    ``base`` (a shared delta base, fp64) is added on the host: sorting is
    shift-invariant, so the device sorts the deltas alone.
    """
    if kind not in ("median", "trim_sum"):
        raise ValueError(f"kind {kind!r}")
    c, n = data.shape
    if n == 0:
        return np.zeros(0, np.float64)
    q8 = data.dtype == np.int8
    geom = geometry(n, c, qchunk if q8 else None, block)
    args = [_data_rows(data, geom)]
    if q8:
        args.append(_scale_rows(scales, qchunk, geom))
    out = sort_fn(geom, c, q8, kind, trim_k, interpret_mode())(*args)
    out = np.asarray(out).reshape(2, -1)[:, :n].astype(np.float64)
    if kind == "trim_sum":
        vec = out[0] + out[1]
        if base is not None:
            vec += (c - 2 * trim_k) * np.asarray(base, np.float64)
        return vec
    lo, hi = out
    if base is not None:
        b = np.asarray(base, np.float64)
        lo, hi = lo + b, hi + b
    # numpy's median of an even count: (lo + hi) / 2, in fp64
    return lo if c % 2 else (lo + hi) / 2.0


# ---------------------------------------------------------------------------
# Krum Gram matrix
# ---------------------------------------------------------------------------
def _gram_kernel(*refs, clients: int, q8: bool, rows: int):
    d_ref = refs[0]
    s_ref = refs[1] if q8 else None
    o_ref = refs[-1]
    g = jnp.zeros((clients, clients), jnp.float32)
    for r in range(rows):
        t = d_ref[:, pl.ds(r, 1), :].astype(jnp.float32) \
            .reshape(clients, -1)
        if s_ref is not None:
            t = t * s_ref[:, pl.ds(r, 1), :].reshape(clients, 1)
        # center on the first row: pairwise distances are translation
        # invariant, and removing the common component keeps the
        # ||a||^2+||b||^2-2<a,b> expansion from cancelling catastrophically
        t = t - t[0:1, :]
        g = g + jax.lax.dot_general(
            t, t, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    o_ref[0] = g


@functools.lru_cache(maxsize=None)
def gram_fn(geom: Geometry, clients: int, q8: bool, interpret: bool):
    """The jitted Gram kernel for one tiling."""
    lanes, tr, rows = geom
    return jax.jit(pl.pallas_call(
        functools.partial(_gram_kernel, clients=clients, q8=q8, rows=tr),
        grid=(rows // tr,), in_specs=_block_specs(geom, clients, q8),
        out_specs=pl.BlockSpec((1, clients, clients), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows // tr, clients, clients),
                                       jnp.float32),
        interpret=interpret, name="agg_gram"))


def gram(data: np.ndarray, *,
         scales: Optional[np.ndarray] = None,
         qchunk: int = DEFAULT_QCHUNK,
         block: Optional[int] = None) -> np.ndarray:
    """(C, C) fp64 Gram matrix of the row-0-centered client stack.  A
    shared delta base cancels in the centering, so deltas need none.
    Each grid step returns its fp32 partial; the host sums them in fp64."""
    c, n = data.shape
    if n == 0:
        return np.zeros((c, c), np.float64)
    q8 = data.dtype == np.int8
    geom = geometry(n, c, qchunk if q8 else None, block,
                    max_rows=_GRAM_ROWS)
    args = [_data_rows(data, geom)]
    if q8:
        args.append(_scale_rows(scales, qchunk, geom))
    out = gram_fn(geom, c, q8, interpret_mode())(*args)
    return np.asarray(out).astype(np.float64).sum(axis=0)


# ---------------------------------------------------------------------------
# structured-sparse scatter fold (0xF5 payloads)
# ---------------------------------------------------------------------------
@jax.jit
def dequant_q8(vals, escales):
    """``f32(q * scale)`` elementwise — bitwise the numpy ``_dequant_q8``
    chain (one correctly rounded fp32 product)."""
    return vals.astype(jnp.float32) * escales


def _pad_pow2(a: np.ndarray) -> np.ndarray:
    """Zero-pad to the next power of two so `dequant_q8` compiles once
    per size class instead of once per span length."""
    n = a.size
    p = 1
    while p < n:
        p *= 2
    if p == n:
        return a
    out = np.zeros(p, a.dtype)
    out[:n] = a
    return out


def scatter_wsum(acc: np.ndarray, dest, vals: np.ndarray, w: float, *,
                 scales: Optional[np.ndarray] = None,
                 qchunk: int = DEFAULT_QCHUNK, pos0: int = 0) -> None:
    """``acc[dest] += w * dequant(vals)`` — the 0xF5 sparse-delta fold.

    Not a ``pl.pallas_call``: a data-dependent scatter has no tile
    structure.  The device dequantizes (fp32, :func:`dequant_q8`); the
    host multiplies by the fp64 weight and scatters into the fp64
    accumulator, where ``+=`` with unique indices has no reduction-order
    ambiguity — the same operations as the numpy fold, so bitwise equal.

    ``acc``: fp64 accumulator segment (mutated in place).  ``dest``: a
    slice or unique index array *relative to acc*.  ``vals``: packed
    int8 (with ``scales``, one per ``qchunk`` window of the packed
    stream; ``pos0`` is the packed position of ``vals[0]``) or fp32.
    """
    n = vals.size
    if n == 0:
        return
    if vals.dtype == np.int8:
        # per-element scale of the packed stream (host gather, O(nnz))
        esc = np.asarray(scales, np.float32)[
            (pos0 + np.arange(n, dtype=np.int64)) // qchunk]
        r = np.asarray(dequant_q8(_pad_pow2(vals), _pad_pow2(esc)))[:n]
    else:
        r = vals
    acc[dest] += r.astype(np.float64) * np.float64(w)
