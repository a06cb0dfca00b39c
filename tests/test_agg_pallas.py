"""Differential harness: Pallas aggregation kernels vs the numpy reference.

Every (kernel, codec) pair must agree to <=1 ULP of the output leaf dtype
across layouts (odd sizes, mixed shapes/dtypes), codecs (0xF1 raw / 0xF2
bf16 / 0xF3 int8, including int8 *deltas* against both raw and quantized
bases) and client counts — the same cross-check pattern
``tests/test_kernels.py`` applies to ``secagg_mask``.  The Pallas kernels
run in interpret mode off-TPU; the BlockSpecs/grids are the TPU
configuration under test (``tests/test_chip_compile.py`` compiles them).

The fold is fp32-native with a compensated accumulator, so where a sum
cancels to far below its terms the 1-ULP contract gives way to the error
bound of ``docs/INVARIANTS.md`` §1 (``agg_kernels.fold_error_bound``);
the tests that draw such sums check that bound.  Krum's Gram matrix is an
fp32 matmul, so its *distances* carry an fp32 relative tolerance while
the selection and the final aggregate stay within the fold's contract.
"""
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare env: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

import jax

from repro.fl import agg_kernels as K
from repro.fl.flat import (FlatParams, QuantParams, layout_for, np_dtype,
                           quantize_int8)

pytestmark = pytest.mark.pallas

RNG = np.random.default_rng(0xA66)

LAYOUTS = {
    # odd / prime sizes, mixed shapes — nothing aligns with any block
    "odd_f32": [("float32", (17,)), ("float32", (3, 5)), ("float32", (1,)),
                ("float32", (127,))],
    "scalar_leaf": [("float32", ()), ("float32", (2,))],
    "big_unaligned": [("float32", (1000,)), ("float32", (537,))],
    "uniform_f64": [("float64", (33,)), ("float64", (2, 9))],
    "uniform_f16": [("float16", (21, 4))],
    "uniform_bf16": [("bfloat16", (31,))],
    "mixed_dtypes": [("float64", (5,)), ("float32", (3, 3)),
                     ("float16", (9,))],
}
#: lossy wire codecs only exist for uniform-fp32 layouts
F32_LAYOUTS = [k for k, sig in LAYOUTS.items()
               if all(d == "float32" for d, _ in sig)]
CODECS = ("flat", "bf16", "q8", "q8_delta_flat", "q8_delta_quant",
          "bf16_delta")


# ---------------------------------------------------------------------------
# harness plumbing
# ---------------------------------------------------------------------------
def ulp_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Max ULP distance between two same-dtype float arrays (0 for
    bitwise-equal; +-0 and exact-equal values count as 0)."""
    a, b = np.ravel(a), np.ravel(b)
    assert a.dtype == b.dtype, (a.dtype, b.dtype)
    if a.size == 0:
        return 0
    si = np.dtype(f"i{a.dtype.itemsize}")
    ai = a.view(si).astype(np.int64)
    bi = b.view(si).astype(np.int64)
    mask = (1 << (8 * a.dtype.itemsize - 1)) - 1
    ka = np.where(ai >= 0, ai, -(ai & mask))   # monotonic int mapping
    kb = np.where(bi >= 0, bi, -(bi & mask))
    return int(np.abs(ka - kb).max())


def assert_flat_ulp(got: FlatParams, want: FlatParams, maxulp: int = 1):
    assert got.layout is want.layout
    for g, w in zip(got.to_arrays(), want.to_arrays()):
        d = ulp_diff(g, w)
        assert d <= maxulp, f"{d} ULP > {maxulp} (dtype {g.dtype})"


def assert_fold_bound(got: FlatParams, want: FlatParams, pairs):
    """The on-chip fold contract (``docs/INVARIANTS.md`` §1): each output
    element within one spacing of the reference plus the fold's bound."""
    bound = K.fold_error_bound(pairs, want.layout)
    for spec, g, w in zip(want.layout.leaves, got.to_arrays(),
                          want.to_arrays()):
        b = bound[spec.eoffset:spec.eoffset + spec.size].reshape(w.shape)
        g64, w64 = g.astype(np.float64), w.astype(np.float64)
        tol = np.spacing(np.abs(w)).astype(np.float64) + b
        assert (np.abs(g64 - w64) <= tol).all(), \
            f"max excess {np.max(np.abs(g64 - w64) - tol)} ({g.dtype})"


def _vec_of(layout, rng, scale=1.0):
    return (rng.normal(0, scale, layout.total_size)).astype(np.float32)


def make_payloads(layout_key: str, codec: str, n_clients: int, seed: int,
                  spread: float = 1.0):
    """Client payloads exactly as the wire would hand them to the server:
    FlatParams for raw frames, still-compressed QuantParams for lossy
    ones; delta codecs share one base object like a real round does."""
    layout = layout_for(LAYOUTS[layout_key])
    rng = np.random.default_rng(seed)
    if codec == "flat":
        out = []
        for i in range(n_clients):
            arrays = [np.asarray(
                rng.normal(0, spread * (1 + i), spec.shape),
                np_dtype(spec.dtype)).reshape(spec.shape)
                for spec in layout.leaves]
            out.append(FlatParams.from_arrays(arrays, layout))
        return layout, out
    assert layout.uniform_dtype == "float32", \
        "lossy codecs only apply to uniform-fp32 layouts"
    base_fp = FlatParams.from_arrays(
        [np.asarray(rng.normal(0, 0.5, s.shape), np.float32)
         for s in layout.leaves], layout)
    if codec == "q8_delta_quant":
        qb, sb = quantize_int8(base_fp.math_view())
        base = QuantParams(layout, "q8", qb, sb)
    else:
        base = base_fp
    out = []
    for i in range(n_clients):
        vec = _vec_of(layout, rng, spread * (1 + 0.25 * i))
        if codec == "bf16":
            out.append(QuantParams(layout, "bf16",
                                   vec.astype(np_dtype("bfloat16"))))
        elif codec == "bf16_delta":
            out.append(QuantParams(layout, "bf16",
                                   vec.astype(np_dtype("bfloat16")),
                                   is_delta=True, base=base))
        elif codec == "q8":
            q, s = quantize_int8(vec)
            out.append(QuantParams(layout, "q8", q, s))
        else:                                    # int8 deltas
            q, s = quantize_int8(vec * 1e-3)
            out.append(QuantParams(layout, "q8", q, s,
                                   is_delta=True, base=base))
    return layout, out


def both_backends(fn, *args, **kw):
    return (fn(*args, backend="pallas", **kw),
            fn(*args, backend="numpy", **kw))


# ---------------------------------------------------------------------------
# weighted mean (FedAvg) — full codec x layout matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout_key", sorted(LAYOUTS))
def test_weighted_mean_matches_numpy_raw(layout_key):
    layout, flats = make_payloads(layout_key, "flat", 5, seed=1)
    pairs = [(fp, 10.0 + 3 * i) for i, fp in enumerate(flats)]
    got, want = both_backends(K.weighted_mean, pairs, layout)
    assert_flat_ulp(got, want)


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("n_clients", [1, 2, 5])
def test_weighted_mean_matches_numpy_codecs(codec, n_clients):
    layout, flats = make_payloads("big_unaligned", codec, n_clients, seed=2)
    pairs = [(fp, 7.0 + i) for i, fp in enumerate(flats)]
    got, want = both_backends(K.weighted_mean, pairs, layout)
    assert_fold_bound(got, want, pairs)


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("n_clients", [1, 3, 16])
def test_fold_f64_within_stated_bound(codec, n_clients):
    """The bound itself, before the cast to the output leaves: the Pallas
    fold's fp64 vector vs the numpy fold's, coordinate by coordinate."""
    layout, flats = make_payloads("big_unaligned", codec, n_clients,
                                  seed=40 + n_clients)
    pairs = [(fp, 7.0 + 1.37 * i) for i, fp in enumerate(flats)]
    got = K.weighted_mean_f64(pairs, layout, backend="pallas")
    want = K.weighted_mean_f64(pairs, layout, backend="numpy")
    assert (np.abs(got - want) <= K.fold_error_bound(pairs, layout)).all()


@pytest.mark.parametrize("block", [1024, 4096, 1 << 20])
def test_weighted_mean_block_size_invariance(block):
    """The tiling choice must not change a single bit of the output."""
    layout, flats = make_payloads("big_unaligned", "q8_delta_flat", 4, seed=3)
    pairs = [(fp, 5.0 + i) for i, fp in enumerate(flats)]
    got = K.weighted_mean(pairs, layout, backend="pallas", block=block)
    want = K.weighted_mean(pairs, layout, backend="numpy")
    assert_flat_ulp(got, want)


def test_weighted_mean_cancellation_heavy():
    """Near-zero sums are where FMA contraction / reassociation would
    show up (the regression this harness exists to catch — see the
    agg_reduce module docstring)."""
    layout = layout_for([("float64", (4096,))])
    rng = np.random.default_rng(7)
    base = rng.normal(0, 1, layout.total_size)
    flats, weights = [], []
    for i in range(6):
        sign = 1.0 if i % 2 else -1.0
        flats.append(FlatParams.from_arrays(
            [np.asarray(sign * base + rng.normal(0, 1e-9, base.shape))],
            layout))
        weights.append(1.0 + 1e-6 * i)
    pairs = list(zip(flats, weights))
    got, want = both_backends(K.weighted_mean, pairs, layout)
    assert_flat_ulp(got, want)


# ---------------------------------------------------------------------------
# streaming arrival-order fold
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", ["flat", "bf16", "q8", "q8_delta_quant"])
def test_streaming_fold_matches_numpy(codec):
    layout_key = "odd_f32" if codec == "flat" else "big_unaligned"
    layout, flats = make_payloads(layout_key, codec, 5, seed=4)
    s_np = K.StreamingWeightedSum(layout, backend="numpy")
    s_pl = K.StreamingWeightedSum(layout, backend="pallas")
    for i, fp in enumerate(flats):
        s_np.add(fp, 3.0 + i)
        s_pl.add(fp, 3.0 + i)
    assert s_pl.count == s_np.count == len(flats)
    assert_flat_ulp(s_pl.finalize(), s_np.finalize())


def test_streaming_fold_mixed_backends_is_exact():
    """A round may fold some payloads through Pallas and odd ones through
    the numpy fallback; the per-arrival arithmetic is identical, so the
    mix must equal the pure-numpy fold bitwise."""
    layout, flats = make_payloads("odd_f32", "flat", 4, seed=5)
    s_np = K.StreamingWeightedSum(layout, backend="numpy")
    s_mix = K.StreamingWeightedSum(layout, backend="pallas")
    for i, fp in enumerate(flats):
        s_np.add(fp, 2.0 + i)
        if i % 2:
            s_mix.backend = "numpy"        # simulate a fallback arrival
        else:
            s_mix.backend = "pallas"
        s_mix.add(fp, 2.0 + i)
    assert_flat_ulp(s_mix.finalize(), s_np.finalize())


# ---------------------------------------------------------------------------
# FedAvg's accumulator: operands staged on the device as each result arrives
# ---------------------------------------------------------------------------
SITES4 = ("site-1", "site-2", "site-3", "site-4")


def _fedavg_fold(layout, flats, weights, order):
    """FedAvg's accumulator on the Pallas backend, fed ``flats[i]`` (as
    site ``SITES4[i]``'s fit result) in ``order``; the new model, and
    whether each arrival's operands were on the device before finalize."""
    from repro.fl.messages import FitRes
    from repro.fl.strategy import FedAvg

    acc = FedAvg(backend="pallas").fit_accumulator(
        1, [np.zeros(s.shape, np_dtype(s.dtype)) for s in layout.leaves])
    for i in order:
        fp = flats[i]
        res = (FitRes(None, weights[i], quant=fp)
               if isinstance(fp, QuantParams)
               else FitRes(None, weights[i], flat=fp))
        acc.add(SITES4[i], res)
    on_device = [t is not None and t.src is not None
                 and isinstance(t.src.data, jax.Array)
                 for *_, t in acc.pairs]
    got, _ = acc.finalize([])
    return got, on_device


@pytest.mark.parametrize("order", list(itertools.permutations(range(4))))
@pytest.mark.parametrize("codec", ["q8_delta_quant", "q8", "bf16", "flat"])
def test_fedavg_folds_staged_arrivals_bitwise(codec, order):
    """Each arrival's operands go to the device as it comes in; whatever
    the arrival order, the finalize is bitwise the batch fold over the
    same pairs in node order, with no numpy fallback."""
    layout, flats = make_payloads("big_unaligned", codec, 4, seed=31)
    weights = (3, 1, 4, 2)
    K.reset_fallback_counts()
    got, on_device = _fedavg_fold(layout, flats, weights, order)
    assert on_device == [True] * 4
    want = K.weighted_mean([(fp, float(w)) for fp, w in zip(flats, weights)],
                           layout, backend="pallas").to_arrays()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert K.fallback_counts() == {}


@pytest.mark.parametrize("block", [512, 1024, 1 << 22])
@pytest.mark.parametrize("codec", ["q8_delta_quant", "q8_delta_flat",
                                   "bf16_delta"])
def test_a_staged_round_base_is_its_f64_vector(monkeypatch, codec, block):
    """The first arrival on a round base stages the base's fp64 vector,
    whatever the passes a q8 base's ``to_f64`` makes, and every later
    arrival on that base shares it."""
    from repro.fl import flat

    layout, flats = make_payloads("big_unaligned", codec, 2, seed=33)
    monkeypatch.setattr(flat, "_F64_BLOCK", block)
    bases = {}
    first, second = (K.stage(fp, bases) for fp in flats)
    base = flats[0].base
    want = (flat.dequantize_int8(base.data, base.scales, base.qchunk)
            if isinstance(base, QuantParams)
            else base.math_view().astype(np.float64))
    np.testing.assert_array_equal(first.base, want)
    assert second.base is first.base and len(bases) == 1


def test_fedavg_staged_fold_falls_back_once_on_a_mixed_arrival():
    """A raw fp32 result after three q8 ones cannot join their stack: the
    staged operands are dropped, the round folds on numpy, and one
    ``mixed`` fallback is counted."""
    layout, quants = make_payloads("big_unaligned", "q8", 3, seed=11)
    _, raws = make_payloads("big_unaligned", "flat", 1, seed=12)
    flats, weights = quants + raws, (2, 3, 4, 5)
    K.reset_fallback_counts()
    got, on_device = _fedavg_fold(layout, flats, weights, range(4))
    assert on_device == [True] * 4
    want = K.weighted_mean([(fp, float(w)) for fp, w in zip(flats, weights)],
                           layout, backend="numpy").to_arrays()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert K.fallback_counts() == {"mixed": 1}


# ---------------------------------------------------------------------------
# robust reductions: median / trimmed mean
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", ["flat", "bf16", "q8", "q8_delta_flat",
                                   "q8_delta_quant"])
@pytest.mark.parametrize("n_clients", [2, 3, 6])
def test_median_matches_numpy(codec, n_clients):
    key = "odd_f32" if codec == "flat" else "big_unaligned"
    layout, flats = make_payloads(key, codec, n_clients, seed=6)
    got, want = both_backends(K.median, flats, layout)
    assert_flat_ulp(got, want)


@pytest.mark.parametrize("layout_key", ["uniform_f64", "mixed_dtypes",
                                        "uniform_f16"])
def test_median_matches_numpy_dtypes(layout_key):
    layout, flats = make_payloads(layout_key, "flat", 5, seed=7)
    got, want = both_backends(K.median, flats, layout)
    assert_flat_ulp(got, want)


@pytest.mark.parametrize("codec", ["flat", "q8", "q8_delta_flat"])
@pytest.mark.parametrize("n_clients,k", [(5, 1), (6, 2), (3, 1), (4, 2)])
def test_trimmed_mean_matches_numpy(codec, n_clients, k):
    # (4, 2) exercises n <= 2k: numpy falls back to the untrimmed mean
    key = "odd_f32" if codec == "flat" else "big_unaligned"
    layout, flats = make_payloads(key, codec, n_clients, seed=8)
    got, want = both_backends(K.trimmed_mean, flats, layout, k)
    assert_flat_ulp(got, want)


# ---------------------------------------------------------------------------
# Krum: distances ~tight-tolerance, selection + aggregate exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", ["flat", "bf16", "q8", "q8_delta_quant"])
def test_krum_distances_and_selection(codec):
    key = "odd_f32" if codec == "flat" else "big_unaligned"
    # spread > 0 gives each client a distinct magnitude => well-separated
    # scores, so selection equality is meaningful, not a tie-break fluke
    layout, flats = make_payloads(key, codec, 6, seed=9, spread=1.0)
    Dp = K.krum_distances(flats, layout, backend="pallas")
    Dn = K.krum_distances(flats, layout, backend="numpy")
    # fp32 Gram: docs/INVARIANTS.md §1 bounds the distances relative to
    # the largest one
    np.testing.assert_allclose(Dp, Dn, rtol=0, atol=2.0 ** -16 * Dn.max())
    for f in (0, 1):
        sp = K.krum_scores(Dp, f)
        sn = K.krum_scores(Dn, f)
        assert np.argsort(sp).tolist() == np.argsort(sn).tolist()
        chosen = np.argsort(sp)[:2]
        sel = [(flats[i], 4.0 + i) for i in chosen]
        got, want = both_backends(K.weighted_mean, sel, layout)
        assert_flat_ulp(got, want)


def test_krum_large_common_offset():
    """Late-round regime: client updates nearly identical with a huge
    common component — the centered Gram must not cancel catastrophically
    on either backend."""
    layout = layout_for([("float32", (2048,))])
    rng = np.random.default_rng(10)
    common = rng.normal(0, 1, layout.total_size).astype(np.float32) * 1e4
    flats = [FlatParams.from_arrays(
        [common + rng.normal(0, 1e-2, common.shape).astype(np.float32)],
        layout) for _ in range(5)]
    Dp = K.krum_distances(flats, layout, backend="pallas")
    Dn = K.krum_distances(flats, layout, backend="numpy")
    np.testing.assert_allclose(Dp, Dn, rtol=1e-6, atol=1e-4)
    assert (Dp >= 0).all()


# ---------------------------------------------------------------------------
# hypothesis property: the full matrix, randomly sampled
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(1, 7), st.sampled_from(CODECS),
       st.integers(1, 2500), st.integers(0, 1000))
def test_property_weighted_mean_any_size(n_clients, codec, size, seed):
    """Any (client count, codec, odd buffer size): Pallas == numpy within
    the fold contract (deltas included).  Sizes straddle the int8 scale-window (1024) and
    never align with the kernel blocks."""
    sig = (("float32", (size,)),)
    layout = layout_for(sig)
    rng = np.random.default_rng(seed)
    if codec == "flat":
        flats = [FlatParams.from_arrays(
            [rng.normal(0, 1 + i, (size,)).astype(np.float32)], layout)
            for i in range(n_clients)]
    else:
        base_fp = FlatParams.from_arrays(
            [rng.normal(0, 0.5, (size,)).astype(np.float32)], layout)
        if codec == "q8_delta_quant":
            qb, sb = quantize_int8(base_fp.math_view())
            base = QuantParams(layout, "q8", qb, sb)
        else:
            base = base_fp
        flats = []
        for i in range(n_clients):
            vec = rng.normal(0, 1 + 0.1 * i, (size,)).astype(np.float32)
            if codec.startswith("bf16"):
                flats.append(QuantParams(
                    layout, "bf16", vec.astype(np_dtype("bfloat16")),
                    is_delta=codec.endswith("delta"),
                    base=base if codec.endswith("delta") else None))
            else:
                q, s = quantize_int8(vec)
                is_d = codec.startswith("q8_delta")
                flats.append(QuantParams(layout, "q8", q, s, is_delta=is_d,
                                         base=base if is_d else None))
    pairs = [(fp, 1.0 + i) for i, fp in enumerate(flats)]
    got, want = both_backends(K.weighted_mean, pairs, layout,
                              block=1024 if size > 1024 else None)
    assert_fold_bound(got, want, pairs)


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 7), st.integers(1, 1500), st.integers(0, 500))
def test_property_robust_reductions_any_size(n_clients, size, seed):
    layout = layout_for((("float32", (size,)),))
    rng = np.random.default_rng(seed + 31)
    flats = [FlatParams.from_arrays(
        [rng.normal(0, 1 + i, (size,)).astype(np.float32)], layout)
        for i in range(n_clients)]
    got, want = both_backends(K.median, flats, layout)
    assert_flat_ulp(got, want)
    k = max(0, (n_clients - 1) // 3)
    got, want = both_backends(K.trimmed_mean, flats, layout, k)
    assert_flat_ulp(got, want)


# ---------------------------------------------------------------------------
# dispatch contract
# ---------------------------------------------------------------------------
def test_dispatch_falls_back_on_heterogeneous_codecs():
    """One raw straggler among q8 clients must not abort — the round
    falls back to the numpy kernels and still aggregates exactly."""
    layout, quants = make_payloads("big_unaligned", "q8", 3, seed=11)
    _, raws = make_payloads("big_unaligned", "flat", 1, seed=12)
    pairs = [(fp, 2.0 + i) for i, fp in enumerate(quants + raws)]
    got = K.weighted_mean(pairs, layout, backend="pallas")
    want = K.weighted_mean(pairs, layout, backend="numpy")
    assert_flat_ulp(got, want, maxulp=0)       # same path => bitwise


def test_dispatch_falls_back_on_integer_domain():
    """SecAgg's uint64 shares have no float tile — numpy fallback, and
    wrapping_sum_u64 stays numpy-only."""
    layout = layout_for([("uint64", (9,))])
    flats = [FlatParams.from_arrays(
        [np.arange(9, dtype=np.uint64) * (i + 1)], layout)
        for i in range(3)]
    assert flats[0].tile_source() is None
    got = K.weighted_mean([(f, 1.0) for f in flats], layout,
                          backend="pallas")
    want = K.weighted_mean([(f, 1.0) for f in flats], layout,
                           backend="numpy")
    for g, w in zip(got.to_arrays(), want.to_arrays()):
        np.testing.assert_array_equal(g, w)


def test_dispatch_falls_back_on_distinct_delta_bases():
    layout, a = make_payloads("big_unaligned", "q8_delta_flat", 2, seed=13)
    _, b = make_payloads("big_unaligned", "q8_delta_flat", 2, seed=14)
    pairs = [(fp, 1.0 + i) for i, fp in enumerate(a + b)]  # two base objects
    got = K.weighted_mean(pairs, layout, backend="pallas")
    want = K.weighted_mean(pairs, layout, backend="numpy")
    assert_flat_ulp(got, want, maxulp=0)


def test_backend_resolution_and_env_override(monkeypatch):
    assert K.resolve_backend("numpy") == "numpy"
    assert K.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        K.resolve_backend("cuda")
    try:
        # CPU container, no env override: auto resolves to numpy
        monkeypatch.delenv("REPRO_AGG_BACKEND", raising=False)
        K.set_default_backend(None)
        assert K.resolve_backend(None) == "numpy"
        assert K.resolve_backend("auto") == "numpy"
        # the env knob flips the process default (the CI pallas lane)
        monkeypatch.setenv("REPRO_AGG_BACKEND", "pallas")
        K.set_default_backend(None)
        assert K.resolve_backend(None) == "pallas"
    finally:
        monkeypatch.delenv("REPRO_AGG_BACKEND", raising=False)
        K.set_default_backend(None)


def test_server_config_threads_backend_to_strategy():
    from repro.fl.server import ServerApp, ServerConfig
    from repro.fl.strategy import FedAvg

    strat = FedAvg()
    assert strat.backend is None
    ServerApp(ServerConfig(num_rounds=1, agg_backend="pallas"), strat)
    assert strat.backend == "pallas"
    # explicit strategy choice survives when the config does not override
    strat2 = FedAvg(backend="numpy")
    ServerApp(ServerConfig(num_rounds=1), strat2)
    assert strat2.backend == "numpy"


def test_strategies_run_on_pallas_backend_end_to_end():
    """aggregate_fit through the strategy layer on both backends, all
    robust aggregators — the path the ServerApp drives."""
    from repro.fl.messages import FitRes
    from repro.fl.strategy import make_strategy

    rng = np.random.default_rng(15)
    shapes = [(16, 8), (33,), (1,)]
    results = []
    for c in range(6):
        arrays = [rng.normal(0, 1 + c, s).astype(np.float32) for s in shapes]
        results.append((f"site-{c}", FitRes(arrays, 10 + c, {})))
    current = [np.zeros(s, np.float32) for s in shapes]
    for name in ("fedavg", "fedmedian", "fedtrimmedmean", "krum"):
        got, _ = make_strategy(name, backend="pallas") \
            .aggregate_fit(1, results, [], current)
        want, _ = make_strategy(name, backend="numpy") \
            .aggregate_fit(1, results, [], current)
        for g, w in zip(got, want):
            assert ulp_diff(g, w) <= 1, name


def test_empty_layout_is_safe_on_pallas():
    layout = layout_for([])
    fp = FlatParams.zeros(layout)
    out = K.weighted_mean([(fp, 1.0)], layout, backend="pallas")
    assert out.layout.total_size == 0


# ---------------------------------------------------------------------------
# mesh-sharded streaming fold: shard-count / overlap / placement invariance
# (the shard-cpu CI lane re-runs these under
#  XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------
SHARD_CODECS = ("flat", "bf16", "q8", "q8_delta_quant")


def _fold(layout, flats, *, backend, shards=None, overlap=None, **kw):
    s = K.StreamingWeightedSum(layout, backend=backend, shards=shards,
                               overlap=overlap, **kw)
    for i, fp in enumerate(flats):
        s.add(fp, 3.0 + i)
    return s.finalize()


@pytest.mark.shard
@pytest.mark.parametrize("codec", SHARD_CODECS)
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_sharded_fold_bitwise_across_shard_counts(codec, backend):
    """finalize() must not depend on how the accumulator is split: 1, 2
    and 8 shards x both backends agree bitwise (the fold is pure
    elementwise ops in arrival order on every path)."""
    layout, flats = make_payloads("big_unaligned", codec, 5, seed=21)
    want = _fold(layout, flats, backend="numpy", shards=1, overlap=False)
    for shards in (2, 8):
        got = _fold(layout, flats, backend=backend, shards=shards,
                    overlap=False)
        assert_flat_ulp(got, want, maxulp=0)


@pytest.mark.shard
@pytest.mark.parametrize("codec", SHARD_CODECS)
def test_sharded_overlap_is_bitwise(codec):
    """The decode thread must change wall-clock only: FIFO job order
    keeps the (arrival, shard) fold order of the serial path."""
    layout, flats = make_payloads("big_unaligned", codec, 5, seed=22)
    got = _fold(layout, flats, backend="numpy", shards=8, overlap=True)
    want = _fold(layout, flats, backend="numpy", shards=8, overlap=False)
    assert_flat_ulp(got, want, maxulp=0)


@pytest.mark.shard
@pytest.mark.parametrize("codec", ["flat", "bf16", "q8"])
def test_sharded_matches_single_host_non_delta(codec):
    """Non-delta payloads: the deferred-base algebra is vacuous, so the
    sharded fold equals the frozen single-host accumulator bitwise."""
    layout, flats = make_payloads("big_unaligned", codec, 5, seed=23)
    legacy = _fold(layout, flats, backend="numpy")
    got = _fold(layout, flats, backend="numpy", shards=8, overlap=False)
    assert_flat_ulp(got, legacy, maxulp=0)


@pytest.mark.shard
@pytest.mark.parametrize("codec", ["q8_delta_flat", "q8_delta_quant",
                                   "bf16_delta"])
def test_sharded_delta_close_to_single_host(codec):
    """Deferred bases regroup the summation (sum w_k(d_k+b) folded as
    sum w_k d_k + W b): <=1 ULP of the fp32 output leaves vs the
    per-arrival reconstruction."""
    layout, flats = make_payloads("big_unaligned", codec, 5, seed=24)
    legacy = _fold(layout, flats, backend="numpy")
    got = _fold(layout, flats, backend="numpy", shards=4, overlap=False)
    assert_flat_ulp(got, legacy, maxulp=1)


@pytest.mark.shard
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_sharded_fault_partial_round_bitwise(backend):
    """Straggler faults (PR 2 semantics): a round that aggregates only
    the arrived subset is still shard-count invariant."""
    layout, flats = make_payloads("big_unaligned", "q8_delta_quant", 6,
                                  seed=25)
    arrived = flats[:2] + flats[4:]          # clients 2, 3 timed out
    want = _fold(layout, arrived, backend="numpy", shards=1, overlap=False)
    got = _fold(layout, arrived, backend=backend, shards=8, overlap=False)
    assert_flat_ulp(got, want, maxulp=0)


@pytest.mark.shard
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_sharded_mixed_codec_arrivals(backend):
    """A raw straggler interleaved with q8 clients: per-arrival fallback
    (and per-shard geometry retire on the Pallas path) must not change
    the elementwise fold."""
    layout, quants = make_payloads("big_unaligned", "q8", 3, seed=26)
    _, raws = make_payloads("big_unaligned", "flat", 2, seed=27)
    mixed = [quants[0], raws[0], quants[1], raws[1], quants[2]]
    want = _fold(layout, mixed, backend="numpy", shards=1, overlap=False)
    got = _fold(layout, mixed, backend=backend, shards=8, overlap=False)
    assert_flat_ulp(got, want, maxulp=0)


@pytest.mark.shard
def test_sharded_f32_tile_with_f64_carry_tolerance():
    """The on-chip tile scheme (fp32 decode/scale into a compensated fp32
    pair, fp64 formed on the host) vs the numpy fp64 oracle, sharded:
    within the fold bound, no compounding drift across arrivals."""
    layout, flats = make_payloads("big_unaligned", "q8", 5, seed=28)
    oracle = _fold(layout, flats, backend="numpy", shards=2, overlap=False)
    got = _fold(layout, flats, backend="pallas", shards=2, overlap=False)
    pairs = [(fp, 3.0 + i) for i, fp in enumerate(flats)]
    assert_fold_bound(got, oracle, pairs)


@pytest.mark.shard
def test_sharded_mesh_placement_bitwise():
    """An explicit mesh pins each shard's kernel to a device; placement
    must be invisible in the result.  Needs >1 simulated device (the
    shard-cpu CI lane forces 8)."""
    jax = pytest.importorskip("jax")
    if jax.device_count() < 2:
        pytest.skip("single-device host; shard-cpu lane covers this")
    from repro.launch.mesh import make_agg_mesh

    mesh = make_agg_mesh(min(8, jax.device_count()))
    layout, flats = make_payloads("big_unaligned", "q8", 4, seed=29)
    assert K.StreamingWeightedSum(layout, mesh=mesh).shards \
        == mesh.devices.size
    want = _fold(layout, flats, backend="numpy",
                 shards=mesh.devices.size, overlap=False)
    got = _fold(layout, flats, backend="pallas", mesh=mesh)
    assert_flat_ulp(got, want, maxulp=0)


@pytest.mark.shard
@pytest.mark.parametrize("name", ["fedavgm", "fedadam", "fedyogi"])
def test_sharded_fedopt_moments_match_over_rounds(name):
    """FedOpt server state (velocity / m / v) sharded vs single-vector
    over 3 rounds: the update is elementwise, so the returned parameters
    must match bitwise every round."""
    from repro.fl.messages import FitRes
    from repro.fl.strategy import make_strategy

    rng = np.random.default_rng(30)
    shapes = [(64, 8), (1031,), (3,)]
    sharded = make_strategy(name, shards=4)
    exact = make_strategy(name, low_memory=True)
    cur_s = [np.zeros(s, np.float32) for s in shapes]
    cur_e = [np.zeros(s, np.float32) for s in shapes]
    for rnd in (1, 2, 3):
        results = []
        for c in range(5):
            arrays = [rng.normal(0, 1 + c, s).astype(np.float32)
                      for s in shapes]
            results.append((f"site-{c}", FitRes(arrays, 10 + c, {})))
        got, _ = sharded.aggregate_fit(rnd, results, [], cur_s)
        want, _ = exact.aggregate_fit(rnd, results, [], cur_e)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w, err_msg=name)
        cur_s, cur_e = got, want
