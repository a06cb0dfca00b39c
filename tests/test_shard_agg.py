"""Sharded server aggregation state: units + strategy/server integration.

Covers the pieces ``tests/test_agg_pallas.py``'s differential lane does
not: the ``shard_bounds`` partition contract, the stable base-memo token
(the ``id()``-reuse regression), the padded-accumulator geometry cache,
decode-pipeline failure semantics, quantized FedOpt moments, and the
``ServerConfig`` plumbing.  The shard-cpu CI lane re-runs this module
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import gc

import numpy as np
import pytest

from repro.fl import agg_kernels as K
from repro.fl.flat import (QCHUNK, FlatParams, QuantParams, layout_for,
                           memo_token, quantize_int8)
from repro.sharding import shard_bounds

from test_agg_pallas import assert_flat_ulp, make_payloads, ulp_diff

pytestmark = pytest.mark.shard


# ---------------------------------------------------------------------------
# shard_bounds: the partition contract
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("total,shards,align", [
    (0, 1, 1), (0, 4, 1024), (1, 1, 1), (1, 8, 1024),
    (1537, 8, 1024), (10_000, 3, 1024), (QCHUNK * 7, 8, QCHUNK),
    (50_000_000, 16, QCHUNK), (5, 5, 1), (1023, 2, 1024),
])
def test_shard_bounds_partition_contract(total, shards, align):
    bounds = shard_bounds(total, shards, align=align)
    assert len(bounds) == shards
    # contiguous, ordered, disjoint, covering exactly [0, total)
    cursor = 0
    for lo, hi in bounds:
        assert lo == cursor and hi >= lo
        cursor = hi
    assert cursor == total
    # every non-empty shard starts on an align boundary (so q8 scale
    # windows never straddle a shard edge; empty tail shards clamp to
    # ``total``) and no shard exceeds the balanced size
    per = -(-max(total, 1) // shards)
    per = -(-per // align) * align
    for lo, hi in bounds:
        if hi > lo:
            assert lo % align == 0
        assert hi - lo <= per


def test_shard_bounds_ragged_tail_leaves_empty_shards():
    # total < shards * align: early shards take align-sized ranges, the
    # rest are empty — callers must tolerate (lo == hi) shards
    bounds = shard_bounds(3, 8, align=1024)
    assert bounds[0] == (0, 3)
    assert all(lo == hi == 3 for lo, hi in bounds[1:])


def test_shard_bounds_rejects_bad_arguments():
    with pytest.raises(ValueError):
        shard_bounds(10, 0)
    with pytest.raises(ValueError):
        shard_bounds(10, -2)
    with pytest.raises(ValueError):
        shard_bounds(10, 2, align=0)


class _FakeMesh:
    """axis_names/devices duck type of jax.sharding.Mesh — enough for
    resolve_shards without forcing a multi-device jax runtime here."""

    def __init__(self, shape, names):
        self.devices = np.empty(shape, object)
        self.axis_names = names


def test_resolve_shards_precedence():
    assert K.resolve_shards(None) == 0
    assert K.resolve_shards(None, None) == 0
    assert K.resolve_shards(4) == 4
    with pytest.raises(ValueError):
        K.resolve_shards(-1)
    mesh = _FakeMesh((8,), ("data",))
    assert K.resolve_shards(None, mesh) == 8
    assert K.resolve_shards(2, mesh) == 2          # explicit count wins
    # "data" axis picked out of a 2-D mesh; no "data" -> all devices
    assert K.resolve_shards(None, _FakeMesh((2, 4), ("model", "data"))) == 4
    assert K.resolve_shards(None, _FakeMesh((2, 3), ("x", "y"))) == 6


def test_per_shard_memory_is_fraction_of_single_host():
    """The ISSUE acceptance bound, checked analytically: per-shard fp64
    footprint <= (1/N + 10%) of the single-host accumulator."""
    layout = layout_for([("float32", (1_000_000,))])
    single = K.StreamingWeightedSum(layout).per_shard_acc_bytes()
    assert single == layout.total_size * 8
    for shards in (2, 4, 8, 16):
        s = K.StreamingWeightedSum(layout, shards=shards)
        assert s.per_shard_acc_bytes() <= single * (1 / shards + 0.10)


# ---------------------------------------------------------------------------
# base memo: stable tokens vs id() reuse
# ---------------------------------------------------------------------------
def test_memo_token_stable_and_distinct():
    layout = layout_for([("float32", (8,))])
    a, b = FlatParams.zeros(layout), FlatParams.zeros(layout)
    assert memo_token(a) == memo_token(a)      # stable per object
    assert memo_token(a) != memo_token(b)      # distinct across objects


def test_memo_token_never_recycled_across_id_reuse():
    """Regression: the delta-base memo used to key on ``id(base)``.
    CPython recycles addresses as soon as an object dies, so a freed
    round base could alias a *new* base's cache entry and decode stale
    fp64 bytes.  Tokens must stay unique even when ids collide."""
    layout = layout_for([("float32", (QCHUNK,))])
    tokens, ids = [], []
    for i in range(64):
        q, s = quantize_int8(
            np.full(layout.total_size, float(i + 1), np.float32))
        base = QuantParams(layout, "q8", q, s)
        tokens.append(memo_token(base))
        ids.append(id(base))
        del base
        gc.collect()               # force the allocator to recycle
    assert len(set(tokens)) == len(tokens)
    if len(set(ids)) == len(ids):
        pytest.skip("allocator never recycled an id; collision not forced")


def test_base_memo_not_poisoned_by_id_reuse():
    """Functional form of the regression: three deltas against three
    *different* short-lived bases (freed between arrivals, so their ids
    can be recycled).  The memoizing Pallas fold must match the
    memo-free numpy fold bitwise — a stale memo hit decodes the wrong
    base and diverges wildly."""
    layout = layout_for([("float32", (2048,))])
    rng = np.random.default_rng(31)

    def delta_against_fresh_base(level):
        bq, bs = quantize_int8(
            np.full(layout.total_size, level, np.float32))
        base = QuantParams(layout, "q8", bq, bs)
        q, s = quantize_int8(
            rng.normal(0, 1e-3, layout.total_size).astype(np.float32))
        return QuantParams(layout, "q8", q, s, is_delta=True, base=base)

    s_pl = K.StreamingWeightedSum(layout, backend="pallas")
    s_np = K.StreamingWeightedSum(layout, backend="numpy")
    for i, level in enumerate((1.0, 2.0, 3.0)):
        fp = delta_against_fresh_base(level)
        s_pl.add(fp, 1.0 + i)
        s_np.add(fp, 1.0 + i)
        del fp                     # frees the base; id may be recycled
        gc.collect()
    assert_flat_ulp(s_pl.finalize(), s_np.finalize(), maxulp=0)


# ---------------------------------------------------------------------------
# padded-accumulator geometry cache (single-host Pallas mode)
# ---------------------------------------------------------------------------
def test_padded_acc_cached_across_homogeneous_arrivals():
    """A codec-homogeneous round keeps one padded device accumulator for
    every arrival (no per-arrival pad + slice + sync)."""
    layout, flats = make_payloads("big_unaligned", "q8", 4, seed=32)
    s = K.StreamingWeightedSum(layout, backend="pallas")
    geoms = set()
    for i, fp in enumerate(flats):
        s.add(fp, 2.0 + i)
        assert s._acc_padded is not None and s._acc is None
        geoms.add(s._pad_geom)
    assert len(geoms) == 1         # one geometry, cache never retired
    want = K.StreamingWeightedSum(layout, backend="numpy")
    for i, fp in enumerate(flats):
        want.add(fp, 2.0 + i)
    assert_flat_ulp(s.finalize(), want.finalize(), maxulp=0)


def test_padded_acc_retired_on_geometry_change():
    """An int8 payload with a 256-element scale window tiles 256 lanes
    wide while raw frames tile 1024, so interleaving the two forces the
    retire + re-pad fallback on every switch — which must stay invisible
    in the result."""
    layout, raws = make_payloads("big_unaligned", "flat", 1, seed=34)
    rng = np.random.default_rng(33)
    quants = []
    for _ in range(2):
        q, sc = quantize_int8(
            rng.normal(0, 1, layout.total_size).astype(np.float32),
            qchunk=256)
        quants.append(QuantParams(layout, "q8", q, sc, qchunk=256))
    arrivals = [(quants[0], 2.0), (raws[0], 3.0), (quants[1], 4.0)]
    s = K.StreamingWeightedSum(layout, backend="pallas")
    geoms = []
    for fp, w in arrivals:
        s.add(fp, w)
        geoms.append(s._pad_geom)
    assert geoms[0] != geoms[1]    # the mixed arrival changed geometry
    assert geoms[2] == geoms[0]
    want = K.StreamingWeightedSum(layout, backend="numpy")
    for fp, w in arrivals:
        want.add(fp, w)
    assert_flat_ulp(s.finalize(), want.finalize(), maxulp=0)


# ---------------------------------------------------------------------------
# decode pipeline: ring reuse + failure semantics
# ---------------------------------------------------------------------------
def test_pipeline_ring_reuse_many_arrivals_bitwise():
    """More arrivals than ring slots (12 > 3): slot recycling and the
    depth-1 job queue must preserve the serial fold order."""
    layout, flats = make_payloads("big_unaligned", "q8_delta_quant", 12,
                                  seed=35)
    on = K.StreamingWeightedSum(layout, backend="numpy", shards=4,
                                overlap=True)
    off = K.StreamingWeightedSum(layout, backend="numpy", shards=4,
                                 overlap=False)
    assert on.overlap and not off.overlap
    for i, fp in enumerate(flats):
        on.add(fp, 1.0 + i)
        off.add(fp, 1.0 + i)
    assert_flat_ulp(on.finalize(), off.finalize(), maxulp=0)


class _BoomPayload:
    is_delta = False

    def f64_chunk(self, lo, hi, out):
        raise RuntimeError("decode boom")


def test_pipeline_propagates_decoder_errors():
    """A decoder-thread exception must surface on the caller's thread
    (at add() or finalize(), whichever drains it first), and the failed
    pipeline must reject further work instead of folding silently."""
    layout = layout_for([("float32", (4096,))])
    s = K.StreamingWeightedSum(layout, backend="numpy", shards=2,
                               overlap=True)
    assert s.overlap
    with pytest.raises(RuntimeError):
        s.add(_BoomPayload(), 1.0)
        s.finalize()
    good = FlatParams.zeros(layout)
    with pytest.raises(RuntimeError):
        s.add(good, 1.0)
        s.finalize()


def test_sharded_delta_without_base_is_an_error():
    layout, flats = make_payloads("big_unaligned", "q8_delta_quant", 1,
                                  seed=36)
    orphan = QuantParams(layout, "q8", flats[0].data, flats[0].scales,
                         is_delta=True, base=None)
    s = K.StreamingWeightedSum(layout, backend="numpy", shards=2,
                               overlap=False)
    with pytest.raises(ValueError, match="base"):
        s.add(orphan, 1.0)


def test_sharded_empty_and_tiny_layouts():
    # empty model: all shards empty, finalize is a no-op frame
    empty = layout_for([])
    s = K.StreamingWeightedSum(empty, shards=4)
    s.add(FlatParams.zeros(empty), 1.0)
    assert s.finalize().layout.total_size == 0
    # model smaller than one align window: one real shard + empties
    tiny = layout_for([("float32", (3,))])
    fp = FlatParams.from_arrays(
        [np.array([1.0, -2.0, 3.5], np.float32)], tiny)
    s8 = K.StreamingWeightedSum(tiny, shards=8, overlap=False)
    s1 = K.StreamingWeightedSum(tiny, shards=1, overlap=False)
    s8.add(fp, 2.0)
    s1.add(fp, 2.0)
    assert_flat_ulp(s8.finalize(), s1.finalize(), maxulp=0)


# ---------------------------------------------------------------------------
# FedOpt sharded server state
# ---------------------------------------------------------------------------
def _run_rounds(strategy, shapes, rounds=3, clients=4, seed=36):
    from repro.fl.messages import FitRes

    rng = np.random.default_rng(seed)
    cur = [np.zeros(s, np.float32) for s in shapes]
    for rnd in range(1, rounds + 1):
        results = [
            (f"site-{c}", FitRes(
                [rng.normal(0, 1, s).astype(np.float32) for s in shapes],
                10 + c, {}))
            for c in range(clients)]
        cur, _ = strategy.aggregate_fit(rnd, results, [], cur)
    return cur


def test_quantized_moments_storage_and_tolerance():
    """quantize_moments stores each shard's m/v as int8 + per-QCHUNK
    scales (~1/8 the fp64 bytes).  The lossiness is documented and
    denominator-shaped: coordinates whose true ``v`` is tiny relative to
    their scale chunk's max see a coarse ``sqrt(v) + tau`` and drift the
    most, so the contract is bulk closeness, not elementwise equality."""
    from repro.fl.strategy import FedAdam

    shapes = [(4096,), (515,)]
    n = sum(int(np.prod(s)) for s in shapes)
    exact = _run_rounds(FedAdam(shards=2), shapes)
    quant_strat = FedAdam(shards=2, quantize_moments=True)
    quant = _run_rounds(quant_strat, shapes)
    state_bytes = 0
    for st in quant_strat._shard_mv:
        for mom in st:
            assert isinstance(mom, tuple) and mom[0].dtype == np.int8
            state_bytes += mom[0].nbytes + mom[1].nbytes
    assert state_bytes <= 0.25 * (2 * n * 8)   # ~1/8 of fp64 m+v
    err = np.abs(np.concatenate([q.ravel() - e.ravel()
                                 for q, e in zip(quant, exact)]))
    assert np.mean(err > 0.05) < 0.03          # >=97% of coords close
    assert np.median(err) < 5e-3               # the bulk is tight


def test_fedavgm_sharded_velocity_state_shape():
    from repro.fl.strategy import FedAvgM

    strat = FedAvgM(shards=3)
    shapes = [(1031,), (7,)]
    _run_rounds(strat, shapes, rounds=2)
    total = sum(int(np.prod(s)) for s in shapes)
    bounds = shard_bounds(total, 3, align=QCHUNK)
    assert [v.size for v in strat._shard_vel] \
        == [hi - lo for lo, hi in bounds]


# ---------------------------------------------------------------------------
# server / strategy plumbing
# ---------------------------------------------------------------------------
def test_server_config_threads_shards_to_strategy():
    from repro.fl.server import ServerApp, ServerConfig
    from repro.fl.strategy import FedAvg

    strat = FedAvg()
    assert strat.shards is None
    ServerApp(ServerConfig(num_rounds=1, agg_shards=4), strat)
    assert strat.shards == 4
    mesh = _FakeMesh((8,), ("data",))
    strat_m = FedAvg()
    ServerApp(ServerConfig(num_rounds=1, shard_mesh=mesh), strat_m)
    assert strat_m.shard_mesh is mesh
    # explicit strategy choice survives when the config does not override
    strat2 = FedAvg(shards=2)
    ServerApp(ServerConfig(num_rounds=1), strat2)
    assert strat2.shards == 2


def test_fedavg_end_to_end_sharded_matches_streaming():
    """aggregate_fit with shards=2 vs the single-host streaming fold:
    bitwise (non-delta payloads); vs the deferred batch kernel: <=1 ULP
    (the documented streaming-vs-deferred difference, not sharding's)."""
    from repro.fl.strategy import FedAvg

    shapes = [(33, 5), (2049,)]
    sharded = _run_rounds(FedAvg(shards=2), shapes)
    streaming = _run_rounds(FedAvg(low_memory=True), shapes)
    deferred = _run_rounds(FedAvg(), shapes)
    for g, w in zip(sharded, streaming):
        np.testing.assert_array_equal(g, w)
    for g, w in zip(sharded, deferred):
        assert ulp_diff(g, w) <= 1
