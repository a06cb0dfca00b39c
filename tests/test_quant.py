"""Quantized wire codec (0xF2 bf16 / 0xF3 int8+per-chunk-scales) tests.

Covers the contracts the compressed hot path rests on:
- cross-version interop: every (encoder, decoder) pair across
  legacy/0xF1/0xF2/0xF3 round-trips (bitwise for the lossless pair,
  within the quantization bound for the lossy ones) or raises a clear
  ``UnsupportedCodec`` for reserved version bytes this build lacks;
- the int8 per-chunk quantization error bound (hypothesis property) of
  both engines, host and device, and their bitwise-equal scales;
- zero-copy decode of compressed frames (data/scales are views);
- delta encoding: client and server agree bitwise on the round base,
  reconstruction error is bounded by the *update* magnitude;
- fused dequantize+accumulate kernels: aggregating compressed results
  (deferred and streaming accumulators, robust strategies) matches the
  fp32 path within the quantization bound;
- SecAgg mask cancellation in the quantized integer domain (hypothesis);
- codec negotiation end to end: ServerApp picks the advertised codec,
  demotes to lossless flat for fleets that don't advertise it, and
  SecAgg composes (masked uint64 shares fall back to 0xF1).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare env: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.fl import flat
from repro.fl.flat import (FlatParams, QCHUNK, QuantParams, quantizable,
                           quantize_int8, layout_of)
from repro.fl.messages import (FLAT_MAGIC, BF16_MAGIC, Q8_MAGIC, FitIns,
                               FitRes, TaskIns, UnsupportedCodec,
                               WIRE_CODECS, arrays_to_bytes, bytes_to_arrays,
                               decode_fit_ins, decode_fit_res,
                               decode_properties_res, decode_task_res,
                               encode_fit_ins, encode_fit_res,
                               encode_task_ins, peek_config, peek_params)
from repro.fl.strategy import make_strategy

RNG = np.random.default_rng(21)


def _f32_arrays(seed=0, shapes=((33, 17), (1500,), (2, 3, 5))):
    rng = np.random.default_rng(seed)
    return [rng.normal(0, 0.5, size=s).astype(np.float32) for s in shapes]


def _q8_bound(q: QuantParams) -> float:
    """Per-coordinate reconstruction bound: half the largest chunk scale
    (plus fp32 rounding slack)."""
    return float(q.scales.max()) * 0.5 * (1 + 1e-5) + 1e-12


# ---------------------------------------------------------------------------
# int8 quantization primitive: the host and device engines
# ---------------------------------------------------------------------------
#: the device engine's slab in these tests (the module's SLAB is 2^24):
#: small, so every case runs on the CPU backend in well under a second
TEST_SLAB = 2 * QCHUNK
ENGINES = {
    "host": lambda x: flat._quantize_int8_host(x, QCHUNK),
    "device": lambda x: flat._quantize_int8_device(x, QCHUNK, TEST_SLAB)[:2],
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("case", ["random", "all_zero_chunks"])
def test_int8_quantization_error_bound(case, engine):
    """|x - scale*q| <= scale/2 per coordinate, any length (ragged tails
    and slab tails included), any dynamic range; all-zero chunks get
    scale 1.0 and q 0."""
    quantize = ENGINES[engine]
    if case == "all_zero_chunks":
        q, scales = quantize(np.zeros(2 * QCHUNK + 5, np.float32))
        assert (scales == 1.0).all() and (q == 0).all()
        return

    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 3 * QCHUNK + 7), st.integers(0, 10_000),
           st.floats(1e-6, 1e3))
    def bounded(n, seed, magnitude):
        rng = np.random.default_rng(seed)
        x = (rng.normal(0, magnitude, size=n)).astype(np.float32)
        q, scales = quantize(x)
        assert q.dtype == np.int8 and scales.dtype == np.float32
        assert q.size == n
        assert scales.size == -(-n // QCHUNK) and (scales > 0).all()
        sv = np.repeat(scales.astype(np.float64), QCHUNK)[:n]
        err = np.abs(q.astype(np.float64) * sv - x.astype(np.float64))
        bound = sv * 0.5 * (1 + 1e-5) + 1e-12
        assert (err <= bound).all(), float((err - bound).max())

    bounded()


def _agreement_input(case):
    rng = np.random.default_rng(5)
    n = {"one_slab": TEST_SLAB, "ragged_tail": 2 * TEST_SLAB + 3 * QCHUNK // 2 + 7,
         "zero_slab": 3 * TEST_SLAB - 100, "fp64": 2 * TEST_SLAB + 901}[case]
    x = rng.normal(0, 1e-2, size=n)
    x[::97] *= 1e3                     # windows of very different ranges
    if case == "zero_slab":
        x[TEST_SLAB:2 * TEST_SLAB] = 0.0
    return x if case == "fp64" else x.astype(np.float32)


@pytest.mark.parametrize("case", ["one_slab", "ragged_tail", "zero_slab",
                                  "fp64"])
def test_device_engine_agrees_with_the_host_engine(case):
    """The device engine gives the host engine's bytes, every time."""
    x = _agreement_input(case)
    qh, sh = ENGINES["host"](x)
    qd, sd = ENGINES["device"](x)
    assert sd.dtype == sh.dtype and sd.tobytes() == sh.tobytes()
    assert qd.dtype == qh.dtype and qd.tobytes() == qh.tobytes()
    again = ENGINES["device"](x)
    assert again[0].tobytes() == qd.tobytes()
    assert again[1].tobytes() == sd.tobytes()


def test_windows_in_the_tie_band_are_rounded_on_the_host(monkeypatch):
    """A window with a quotient at a rounding tie is flagged, one of
    whole quotients is not; a device that rounds the flagged windows
    otherwise still yields the host engine's bytes."""
    import jax.numpy as jnp

    ties = np.zeros(QCHUNK, np.float32)        # scale 1: x is the quotient
    ties[0], ties[1:128] = 127.0, np.arange(127) + 0.5
    whole = (np.arange(QCHUNK) % 255 - 127).astype(np.float32)
    x = np.concatenate([ties, whole])
    s = flat._unit_scales(np.asarray(flat._slab_amax(x, QCHUNK)))
    assert np.asarray(flat._slab_q(x, s)[1]).tolist() == [True, False]

    slab_q = flat._slab_q

    def rounds_flagged_windows_up(xd, scales):
        qd, near = slab_q(xd, scales)
        up = jnp.minimum(qd.reshape(near.size, -1).astype(jnp.int32) + 1, 127)
        qd = jnp.where(near[:, None], up, qd.reshape(near.size, -1))
        return qd.astype(jnp.int8).reshape(-1), near

    monkeypatch.setattr(flat, "_slab_q", rounds_flagged_windows_up)
    qh, sh = ENGINES["host"](x)
    qd, sd = ENGINES["device"](x)
    assert sd.tobytes() == sh.tobytes() and qd.tobytes() == qh.tobytes()


@pytest.mark.parametrize("platform,n,engine", [
    ("tpu", TEST_SLAB, "device"),
    ("tpu", TEST_SLAB + 5, "device"),
    ("tpu", TEST_SLAB - 1, "host"),
    ("cpu", TEST_SLAB, "host"),
])
def test_quantize_int8_picks_the_engine(monkeypatch, platform, n, engine):
    """A TPU and a vector of at least SLAB elements take the device
    engine; a CPU platform or a shorter vector the host one."""
    monkeypatch.setattr(flat, "SLAB", TEST_SLAB)
    monkeypatch.setattr(flat, "on_tpu", lambda: platform == "tpu")
    x = _agreement_input("ragged_tail")[:n]
    before = dict(flat.quant_stats)
    q, scales = quantize_int8(x)
    assert {k: v - before[k] for k, v in flat.quant_stats.items()} == {
        e: int(e == engine) for e in ("device", "host")}
    qh, sh = ENGINES["host"](x)
    assert scales.tobytes() == sh.tobytes() and q.tobytes() == qh.tobytes()


def test_concurrent_quantizes_are_exact_and_counted(monkeypatch):
    """More threads than cores quantize at once, half on each engine:
    every result is the host engine's and no count is lost."""
    import sys
    import threading

    monkeypatch.setattr(flat, "SLAB", TEST_SLAB)
    monkeypatch.setattr(flat, "on_tpu", lambda: True)
    x = _agreement_input("ragged_tail")
    want = [ENGINES["host"](x[:n]) for n in (x.size, TEST_SLAB - 1)]
    before = dict(flat.quant_stats)
    bad = []

    def work(i):
        for _ in range(5):
            n = x.size if i % 2 else TEST_SLAB - 1
            q, s = quantize_int8(x[:n])
            wq, ws = want[0 if i % 2 else 1]
            if q.tobytes() != wq.tobytes() or s.tobytes() != ws.tobytes():
                bad.append(i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not bad
    assert {k: v - before[k] for k, v in flat.quant_stats.items()} == {
        "device": 40, "host": 40}


# ---------------------------------------------------------------------------
# cross-version interop matrix
# ---------------------------------------------------------------------------
ENCODERS = ["legacy", "flat", "bf16", "q8"]
LOSSLESS = {"legacy", "flat"}


@pytest.mark.parametrize("codec", ENCODERS)
def test_fit_res_interop_matrix(codec):
    """One decoder, four frame versions: auto-detect + round-trip."""
    arrays = _f32_arrays(seed=3)
    res = FitRes(arrays, 11, {"loss": 0.25})
    dec = decode_fit_res(encode_fit_res(res, codec=codec))
    assert dec.num_examples == 11 and dec.metrics["loss"] == 0.25
    got = dec.materialize()
    assert [g.shape for g in got] == [a.shape for a in arrays]
    for g, a in zip(got, arrays):
        if codec in LOSSLESS:
            assert g.tobytes() == a.tobytes()
        elif codec == "bf16":
            np.testing.assert_allclose(g, a, atol=0, rtol=2 ** -8)
        else:
            assert np.abs(g.astype(np.float64) - a.astype(np.float64)).max() \
                <= _q8_bound(dec.quant)


@pytest.mark.parametrize("codec", ENCODERS)
def test_fit_ins_and_arrays_interop_matrix(codec):
    arrays = _f32_arrays(seed=4)
    tol = {"legacy": 0.0, "flat": 0.0}.get(codec)
    dec = decode_fit_ins(encode_fit_ins(FitIns(arrays, {"round": 2}),
                                        codec=codec))
    assert dec.config["round"] == 2
    back = bytes_to_arrays(arrays_to_bytes(arrays, codec=codec))
    for path in (dec.parameters, back):
        for g, a in zip(path, arrays):
            if tol == 0.0:
                assert g.tobytes() == a.tobytes()
            else:
                np.testing.assert_allclose(
                    g.astype(np.float64), a.astype(np.float64), atol=0.02)
    # client-facing decodes must be writable even for compressed frames
    dec.parameters[0] += 1.0


# repro: allow[codec-literal] reason=deliberately-unregistered bytes probing the UnsupportedCodec path
@pytest.mark.parametrize("magic", [0xF0, 0xF6, 0xFF])
def test_reserved_version_bytes_raise_unsupported_codec(magic):
    frame = encode_fit_res(FitRes(_f32_arrays(), 1, {}), codec="flat")
    doctored = bytes([magic]) + frame[1:]
    for decoder in (decode_fit_res, decode_fit_ins, bytes_to_arrays):
        with pytest.raises(UnsupportedCodec):
            decoder(doctored)


def test_lossy_request_falls_back_to_flat_for_non_fp32():
    """Ineligible payloads (mixed dtype / uint64 SecAgg shares) silently
    ship on the lossless 0xF1 frame — negotiation is advisory."""
    mixed = [np.ones((4, 4), np.float32), np.arange(6, dtype=np.int32)]
    u64 = [RNG.integers(0, 2 ** 63, size=100, dtype=np.uint64)]
    for arrays in (mixed, u64):
        for codec in ("bf16", "q8"):
            b = encode_fit_res(FitRes(arrays, 1, {}), codec=codec)
            assert b[0] == FLAT_MAGIC
            got = decode_fit_res(b).materialize()
            for g, a in zip(got, arrays):
                assert g.tobytes() == a.tobytes()
    assert not quantizable(layout_of(mixed))


def test_quantized_decode_is_zero_copy():
    arrays = [RNG.normal(size=(256, 64)).astype(np.float32)]
    for codec, magic in (("bf16", BF16_MAGIC), ("q8", Q8_MAGIC)):
        b = encode_fit_res(FitRes(arrays, 1, {}), codec=codec)
        assert b[0] == magic
        q = decode_fit_res(b).quant
        assert not q.data.flags["OWNDATA"]
        if q.scales is not None:
            assert not q.scales.flags["OWNDATA"]


def test_q8_wire_size_is_4x_smaller():
    arrays = [RNG.normal(size=(1 << 20,)).astype(np.float32)]
    flat = encode_fit_res(FitRes(arrays, 1, {}), codec="flat")
    q8 = encode_fit_res(FitRes(arrays, 1, {}), codec="q8")
    assert len(flat) / len(q8) > 3.5


# ---------------------------------------------------------------------------
# delta encoding
# ---------------------------------------------------------------------------
def test_delta_roundtrip_bounded_by_update_magnitude():
    base_arrays = _f32_arrays(seed=7)
    delta_scale = 1e-3                     # update << weights
    result = [a + RNG.normal(0, delta_scale, size=a.shape).astype(np.float32)
              for a in base_arrays]
    base = FlatParams.from_arrays(base_arrays)
    b = encode_fit_res(FitRes(result, 5, {}), codec="q8", base=base)
    dec = decode_fit_res(b)
    assert dec.quant.is_delta
    dec.quant.base = base
    got = dec.materialize()
    bound = _q8_bound(dec.quant)
    assert bound < delta_scale             # bound scales with the UPDATE
    for g, r in zip(got, result):
        assert np.abs(g.astype(np.float64) - r.astype(np.float64)).max() \
            <= bound


def test_delta_without_base_raises_clearly():
    base = FlatParams.from_arrays(_f32_arrays(seed=8))
    b = encode_fit_res(FitRes(_f32_arrays(seed=9), 5, {}), codec="q8",
                       base=base)
    dec = decode_fit_res(b)
    with pytest.raises(ValueError, match="base"):
        dec.materialize()
    # a delta frame must never be decodable as plain client-facing params
    with pytest.raises(ValueError, match="delta"):
        decode_fit_ins(b)


def test_delta_base_layout_mismatch_falls_back_lossless():
    base = FlatParams.from_arrays([np.ones((3, 3), np.float32)])
    result = _f32_arrays(seed=10)
    b = encode_fit_res(FitRes(result, 5, {}), codec="q8", base=base)
    assert b[0] == FLAT_MAGIC


# ---------------------------------------------------------------------------
# fused dequantize+accumulate kernels
# ---------------------------------------------------------------------------
def _quantized_results(n_clients, seed, base):
    rng = np.random.default_rng(seed)
    results_f32, results_q = [], []
    for c in range(n_clients):
        arrays = [a + rng.normal(0, 1e-3, size=a.shape).astype(np.float32)
                  for a in base.to_arrays()]
        w = 10 + 3 * c
        results_f32.append((f"site-{c}", FitRes(arrays, w, {})))
        dec = decode_fit_res(encode_fit_res(FitRes(arrays, w, {}),
                                            codec="q8", base=base))
        dec.quant.base = base
        results_q.append((f"site-{c}", dec))
    return results_f32, results_q


@pytest.mark.parametrize("name,kw", [
    ("fedavg", {}), ("fedavg", {"low_memory": True}),
    ("fedmedian", {}), ("fedtrimmedmean", {"beta": 0.25}),
    ("krum", {"num_byzantine": 1, "num_selected": 2}),
])
def test_strategies_consume_compressed_results(name, kw):
    """Accumulators stream QuantParams through the fused kernels; output
    matches the fp32 path within the quantization bound."""
    base = FlatParams.from_arrays(_f32_arrays(seed=31))
    results_f32, results_q = _quantized_results(6, 32, base)
    current = base.to_arrays()
    want, _ = make_strategy(name, **kw).aggregate_fit(
        1, results_f32, [], current)
    got, _ = make_strategy(name, **kw).aggregate_fit(
        1, results_q, [], current)
    bound = max(_q8_bound(r.quant) for _, r in results_q)
    for g, w in zip(got, want):
        assert np.abs(g.astype(np.float64) - w.astype(np.float64)).max() \
            <= 2 * bound + 1e-9


def test_batch_only_strategy_sees_materialized_parameters():
    """A FedAvg subclass overriding only the batch aggregate_fit predates
    the compressed wire format and reads res.parameters directly; the base
    accumulator must materialize quantized results before deferring."""
    from repro.fl.strategy import FedAvg

    seen = []

    class BatchOnly(FedAvg):
        def aggregate_fit(self, rnd, results, failures, current):
            for _, r in results:
                assert r.parameters is not None
                seen.append(len(r.parameters))
            return current, {"n": len(results)}

    base = FlatParams.from_arrays(_f32_arrays(seed=51))
    _, results_q = _quantized_results(3, 52, base)
    strat = BatchOnly()
    acc = strat.fit_accumulator(1, base.to_arrays())
    assert type(acc).__name__ == "FitAccumulator"   # routed to the base
    for node, r in results_q:
        acc.add(node, r)
    _, m = acc.finalize([])
    assert m["n"] == 3 and seen == [3, 3, 3]


def test_incremental_accumulator_matches_batch_on_compressed():
    base = FlatParams.from_arrays(_f32_arrays(seed=41))
    _, results_q = _quantized_results(5, 42, base)
    strat = make_strategy("fedavg")
    acc = strat.fit_accumulator(1, base.to_arrays())
    for node, r in results_q:
        acc.add(node, r)
    got, m = acc.finalize([])
    want, _ = strat.aggregate_fit(1, results_q, [], base.to_arrays())
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert m["num_clients"] == 5


# ---------------------------------------------------------------------------
# SecAgg: mask cancellation in the quantized integer domain
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 50), st.integers(2, 5),
       st.integers(1, 100))
def test_secagg_masks_cancel_in_integer_domain(seed, n, n_sites, round_):
    """Pairwise masks over the fixed-point uint64 flat buffer cancel
    EXACTLY (mod 2^64) in the server's wrapping sum, whatever the values,
    fleet size, or round."""
    from repro.fl.mods import _prg_mask_flat, quantize

    rng = np.random.default_rng(seed)
    layout = layout_of([np.empty(n, np.float32)])
    xs = [rng.normal(0, 100, size=n) for _ in range(n_sites)]
    qs = [quantize(x) for x in xs]
    masked = []
    for i in range(n_sites):
        share = qs[i].copy()
        for j in range(n_sites):
            if i == j:
                continue
            pair_seed = 7_000_003 * min(i, j) + max(i, j)
            share += _prg_mask_flat(pair_seed, round_, layout,
                                    positive=i < j)
        masked.append(share)
    got = np.zeros(n, np.uint64)
    for m in masked:
        got += m
    want = np.zeros(n, np.uint64)
    for q in qs:
        want += q
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# negotiation (unit + end-to-end)
# ---------------------------------------------------------------------------
def test_client_app_advertises_codecs():
    from repro.fl.client import ClientApp, NumPyClient

    class C(NumPyClient):
        def get_properties(self, config):
            return {"gpu": 1}

    app = ClientApp(lambda cid: C().to_client())
    t = TaskIns("get_properties", 0, b"", task_id="t")
    tr = decode_task_res(app.handle(encode_task_ins(t)))
    props = decode_properties_res(tr.payload)
    assert props["gpu"] == 1
    assert set(WIRE_CODECS) <= set(props["codecs"])


class _FakeDriver:
    """Scripted driver: maps task_type -> node -> TaskRes payload/error."""

    def __init__(self, nodes, on_properties):
        self.nodes = nodes
        self.on_properties = on_properties

    def node_ids(self):
        return list(self.nodes)

    def send_and_receive_iter(self, tasks, timeout):
        from repro.fl.messages import (TaskRes, decode_task_ins,
                                       encode_task_res)
        for node, tb in sorted(tasks.items()):
            t = decode_task_ins(tb)
            assert t.task_type == "get_properties"
            payload, error = self.on_properties(node)
            yield node, encode_task_res(TaskRes(
                t.task_type, t.round, payload, task_id=t.task_id,
                error=error))


def _negotiate(on_properties, codec="q8"):
    from repro.fl.server import ServerApp, ServerConfig
    from repro.fl.strategy import FedAvg

    app = ServerApp(ServerConfig(codec=codec), FedAvg())
    return app._negotiate_codec(_FakeDriver(["a", "b"], on_properties),
                                ["a", "b"])


def test_negotiation_picks_advertised_codec():
    from repro.fl.messages import encode_properties_res
    ok = encode_properties_res({"codecs": ["flat", "q8", "bf16"]})
    assert _negotiate(lambda node: (ok, "")) == ("q8", "")


def test_negotiation_demotes_when_any_node_lacks_codec():
    """Demotion is never silent: the note names the culprit node."""
    from repro.fl.messages import encode_properties_res
    full = encode_properties_res({"codecs": ["flat", "q8"]})
    old = encode_properties_res({"codecs": ["flat", "legacy"]})
    codec, note = _negotiate(lambda node: (full if node == "a" else old, ""))
    assert codec == "flat" and "b" in note and "q8" in note


def test_negotiation_demotes_when_node_errors_on_unknown_task():
    """Seed-era peers error on get_properties — the fleet stays lossless."""
    from repro.fl.messages import encode_properties_res
    full = encode_properties_res({"codecs": ["flat", "q8"]})
    codec, note = _negotiate(
        lambda node: (full, "") if node == "a"
        else (b"", "unknown task type"))
    assert codec == "flat" and "b" in note


def test_end_to_end_negotiated_q8_converges_within_tolerance():
    from repro.core import run_native
    from repro.fl import FedAvg, ServerApp, ServerConfig
    from repro.fl.quickstart import make_client_app

    sites = ["site-1", "site-2", "site-3"]
    h_flat = run_native(ServerApp(ServerConfig(num_rounds=2), FedAvg()),
                        lambda s: make_client_app(s), sites)
    h_q8 = run_native(ServerApp(ServerConfig(num_rounds=2, codec="q8"),
                                FedAvg()),
                      lambda s: make_client_app(s), sites)
    assert h_q8.rounds[-1].metrics["wire_codec"] == "q8"
    assert "wire_codec" not in h_flat.rounds[-1].metrics
    for (_, lf), (_, lq) in zip(h_flat.losses(), h_q8.losses()):
        assert abs(lf - lq) < 0.05, (lf, lq)
    d = max(float(np.abs(a.astype(np.float64) - b.astype(np.float64)).max())
            for a, b in zip(h_flat.final_parameters, h_q8.final_parameters))
    assert d < 0.05


def test_demoted_run_reports_wire_codec_flat_with_note():
    """ServerConfig requests q8 but one node only speaks flat/legacy: the
    run demotes AND says so in every round's metrics."""
    from repro.core import run_native
    from repro.fl import ClientApp, FedAvg, ServerApp, ServerConfig
    from repro.fl.quickstart import QuickstartClient

    class OldClient(QuickstartClient):
        def get_properties(self, config):
            return {"codecs": ["flat", "legacy"]}

    sites = ["site-1", "site-2", "site-3"]

    def app_fn(site):
        cls = OldClient if site == "site-2" else QuickstartClient
        return ClientApp(lambda cid: cls(site).to_client())

    h = run_native(ServerApp(ServerConfig(num_rounds=1, codec="q8"),
                             FedAvg()), app_fn, sites)
    m = h.rounds[-1].metrics
    assert m["wire_codec"] == "flat"
    assert "site-2" in m["wire_codec_demotion"]


def test_end_to_end_secagg_composes_with_q8_negotiation():
    """SecAgg's uint64 masked shares ship losslessly (0xF1) under a q8
    negotiation: masks still cancel exactly, the run matches the plain
    FedAvg q8 run up to the lossless-vs-lossy uplink difference."""
    import zlib
    from repro.core import run_native
    from repro.fl import (FedAvg, SecAggFedAvg, SecAggMod, ServerApp,
                          ServerConfig)
    from repro.fl.quickstart import make_client_app

    sites = ["site-1", "site-2", "site-3"]

    def seed_fn(a, b):
        lo, hi = sorted([a, b])
        return zlib.crc32(f"{lo}|{hi}".encode())

    plain = run_native(ServerApp(ServerConfig(num_rounds=2, codec="q8"),
                                 FedAvg()),
                       lambda s: make_client_app(s), sites)
    sec = run_native(ServerApp(ServerConfig(num_rounds=2, codec="q8"),
                               SecAggFedAvg()),
                     lambda s: make_client_app(s, mods=[SecAggMod(
                         site=s, peers=sites, pairwise_seed_fn=seed_fn)]),
                     sites)
    d = max(float(np.abs(a.astype(np.float64) - b.astype(np.float64)).max())
            for a, b in zip(plain.final_parameters, sec.final_parameters))
    assert d < 0.02, d
