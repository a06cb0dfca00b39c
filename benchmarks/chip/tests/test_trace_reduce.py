"""The trace reduction and the per-layer readers on a small synthetic
trace whose every number can be counted by hand.

One round spans [0, 1000) ns.  The device runs two train-step ops and one
fold kernel call; the host has two fit spans, an evaluate span and a fold
span.
"""
import pytest
from jax.profiler import ProfileData

import readings
import trace_reduce as tr

AGG = ('%agg_weighted_sum.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) '
       'custom-call(f32[4,4]{1,0} %a, s8[4,8,128]{2,1,0} %b), '
       'custom_call_target="tpu_custom_call"')
DEVICE = {
    "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion()", 120, 200),
                ("%fusion.2 = bf16[8]{0} fusion()", 210, 280),
                (AGG, 520, 540)],
    "XLA Modules": [("jit_train_step(123)", 110, 290),
                    ("jit_wrapped(9)", 515, 545)],
}
HOST = [("bench.round", 0, 1000, {"round": "3"}),
        ("bench.fit", 100, 300, {"site": "site-1"}),
        ("bench.fit", 150, 350, {"site": "site-2"}),
        ("bench.fold", 500, 600, {"op": "finalize"}),
        ("bench.eval", 700, 750, {"site": "site-1"}),
        ("np.asarray", 10, 20, {})]


def _plane(pid, name, lines):
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for evs in lines.values() for e in evs
                   for k in (e[3] if len(e) > 3 else {})})
    smeta = {k: i + 1 for i, k in enumerate(keys)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for li, (lname, evs) in enumerate(lines.items()):
        out.append(f'lines {{ id: {li + 1} name: "{lname}" timestamp_ns: 0')
        for e in evs:
            stats = "".join(
                f' stats {{ metadata_id: {smeta[k]} str_value: "{v}" }}'
                for k, v in (e[3] if len(e) > 3 else {}).items())
            out.append(f"events {{ metadata_id: {meta[e[0]]} offset_ps: "
                       f"{e[1] * 1000} duration_ps: {(e[2] - e[1]) * 1000}"
                       f"{stats} }}")
        out.append("}")
    for n, i in meta.items():
        esc = n.replace('"', '\\"')
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{esc}" }} }}')
    for k, i in smeta.items():
        out.append(f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{k}" }} }}')
    out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def trace():
    text = (_plane(1, "/device:TPU:0", DEVICE) + "\n"
            + _plane(2, "/host:CPU", {"python3": HOST}))
    return tr.from_profile(ProfileData.from_text_proto(text))


@pytest.fixture
def ctx(trace):
    return readings.Context(
        trace=trace, cfg={}, mix={"sites": 1, "local_steps": 1},
        peaks={"bf16_flops": 1e5, "hbm_bytes_per_s": 1e12},
        fit_flops_per_round=0.009, eval_flops_per_round=0.001)


def test_the_loader_keeps_device_ops_programs_and_bench_spans(trace):
    assert trace.window == (0, 1000)
    assert [tr.op_name(e) for e in trace.ops["/device:TPU:0"]] == [
        "fusion.1", "fusion.2", "agg_weighted_sum.1"]
    assert sorted(trace.spans) == ["bench.eval", "bench.fit", "bench.fold"]
    assert [e.stats["site"] for e in trace.spans["bench.fit"]] == [
        "site-1", "site-2"]


def test_busy_time_is_the_union_of_op_intervals(trace):
    assert tr.busy(trace, 0, 1000) == {"/device:TPU:0": 80 + 70 + 20}
    assert tr.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert tr.length([(0, 5), (3, 8), (10, 12)]) == 10


def test_gaps_come_longest_first_and_are_labelled_by_host_span(trace):
    gaps = tr.gaps(trace, "/device:TPU:0", 0, 1000)
    assert gaps == [(540, 1000), (280, 520), (0, 120), (200, 210)]
    names = ("bench.fit", "bench.eval", "bench.fold")
    # 60 ns under the fold, 50 under evaluate, 350 under no span
    assert tr.label(trace, gaps[0], names) == "relay_codec+fold:finalize"
    # site-2's fit covers 70 ns, site-1's and the fold 20 each, 150 none
    assert tr.label(trace, gaps[1], names) == "relay_codec+fit:site-2"
    assert tr.label(trace, gaps[3], names) == "fit:site-1+fit:site-2"


def test_ops_are_named_with_their_program(trace):
    dev = "/device:TPU:0"
    ops = trace.ops[dev]
    assert [tr.program_of(trace, dev, e) for e in ops] == [
        "jit_train_step", "jit_train_step", "jit_wrapped"]
    assert tr.op_shapes(ops[2]).endswith("s8[4,8,128]{2,1,0} %b")


def test_span_readers(ctx):
    assert readings.read("fit_s", ctx) == pytest.approx(400e-9)
    assert readings.read("eval_s", ctx) == pytest.approx(50e-9)
    assert readings.read("fold_s", ctx) == pytest.approx(100e-9)
    # the window less the union of fit, evaluate and fold spans
    assert readings.read("relay_codec_s", ctx) == pytest.approx(
        (1000 - 250 - 100 - 50) * 1e-9)


def test_device_readers(ctx):
    assert readings.read("idle_frac", ctx) == pytest.approx(83.0)
    # 0.009 FLOP over 180 ns of train-step program at 1e5 FLOP/s
    assert readings.read("fit_step_mfu", ctx) == pytest.approx(50.0)
    # 12352 bytes at 1e12 B/s over 20 ns of kernel
    assert readings.read("fold_roofline", ctx) == pytest.approx(61.76)
    # 0.01 FLOP over a 1000 ns round at 1e5 FLOP/s
    assert readings.read("round_mfu", ctx) == pytest.approx(10.0)


def test_readers_with_nothing_to_read_return_none(ctx):
    empty = tr.Trace(rounds=ctx.trace.rounds, spans={}, ops={}, modules={})
    bare = readings.Context(empty, {}, ctx.mix, ctx.peaks, 1.0, 1.0)
    for name in ("fit_s", "eval_s", "fold_s", "fit_step_mfu",
                 "fold_roofline", "idle_frac"):
        assert readings.read(name, bare) is None
    no_round = readings.Context(tr.Trace(), {}, ctx.mix, ctx.peaks, 1.0, 1.0)
    assert readings.read("round_mfu", no_round) is None


@pytest.fixture(scope="module")
def four_chip_trace():
    """One logical train step on four chips: a ``train_step`` program
    event on each device, of 180, 170, 160 and 150 ns, in a 1000 ns
    round."""
    planes = [_plane(i + 1, f"/device:TPU:{i}", {
        "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion()", 120, 200)],
        "XLA Modules": [("jit_train_step(123)", 110, 290 - 10 * i)]})
        for i in range(4)]
    text = "\n".join(planes + [_plane(9, "/host:CPU", {"python3": HOST})])
    return tr.from_profile(ProfileData.from_text_proto(text))


@pytest.mark.parametrize("chips, fit_mfu, round_mfu", [
    # 4 events / 4 chips = 1 step of 0.0066 FLOP over the events' 660 ns
    # at 1e5 FLOP/s; 0.008 FLOP over a 1000 ns round x 4 chips x 1e5
    (4, 10.0, 2.0),
    # one chip's arithmetic, as before chips were counted: 4 steps over
    # 660 ns, the round over one chip's peak
    (1, 40.0, 8.0)])
def test_mfu_readers_count_the_chips(four_chip_trace, chips, fit_mfu,
                                     round_mfu):
    c = readings.Context(
        trace=four_chip_trace, cfg={}, mix={"sites": 1, "local_steps": 1},
        peaks={"bf16_flops": 1e5, "hbm_bytes_per_s": 1e12},
        fit_flops_per_round=0.0066, eval_flops_per_round=0.0014,
        chips=chips)
    assert readings.read("fit_step_mfu", c) == pytest.approx(fit_mfu)
    assert readings.read("round_mfu", c) == pytest.approx(round_mfu)
    # a mean over the chips: 80 ns of 1000 busy on each
    assert readings.read("idle_frac", c) == pytest.approx(92.0)
