"""The program-span readers and gap labels on a second synthetic trace
whose every number can be counted by hand.

One round spans [0, 1000) ns.  The device runs one train-step op at
[300, 400) and one fold kernel call at [700, 720), so its idle gaps are
[0, 300), [400, 700) and [720, 1000).  The host has the server's thread
(the round, the fold's finalize, its staging, kernel call and unstaging,
a downlink encode and a result delivery), site 1's SuperNode thread (an
empty pull, a pull that finds a task and its decodes, the fit with its
copies, the uplink encode and push, another empty pull), the relay
thread (the SuperLink serving the pull that found a task) and the
SuperLink's thread (serving the push).
"""
import pytest
from jax.profiler import ProfileData

import gaps
import program_spans as ps
import readings
import trace_reduce as tr
import test_trace_reduce as first

NAMES = ("bench.fit", "bench.eval", "bench.fold")
PULL = {"method": "pull_task_ins"}
DEVICE = {
    "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion()", 300, 400),
                (first.AGG, 700, 720)],
    "XLA Modules": [("jit_train_step(1)", 300, 400),
                    ("jit_wrapped(2)", 700, 720)],
}
SERVER = [
    ("bench.round", 0, 1000, {"round": "2"}),
    ("repro.codec.encode", 10, 60,
     {"op": "fit_ins", "codec": "q8", "nbytes": 500}),
    ("repro.codec.encode", 60, 70,
     {"op": "task_ins", "codec": "msgpack", "nbytes": 520}),
    ("repro.superlink.deliver", 580, 585, {"queued_s": 0.25}),
    ("bench.fold", 600, 800, {"op": "finalize"}),
    ("repro.fold.stage", 610, 690, {"nbytes": 2000, "clients": 2}),
    ("repro.fold.kernel", 690, 730, {"nbytes": 1000, "clients": 2}),
    ("repro.fold.unstage", 730, 790, {"nbytes": 800, "clients": 2}),
]
SITE = [
    ("repro.relay.request", 80, 90, {**PULL, "nbytes": 40, "hit": 0}),
    ("repro.relay.request", 100, 200, {**PULL, "nbytes": 600, "hit": 1}),
    ("repro.codec.decode", 200, 210,
     {"op": "task_ins", "codec": "msgpack", "nbytes": 600}),
    ("repro.codec.decode", 210, 265,
     {"op": "fit_ins", "codec": "q8", "nbytes": 500}),
    ("bench.fit", 265, 450, {"site": "site-1"}),
    ("repro.xfer.h2d", 270, 290, {"nbytes": 400}),
    ("repro.xfer.d2h", 410, 430, {"nbytes": 400}),
    ("repro.codec.encode", 450, 500,
     {"op": "fit_res", "codec": "q8", "nbytes": 300}),
    ("repro.relay.request", 500, 560,
     {"method": "push_task_res", "nbytes": 320}),
    ("repro.relay.request", 850, 860, {**PULL, "nbytes": 40, "hit": 0}),
]
RELAY = [("repro.superlink.serve", 120, 180, {**PULL, "queued_s": 0.05})]
LINK = [("repro.superlink.serve", 520, 540, {"method": "push_task_res"})]


def _stat(key, value):
    if isinstance(value, str):
        return f'metadata_id: {key} str_value: "{value}"'
    if isinstance(value, int):
        return f"metadata_id: {key} int64_value: {value}"
    return f"metadata_id: {key} double_value: {value!r}"


def _plane(pid, name, lines):
    """A text-proto XPlane whose events carry string, int and float
    stats, as the program's spans do."""
    evs = [e for line in lines.values() for e in line]
    meta = {n: i + 1 for i, n in enumerate(sorted({e[0] for e in evs}))}
    keys = sorted({k for e in evs for k in (e[3] if len(e) > 3 else {})})
    smeta = {k: i + 1 for i, k in enumerate(keys)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for li, (lname, line) in enumerate(lines.items()):
        out.append(f'lines {{ id: {li + 1} name: "{lname}" timestamp_ns: 0')
        for e in line:
            stats = "".join(f" stats {{ {_stat(smeta[k], v)} }}"
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
            + _plane(2, "/host:CPU", {"python3": SERVER, "site-1": SITE,
                                      "relay": RELAY, "link": LINK}))
    profile = ProfileData.from_text_proto(text)
    t = tr.from_profile(profile)
    t.program = ps.from_profile(profile)
    return t


@pytest.fixture
def ctx(trace):
    return readings.Context(
        trace=trace, cfg={}, mix={"sites": 1, "local_steps": 1},
        peaks={"bf16_flops": 1e5, "hbm_bytes_per_s": 1e12},
        fit_flops_per_round=0.0, eval_flops_per_round=0.0)


def test_program_spans_are_kept_apart_from_the_benchmark_spans(trace):
    assert sorted(trace.spans) == ["bench.fit", "bench.fold"]
    assert len(trace.rounds) == 1
    assert {n: len(v) for n, v in trace.program.items()} == {
        "repro.codec.encode": 3, "repro.codec.decode": 2,
        "repro.relay.request": 4,
        "repro.superlink.serve": 2, "repro.superlink.deliver": 1,
        "repro.xfer.h2d": 1, "repro.xfer.d2h": 1, "repro.fold.stage": 1,
        "repro.fold.kernel": 1, "repro.fold.unstage": 1}
    req = trace.program["repro.relay.request"]
    assert [(e.stats["method"], e.stats.get("hit")) for e in req] == [
        ("pull_task_ins", 0), ("pull_task_ins", 1), ("push_task_res", None),
        ("pull_task_ins", 0)]


def test_program_span_readers(ctx):
    # encodes 50 + 10 + 50, decodes 10 + 55
    assert readings.read("codec_s", ctx) == pytest.approx(175e-9)
    # 500 + 520 + 300 + 600 + 500 frame bytes over those 175 ns
    assert readings.read("codec_gbps", ctx) == pytest.approx(2420 / 175)
    # the pull that found a task (100) and the push (60), not the polls
    assert readings.read("relay_s", ctx) == pytest.approx(160e-9)
    assert readings.read("pull_hit_pct", ctx) == pytest.approx(100 / 3)
    # the served task's 0.05 s and the delivered result's 0.25 s
    assert readings.read("queue_wait_s", ctx) == pytest.approx(0.30)
    # staging 80, unstaging 60; the kernel call is not staging
    assert readings.read("fold_stage_s", ctx) == pytest.approx(140e-9)
    assert readings.read("fold_call_s", ctx) == pytest.approx(40e-9)
    assert readings.read("xfer_s", ctx) == pytest.approx(40e-9)


def test_gap_labels_count_the_program_spans(trace):
    gaps = tr.gaps(trace, "/device:TPU:0", 0, 1000)
    assert gaps == [(0, 300), (400, 700), (720, 1000)]
    # the pull that found a task 100 (its serve under it), the downlink
    # decode 55 and encode 50; the empty pull and 30 ns no span covers
    # lose, as does the fit's 20 outside its host-to-device copy
    assert ps.label(trace, gaps[0], NAMES) == \
        "relay.request:pull_task_ins+codec.decode:fit_ins"
    # staging 80 inside the fold's finalize, the push 60, the uplink
    # encode 50, 35 no span covers, the fit 30 outside its copy
    assert ps.label(trace, gaps[1], NAMES) == \
        "fold.stage+relay.request:push_task_res"
    # 190 ns no span covers beat unstaging's 60; the kernel call's 10,
    # the finalize's last 10 and the empty pull's 10 trail
    assert ps.label(trace, gaps[2], NAMES) == "relay_codec+fold.unstage"
    # the reduction's own labels see only the benchmark's spans: 150 ns
    # no fit or fold span covers, the finalize 100, the fit 50
    assert tr.label(trace, gaps[1], NAMES) == \
        "relay_codec+fold:finalize"


def test_cover_gives_each_instant_to_one_label(trace):
    got = ps.cover(trace, (0, 300), NAMES)
    assert got == {
        "fit:site-1": 5 + 10, "codec.encode:fit_ins": 50,
        "codec.encode:task_ins": 10, "relay:poll": 10,
        "relay.request:pull_task_ins": 100, "codec.decode:task_ins": 10,
        "codec.decode:fit_ins": 55, "xfer.h2d": 20,
        "relay_codec": 10 + 10 + 10}
    assert sum(got.values()) == 300


def test_empty_pulls_and_hops_take_only_time_nothing_else_covers(trace):
    e = trace.program["repro.relay.request"]
    assert [ps.program_label(x) for x in e] == [
        "relay:poll", "relay.request:pull_task_ins",
        "relay.request:push_task_res", "relay:poll"]
    assert ps.label(trace, (80, 90), NAMES) == "relay:poll"
    assert ps.label(trace, (120, 180), NAMES) == \
        "relay.request:pull_task_ins"
    hops = {n: trace.program[n] for n in ps.HOPS}
    assert ps.label(trace, (120, 180), NAMES, spans=hops) == \
        "superlink.serve:pull_task_ins"


@pytest.mark.parametrize("gap", range(4))
def test_labels_without_program_spans_are_the_reductions(gap):
    """On the first synthetic trace, which has no program spans, the
    labels are ``trace_reduce.label``'s."""
    t = tr.from_profile(ProfileData.from_text_proto(
        first._plane(1, "/device:TPU:0", first.DEVICE) + "\n"
        + first._plane(2, "/host:CPU", {"python3": first.HOST})))
    t.program = {}
    g = tr.gaps(t, "/device:TPU:0", 0, 1000)[gap]
    assert ps.label(t, g, NAMES) == tr.label(t, g, NAMES)


def test_readers_without_program_spans_return_none(ctx, tmp_path,
                                                   monkeypatch):
    bare = tr.Trace(rounds=ctx.trace.rounds, spans=ctx.trace.spans)
    monkeypatch.setattr(ps, "TRACE_DIR", tmp_path)   # no trace file
    c = readings.Context(bare, {}, ctx.mix, ctx.peaks, 0.0, 0.0)
    for name in ("codec_s", "codec_gbps", "relay_s", "pull_hit_pct",
                 "queue_wait_s", "fold_stage_s", "fold_call_s", "xfer_s"):
        assert readings.read(name, c) is None
    assert bare.program == {}


def test_the_gap_report_splits_the_window_by_layer(trace):
    got = gaps.report(trace)
    assert got["rounds"] == 1
    assert got["window_s"] == pytest.approx(1000e-9)
    by = {k: round(v * 1e9) for k, v in got["by_layer"].items()}
    assert by == {
        # the found pull 100, the push 60 (their serves under them), the
        # empty pulls 10 + 10, the delivery 5
        "relay": 185,
        # encodes 50 + 10 + 50, decodes 10 + 55
        "codec": 175,
        # the fit 185 less its copies 20 + 20
        "fit": 145, "copies": 40,
        "fold_staging": 80 + 60, "fold_call": 40,
        # the finalize's 10 before staging and 10 after unstaging
        "bench_fold_only": 20,
        "uncovered": 255}
    assert sum(by.values()) == 1000
    # run.py's breakdown, labelled by the program's spans
    assert [g[0] for g in got["idle_gaps"]] == [
        "relay.request:pull_task_ins+codec.decode:fit_ins",
        "fold.stage+relay.request:push_task_res",
        "relay_codec+fold.unstage"]
    assert gaps.layer("relay:poll") == "relay"
    assert gaps.layer("superlink.serve:pull_task_ins") == "relay"
