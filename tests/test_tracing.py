"""Program spans (``repro.utils.tracing``) in a traced federation.

A two-round, two-site q8 FedAvg federation runs through ``run_in_flare``
with the example's ``LMClient`` on a one-layer model and the Pallas fold,
inside ``jax.profiler.trace``; the trace is read back with
``ProfileData`` and the benchmark's ``program_spans.host_events``.  It
must hold every program span with its arguments (a q8 encode also the
engine that quantized it), codec spans must not nest on a thread, and every task the server pushed must come back from
exactly one pull that reports ``hit=1``.
"""
import glob
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.profiler import ProfileData  # noqa: E402

from repro.core import run_in_flare  # noqa: E402
from repro.core.superlink import NativeConnection, SuperLink  # noqa: E402
from repro.data.loader import FederatedDataLoader  # noqa: E402
from repro.fl import FedAvg, ServerApp, ServerConfig  # noqa: E402
from repro.fl.client import ClientApp  # noqa: E402
from repro.fl.flat import FlatParams  # noqa: E402
from repro.fl.messages import (FitRes, arrays_to_bytes,  # noqa: E402
                               decode_fit_res, encode_fit_res)
from repro.runtime import FlareRuntime  # noqa: E402
from repro.utils import tracing  # noqa: E402
from test_federated_llm import _tiny  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
# the benchmark's reader of program spans (appended: its module names
# never shadow the repo's)
sys.path.append(str(_ROOT / "benchmarks" / "chip"))
import program_spans  # noqa: E402
SITES = ["site-1", "site-2"]
CODEC_OPS = {"fit_ins", "fit_res", "evaluate_ins", "evaluate_res",
             "task_ins", "task_res"}
# span -> the arguments every one of its events carries
ARGS = {
    "repro.codec.encode": {"op", "codec", "nbytes"},
    "repro.codec.decode": {"op", "codec", "nbytes"},
    "repro.relay.request": {"method", "nbytes"},
    "repro.superlink.serve": {"method"},
    "repro.superlink.deliver": {"queued_s"},
    "repro.xfer.h2d": {"nbytes"},
    "repro.xfer.d2h": {"nbytes"},
    "repro.fold.stage": {"nbytes", "clients"},
    "repro.fold.kernel": {"nbytes", "clients"},
    "repro.fold.unstage": {"nbytes", "clients"},
}


def _lm_client():
    spec = importlib.util.spec_from_file_location(
        "federated_llm_example", _ROOT / "examples" / "federated_llm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.LMClient


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``(events, pushed)``: the trace's ``repro.*`` host events as
    ``(thread line, name, start, end, args)``, and the ids of every task
    the server pushed."""
    LMClient = _lm_client()
    cfg, tcfg = _tiny()
    loader = FederatedDataLoader(cfg.vocab_size, tcfg.seq_len,
                                 num_sites=len(SITES),
                                 batch_per_site=tcfg.global_batch, seed=3,
                                 non_iid_alpha=0.5, prefetch=1)

    def client_app_fn(site):
        return ClientApp(client_fn=lambda cid: LMClient(
            site, cfg, tcfg, loader, 2).to_client())

    init = LMClient("site-1", cfg, tcfg, loader, 2).get_parameters({})
    pushed = []
    push = SuperLink.push_task_ins

    def counting_push(self, node_id, task):
        tid = push(self, node_id, task)
        pushed.append(tid)
        return tid

    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    rt = FlareRuntime(request_timeout=60.0)
    try:
        for s in SITES:
            rt.provision_site(s)
        server = ServerApp(
            config=ServerConfig(num_rounds=2, round_timeout=60, codec="q8"),
            strategy=FedAvg(initial_parameters=init, backend="pallas"))
        with pytest.MonkeyPatch.context() as mp, \
                jax.profiler.trace(str(out), profiler_options=opts):
            mp.setattr(SuperLink, "push_task_ins", counting_push)
            run_in_flare(rt, server, client_app_fn, SITES, timeout=120)
            # decodes outside any codec function: a lazy dequantize of a
            # q8 frame, and of a q8 delta once its base is attached
            base = FlatParams.from_arrays(init)
            arrays_to_bytes(init)
            decode_fit_res(encode_fit_res(FitRes(init, 1),
                                          codec="q8")).quant.to_flat()
            res = decode_fit_res(encode_fit_res(FitRes(init, 1),
                                                codec="q8", base=base))
            res.quant.base = base
            res.materialize()
    finally:
        rt.shutdown()
    prof = ProfileData.from_file(
        glob.glob(str(out / "plugins/profile/*/*.xplane.pb"))[0])
    events = [(line, e.name, e.start, e.end, e.stats)
              for line, e in program_spans.host_events(prof)]
    return events, pushed


def test_span_is_one_shared_no_op_without_a_trace():
    assert not tracing.enabled()
    assert tracing.span("repro.x", a=1) is tracing.OFF
    assert tracing.outermost("repro.y") is tracing.OFF
    with tracing.span("repro.x") as s:
        tracing.annotate(s, nbytes=1)        # a no-op on the no-op
    assert s is None


def test_a_traced_federation_holds_every_span_with_its_args(traced):
    events, _ = traced
    by_name = {}
    for _, name, start, end, args in events:
        assert end >= start
        by_name.setdefault(name, []).append(args)
    assert set(by_name) == set(ARGS)
    for name, keys in ARGS.items():
        for args in by_name[name]:
            assert keys <= set(args), (name, args)
            if "nbytes" in keys:
                assert args["nbytes"] > 0, (name, args)
    ops = {k: {a["op"] for a in by_name[f"repro.codec.{k}"]}
           for k in ("encode", "decode")}
    assert ops["encode"] >= CODEC_OPS | {"arrays"}
    assert ops["decode"] >= CODEC_OPS | {"peek_params", "to_flat",
                                         "materialize"}
    assert {a["codec"] for a in by_name["repro.codec.encode"]
            if a["op"] == "fit_res"} == {"q8"}
    requests = by_name["repro.relay.request"]
    assert {a["method"] for a in requests} == {
        "register", "pull_task_ins", "push_task_res"}
    assert all(("hit" in a) == (a["method"] == "pull_task_ins")
               for a in requests)
    served = [a for a in by_name["repro.superlink.serve"]
              if "queued_s" in a]
    assert served and all(a["method"] == "pull_task_ins"
                          and a["queued_s"] >= 0 for a in served)
    assert all(a["queued_s"] >= 0
               for a in by_name["repro.superlink.deliver"])
    assert {a["clients"] for a in by_name["repro.fold.stage"]} == {2}


def test_every_q8_encode_names_its_quantizer_engine(traced):
    """Each q8 frame's encode span says which engine quantized it and in
    how many device slabs: on the CPU, the host engine and none."""
    events, _ = traced
    q8 = [a for _, name, _, _, a in events
          if name == "repro.codec.encode" and a["codec"] == "q8"]
    assert {a["op"] for a in q8} >= {"fit_ins", "fit_res", "evaluate_ins"}
    assert all((a.get("q8_engine"), a.get("q8_slabs")) == ("host", 0)
               for a in q8), q8


def test_codec_spans_do_not_nest_on_a_thread(traced):
    events, _ = traced
    lines = {}
    for line, name, start, end, _ in events:
        if name.startswith("repro.codec."):
            lines.setdefault(line, []).append((start, end))
    assert lines
    for spans in lines.values():
        spans.sort()
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end


def test_every_pushed_task_comes_back_from_one_hit_pull(traced):
    events, pushed = traced
    hits = [a for _, name, _, _, a in events
            if name == "repro.relay.request"
            and a["method"] == "pull_task_ins" and a["hit"] == 1]
    # negotiation, then two rounds of a fit and an evaluate per site
    assert len(pushed) == len(SITES) * (1 + 2 * 2)
    assert len(hits) == len(pushed)
    served = [a for _, name, _, _, a in events
              if name == "repro.superlink.serve" and "queued_s" in a]
    assert len(served) == len(pushed)
    assert np.isfinite([a["queued_s"] for a in served]).all()


def test_the_native_path_gets_the_serve_span(tmp_path):
    """``run_native``'s connection calls the SuperLink directly, with no
    relay: its pulls still get ``repro.superlink.serve``, and a returned
    task its ``queued_s``."""
    link = SuperLink()
    conn = NativeConnection(link)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        conn.unary("register", b"n0")
        conn.unary("pull_task_ins", b"n0")             # empty
        link.push_task_ins("n0", b"task")
        conn.unary("pull_task_ins", b"n0")
    prof = ProfileData.from_file(
        glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0])
    served = [e.stats for _, e in program_spans.host_events(prof)
              if e.name == "repro.superlink.serve"]
    assert [a["method"] for a in served] == [
        "register", "pull_task_ins", "pull_task_ins"]
    assert ["queued_s" in a for a in served] == [False, False, True]
    assert served[2]["queued_s"] >= 0


def test_the_sharded_fold_gets_kernel_and_unstage_spans(tmp_path):
    """A fold over two accumulator ranges on the Pallas backend: one
    ``repro.fold.kernel`` for each range of each arrival, one around the
    finalize's copies back, and one ``repro.fold.unstage`` that counts
    its blocks."""
    from repro.fl.agg_kernels import StreamingWeightedSum
    from repro.fl.flat import QuantParams, layout_for, quantize_int8

    n = 5000
    layout = layout_for([("float32", (n,))])
    rng = np.random.default_rng(5)
    base = QuantParams(layout, "q8", *quantize_int8(
        rng.normal(0, 0.5, n).astype(np.float32)))
    s = StreamingWeightedSum(layout, backend="pallas", shards=2,
                             overlap=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for k in range(3):
            q, sc = quantize_int8(rng.normal(0, 1e-3, n).astype(np.float32))
            s.add(QuantParams(layout, "q8", q, sc, is_delta=True,
                              base=base), 1.0 + k)
        s.finalize()
    prof = ProfileData.from_file(
        glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0])
    spans = [e for _, e in program_spans.host_events(prof)
             if e.name.startswith("repro.fold.")]
    kernel = [e.stats for e in spans if e.name == "repro.fold.kernel"]
    assert [a["clients"] for a in kernel] == [1] * 6 + [3]
    assert kernel[-1]["nbytes"] == n * 8
    unstage = [e.stats for e in spans if e.name == "repro.fold.unstage"]
    assert len(unstage) == 1
    assert (unstage[0]["clients"], unstage[0]["nbytes"],
            unstage[0]["blocks"]) == (3, n * 8, 2)
