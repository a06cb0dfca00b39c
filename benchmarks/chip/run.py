#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 benchmarks/chip/run.py --workload h2o-danube-1.8b.q8-local10 \\
        --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<name>.json``) and
has its limits in ``limits/<workload>.json``; each per-layer metric is
``metrics/<name>.py``.  The run makes the weights and every batch from
``--seed``, runs one federated round as set-up, then measures whole
rounds for ``--seconds`` (see ``federation.py``), and checks the first
window round's outputs against the plain reference (``compare``,
``check.py``).

``--trace 0`` reports the end-to-end metrics: ``round_s``, the window's
wall time over its rounds, and ``setup_s``, process start to the window.
``--trace 1`` traces the window instead and reports the per-layer metrics
and a breakdown of device time and idle gaps.  The cell's ``chips`` set
the federation's device layout (``federation.layout``); the device record
gives the peak memory of the fullest of them.

Stdout's last line is the result object; stderr's last lines are each
compared number beside its limit.  Without a TPU, or with fewer chips than
the cell asks for, the run exits 3 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = HERE / ".out" / "trace"


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    return {
        "workload": w,
        "config": json.loads((root / cfg["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (HERE / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def _import(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCount:
    """Counts traces and XLA compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax

        self.events = {"/jax/core/compile/jaxpr_trace_duration": 0,
                       "/jax/core/compile/backend_compile_duration": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.events:
            self.events[event] += 1

    def snapshot(self):
        return tuple(self.events.values())


def program_configs(cell):
    """The program's configs: its registry entry cut to the file's layers
    and vocabulary (and any further ``program.replace``), and the
    traffic's optimizer."""
    from repro.config import TrainConfig, get_model_config

    c, mix = cell["config"], cell["traffic"]
    cfg = get_model_config(c["program"]["arch"]).replace(
        num_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        **c["program"].get("replace", {}))
    o = mix["optimizer"]
    tcfg = TrainConfig(global_batch=mix["batch"], seq_len=mix["seq_len"],
                       learning_rate=o["learning_rate"],
                       warmup_steps=o["warmup_steps"],
                       total_steps=o["total_steps"], optimizer=o["name"],
                       weight_decay=o["weight_decay"], beta1=o["beta1"],
                       beta2=o["beta2"], eps=o["eps"],
                       grad_clip=o["grad_clip"])
    return cfg, tcfg


def _paths() -> None:
    for p in (ROOT / "src", HERE):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def prepare(cell: dict) -> dict:
    """The program's configs and model for the cell, its checkpoint
    leaves (checked against the configuration file's shapes) and the
    configuration's reference module."""
    _paths()
    import jax

    import weights
    from repro.models import build_model

    c = cell["config"]
    ref_mod = _import(HERE / "reference" / f"{c['reference']}.py",
                      f"bench_ref_{c['reference']}")
    cfg, tcfg = program_configs(cell)
    model = build_model(cfg)
    flat_leaves = jax.tree_util.tree_flatten_with_path(model.abstract())[0]
    leaves = [(weights.leaf_path(p), tuple(s.shape), str(s.dtype))
              for p, s in flat_leaves]
    want = ref_mod.leaf_shapes(c)
    have = {p: s for p, s, _ in leaves}
    if have != want:
        raise SystemExit(f"the program's {c['program']['arch']} does not "
                         f"have the file's shapes: {have} != {want}")
    return {"cfg": cfg, "tcfg": tcfg, "model": model, "leaves": leaves,
            "ref": ref_mod}


def compare(p: dict, cell: dict, rec) -> tuple:
    """The record of the window's first round against the reference.
    Returns ``(numbers, answers)``: every number ``check`` defines, and
    the reference's answers they were read against (for the controls)."""
    import numpy as np

    import check
    import weights

    c, mix, ref_mod, leaves = (cell["config"], cell["traffic"], p["ref"],
                               p["leaves"])
    start = [np.asarray(a) for a in rec.start_model]
    w_start = weights.by_path(leaves, ref_mod.q8_roundtrip(start))
    ans = {"w_start": w_start, "fit": ref_mod.fit_steps(
        w_start, rec.batches, c, mix["optimizer"], state=rec.carried)}
    got = check.fit_readings({"losses": rec.losses,
                              "grad_norms": rec.grad_norms,
                              "change_norms": rec.change_norms}, ans["fit"])
    ans["w_eval"] = weights.by_path(leaves, ref_mod.q8_roundtrip(
        rec.fold["out"]))
    ans["eval"] = ref_mod.eval_loss(ans["w_eval"], rec.eval_batch, c)
    got["eval_loss_gap"] = check.loss_gap([rec.eval_loss], [ans["eval"]])
    # site 1's uplink: its fit's weights less the round's downlink, as
    # the reference decodes it, against the frame the fold received
    ans["delta"] = (ref_mod.flat(rec.fit_out)
                    - ref_mod.q8_decode_flat(ref_mod.flat(start)))
    by_node = dict(rec.fold["arrivals"])
    up = by_node["site-1"].quant
    got["uplink_q8_err"] = check.uplink_reading(ans["delta"], up.data,
                                                up.scales)
    arrivals = [(float(res.num_examples), res.quant.data, res.quant.scales)
                for _, res in rec.fold["arrivals"]]
    base = up.base
    got["fold_err_over_bound"] = check.fold_reading(
        arrivals, (base.data, base.scales), ref_mod.flat(rec.fold["out"]))
    got["_eval"] = (rec.eval_loss, ans["eval"])
    return got, ans


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t0: float = T0) -> dict:
    """Set up, measure and check one cell; returns the result object,
    with the compared numbers under ``checks``."""
    _paths()
    import numpy as np

    import jax

    import check
    import federation
    import flops
    import readings
    import trace_reduce
    import traffic
    import weights
    from repro.fl import agg_kernels
    from repro.kernels import agg_reduce
    from repro.kernels.platform import enable_compile_cache

    log(f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counts = CompileCount()
    c, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    p = prepare(cell)
    cfg, tcfg, leaves = p["cfg"], p["tcfg"], p["leaves"]
    log(f"{c['name']}: {p['model'].param_count()} params in {len(leaves)} "
        f"leaves; {mix['sites']} sites x {mix['local_steps']} local steps "
        f"of {mix['batch']} x {mix['seq_len']} tokens, codec "
        f"{mix['codec']}")

    init = [np.asarray(a) for a in weights.make(leaves, c, seed)]
    loader = traffic.SiteTokens(mix, c["vocab_size"], seed)
    record = federation.Record(beta1=tcfg.beta1)
    marks = {}

    def window_open():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        marks["open"] = counts.snapshot()

    def window_close():
        marks["close"] = counts.snapshot()
        if trace:
            jax.profiler.stop_trace()

    chips = cell["workload"]["chips"]
    agg_kernels.reset_fallback_counts()
    history, strat, clients = federation.run(
        cfg, tcfg, mix, loader, init, seconds, chips,
        on_window_open=window_open, on_window_close=window_close,
        record=record)
    n_rounds = len(strat.window_rounds)
    round_s = (strat.window_close_t - strat.window_open_t) / n_rounds
    setup_s = strat.window_open_t - t0
    d_trace, d_compile = (b - a for a, b in zip(marks["open"],
                                                 marks["close"]))
    failed = sum(len(r.failures) for r in history.rounds
                 if r.round in strat.window_rounds)
    rounds = sorted(strat.round_t)
    log(f"window: {n_rounds} rounds {strat.window_rounds}, round seconds "
        f"{[strat.round_t[r] for r in strat.window_rounds]}; set-up rounds "
        f"{[strat.round_t[r] for r in rounds[:federation.WARMUP_ROUNDS]]}")
    log(f"inside the window: {d_compile} compiles (cache loads included), "
        f"{d_trace} traces")
    info = agg_reduce.wsum_fn.cache_info()
    log(f"fold: backend {agg_kernels.default_backend()}, accumulator "
        f"ranges {strat.fold_shards}, kernel programs {info.currsize}, "
        f"numpy fallbacks {agg_kernels.fallback_counts()}")
    log(f"sites' train states: {state_layout(clients)}")
    evals = [(r.round, r.loss) for r in history.rounds
             if r.loss is not None]
    log(f"federated eval loss by round: {evals}")

    device = device_record(jax.devices(), chips)

    t_done = time.perf_counter()
    # free the program's device state before the reference runs
    for cl in clients:
        cl.__dict__.clear()
    attempted = strat.attempted
    del clients, strat, history
    gc.collect()

    got, _ = compare(p, cell, record)
    log(f"fit steps from step {record.carried['step']}: program losses "
        f"{record.losses}; worst leaves: gradient {got['_grad_leaf']}, "
        f"change {got['_change_leaf']}; left out {got['_excluded']}; "
        f"median-leaf gaps: gradient {got['fit_grad_median_gap']!r}, "
        f"change {got['fit_change_median_gap']!r}; "
        f"eval loss (program, reference) {got['_eval']}")
    got = {k: v for k, v in got.items() if not k.startswith("_")}
    correct, table = check.verdict(got, limits)
    t_checked = time.perf_counter()

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        e2e = {"round_s": round_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    else:
        log(f"traced run: round {round_s!r} s, set-up {setup_s!r} s")
        tr = trace_reduce.load(str(next(TRACE_DIR.glob(
            "plugins/profile/*/*.xplane.pb"))))
        pk = flops.peaks(device["kind"])
        step = flops.train_step_flops(c, mix["batch"], mix["seq_len"])
        fwd = flops.forward_flops(c, mix["batch"], mix["seq_len"])
        ctx = readings.Context(
            trace=tr, cfg=c, mix=mix, peaks=pk,
            fit_flops_per_round=step * mix["sites"] * mix["local_steps"],
            eval_flops_per_round=fwd * mix["sites"], chips=chips)
        metrics = {}
        for m in cell["per_layer"]:
            v = readings.read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        lo, hi = tr.window
        busy = trace_reduce.busy(tr, lo, hi)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = breakdown(tr, lo, hi)
    log(f"after the window: checks {t_checked - t_done!r} s, trace "
        f"reduction {time.perf_counter() - t_checked!r} s")
    result["device"] = device
    result["checks"] = table
    return result


def device_record(devices, chips: int) -> dict:
    """The result's ``device``: as JAX reports it, with the peak memory
    of the fullest of the cell's ``chips`` devices and each one's."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:chips]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks, key=lambda b: b or 0),
            "memory_peak_bytes_by_device": peaks}


def state_layout(clients) -> dict:
    """Per site: how many devices the least spread leaf of its train
    state lives on, and the share of the state's bytes on its fullest
    device."""
    import jax

    out = {}
    for cl in clients:
        leaves = jax.tree.leaves(cl._state)
        on = {}
        for leaf in leaves:
            for s in leaf.addressable_shards:
                on[s.device] = on.get(s.device, 0) + s.data.nbytes
        total = sum(leaf.nbytes for leaf in leaves)
        out[cl.site] = {
            "devices": min(len(leaf.sharding.device_set) for leaf in leaves),
            "fullest_share": max(on.values()) / total}
    return dict(sorted(out.items()))


def breakdown(tr, lo, hi) -> dict:
    import program_spans
    import trace_reduce

    by_op = {}
    for dev, evs in tr.ops.items():
        for e in trace_reduce.in_window(evs, lo, hi):
            key = (f"{trace_reduce.program_of(tr, dev, e)}/"
                   f"{trace_reduce.op_name(e)}")
            by_op[key] = by_op.get(key, 0) + (e.end - e.start)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    for dev in tr.ops:
        for g in trace_reduce.gaps(tr, dev, lo, hi)[:10]:
            gaps.append([program_spans.label(
                tr, g, ("bench.fit", "bench.eval", "bench.fold")),
                (g[1] - g[0]) * 1e-9])
    gaps.sort(key=lambda x: -x[1])
    return {"device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": gaps[:10]}


def emit(result: dict) -> None:
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"this cell runs on {chips} TPU chip(s); JAX has {devices}",
              file=sys.stderr, flush=True)
        sys.exit(3)
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
