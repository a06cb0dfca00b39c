#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and
the faults', on the chip, at the cell's own sizes.  Not part of a
benchmark run.

    python3 benchmarks/chip/calibrate.py --workload W --seeds 12 \\
        --control-seeds 3 --first-seed 1000

For every seed, a fresh set of the benchmark's clients, on the cell's
device layout (``federation.layout``), runs the cell's first two rounds
without the relay: every site fits from the q8 downlink of the seeded
weights, FedAvg folds their q8 uplinks, ``end_of_setup``
runs as in a benchmark run, and the checked round follows: every site
fits from the downlink of the new model through the window's branch of
``fit`` (the carried optimizer state), FedAvg folds, and site 1
evaluates the folded model.  Site 1 records what a run records, and
``run.compare`` reads it against the float32 reference: the lower
readings.  On the first ``--control-seeds`` seeds also, each in the
program's place:

- the control: the reference computed in float8 (matmul operands e4m3,
  their cotangents e5m2: the precision below the configuration's
  bfloat16, in both passes), for the fit steps and the evaluate; for the
  uplink, the delta rounded to bfloat16 before the q8 encode; for the
  fold, the same fold with fp32 weights and accumulator;
- the fault "half of the batch left out, the mean over the rest": the
  reference on the first half of each batch's rows;
- the fault "the carried optimizer state dropped": the reference's steps
  from fresh moments and step 0.

The fault "a step that returns its state unchanged" reads 1 on both
change gaps by definition (no change against the reference's) and needs
no run.  One JSON line per seed, then a summary line: the largest
reading of the program per number (the lower) and the smallest of the
control and of each fault (the candidates for the upper).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import run as harness  # noqa: E402

FIT = ("fit_loss_gap", "fit_grad_gap", "fit_grad_median_gap",
       "fit_change_gap", "fit_change_median_gap")
NUMBERS = (*FIT, "eval_loss_gap", "uplink_q8_err", "fold_err_over_bound")


def _round(clients, model, rnd: int, shard_mesh, record=None):
    """One federated round without the relay: every client fits from the
    q8 downlink of ``model``, FedAvg folds the q8 uplinks (on
    ``shard_mesh``, as ``federation.run``'s server does).  Returns the new
    model; ``record.fold`` gets the fold as a run records it."""
    from repro.fl import FedAvg
    from repro.fl.messages import (FitIns, FitRes, decode_fit_ins,
                                   decode_fit_res, encode_fit_ins,
                                   encode_fit_res, peek_params)

    down = encode_fit_ins(FitIns(model, {"round": rnd, "codec": "q8"}),
                          codec="q8")
    base = peek_params(down)
    acc = FedAvg(shard_mesh=shard_mesh).fit_accumulator(rnd, model)
    arrivals = []
    for cl in clients:
        ins = decode_fit_ins(down)
        arrays, n, _ = cl.fit(ins.parameters, ins.config)
        res = decode_fit_res(encode_fit_res(FitRes(arrays, n, {}),
                                            codec="q8", base=ins.flat))
        res.quant.base = base
        acc.add(cl.site, res)
        arrivals.append((cl.site, res))
    new, _ = acc.finalize([])
    if record is not None:
        record.fold = {"out": new, "arrivals": arrivals}
    return new


def one_seed(cell, prep, seed: int, control: bool) -> dict:
    import numpy as np

    import check
    import federation
    import traffic
    import weights
    from repro.fl.messages import (EvaluateIns, decode_evaluate_ins,
                                   encode_evaluate_ins)

    c, mix = cell["config"], cell["traffic"]
    leaves, ref_mod = prep["leaves"], prep["ref"]
    init = [np.asarray(a) for a in weights.make(leaves, c, seed)]
    loader = traffic.SiteTokens(mix, c["vocab_size"], seed)
    Client = federation.client_class(federation._example().LMClient)
    rec = federation.Record(beta1=prep["tcfg"].beta1)
    mesh, shard_mesh = federation.layout(cell["workload"]["chips"])
    clients = [Client(f"site-{s + 1}", prep["cfg"], prep["tcfg"], loader,
                      mix["local_steps"], mesh=mesh)
               for s in range(mix["sites"])]
    clients[0].record = rec
    model = _round(clients, init, 1, shard_mesh)
    federation.end_of_setup(clients[0], model)
    rec.start_model = model
    new = _round(clients, model, federation.CHECKED_ROUND, shard_mesh, rec)
    ev = decode_evaluate_ins(encode_evaluate_ins(
        EvaluateIns(new, {"round": federation.CHECKED_ROUND}), codec="q8"))
    clients[0].evaluate(ev.parameters, ev.config)
    for cl in clients:
        cl.__dict__.clear()
    del clients
    gc.collect()

    got, ans = harness.compare(prep, cell, rec)
    out = {"seed": seed, **{k: got[k] for k in NUMBERS},
           "worst_leaves": [got["_grad_leaf"], got["_change_leaf"]],
           "excluded": got["_excluded"], "eval": got["_eval"]}
    if not control:
        return out
    opt, w0 = mix["optimizer"], ans["w_start"]
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
            for b in rec.batches]
    for tag, batches, prec, start in (
            ("control", rec.batches, "float8", rec.carried),
            ("half_batch", half, "float32", rec.carried),
            ("state_dropped", rec.batches, "float32", None)):
        bad = ref_mod.fit_steps(w0, batches, c, opt, precision=prec,
                                state=start)
        got = check.fit_readings(bad, ans["fit"])
        out.update({f"{tag}.{k}": got[k] for k in FIT})
    for tag, batch, prec in (
            ("control", rec.eval_batch, "float8"),
            ("half_batch", {k: v[: v.shape[0] // 2]
                            for k, v in rec.eval_batch.items()}, "float32")):
        out[f"{tag}.eval_loss_gap"] = check.loss_gap(
            [ref_mod.eval_loss(ans["w_eval"], batch, c, prec)],
            [ans["eval"]])
    import ml_dtypes

    coarse = ans["delta"].astype(ml_dtypes.bfloat16).astype(np.float32)
    q, scales = ref_mod.q8_encode_flat(coarse)
    out["control.uplink_q8_err"] = check.uplink_reading(ans["delta"], q,
                                                        scales)
    base = dict(rec.fold["arrivals"])["site-1"].quant.base
    arrivals = [(float(res.num_examples), res.quant.data, res.quant.scales)
                for _, res in rec.fold["arrivals"]]
    out["control.fold_err_over_bound"] = check.fold_reading(
        arrivals, (base.data, base.scales), ref_mod.flat(rec.fold["out"]),
        precision="float32")
    out["state_unchanged.fit_change_gap"] = 1.0
    out["state_unchanged.fit_change_median_gap"] = 1.0
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(harness.ROOT / ".jax_cache"))
    cell = harness.load_cell(args.workload)

    import jax

    chips = cell["workload"]["chips"]
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
        print(f"calibration runs on {chips} TPU chip(s); JAX has "
              f"{jax.devices()}", file=sys.stderr)
        sys.exit(3)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    prep = harness.prepare(cell)
    rows = []
    for i in range(args.seeds):
        t = time.perf_counter()
        row = one_seed(cell, prep, args.first_seed + 7919 * i,
                       i < args.control_seeds)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in NUMBERS:
        summary[f"lower.{k}"] = max(r[k] for r in rows)
    for k in sorted({k for r in rows for k in r if "." in k}):
        summary[f"upper.{k}"] = min(r[k] for r in rows if k in r)
    summary["seconds"] = time.perf_counter() - T0
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
