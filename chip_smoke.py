#!/usr/bin/env python3
"""Chip smoke test: a federated LLM run end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip paths only

One chip: the ``examples/federated_llm.py --scale full`` federation (~126M
fp32 params, 4 sites, FedAvg) through its normal entry points —
``ServerApp`` -> ``run_in_flare`` -> ``LMClient.fit`` (jitted fit steps
on the chip) -> wire codec -> server fold -> new global model.  It runs
2 rounds x 3 local steps with the ``q8`` codec (the dense Pallas fold,
compiled) and 2 rounds with the ``sparse`` codec, and checks that

- every loss is finite and the federated eval loss falls;
- the dense fold ran as a compiled kernel with no numpy fallback;
- the last q8 round's new global model is its on-chip fold, which stays
  within the error bound of ``docs/INVARIANTS.md`` §1 of the numpy fp64
  fold of the same arrivals, with the round's weights and with weights
  that are not powers of two.

Four chips: one fit step on a (data=4, model=1) mesh next to the same step
on one chip (loss, grad norm, and the first Adam moment leaf by leaf),
and one q8 round's arrivals folded with
``make_agg_mesh(4)`` next to the 1-shard fold (bitwise equal), with the
device that holds each shard's accumulator.

Refuses to run without a TPU.  The last line of stdout is one JSON object
``{"ok": true, "device": {...}}``; any failed check exits non-zero first.
"""
import argparse
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ROUNDS, LOCAL_STEPS, SEED = 2, 3, 0


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def _example():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "federated_llm_example", ROOT / "examples" / "federated_llm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def _recording_fedavg():
    """FedAvg that times each round (tasks sent -> new global model) and
    keeps the last round's arrivals for the fold check."""
    from repro.fl import FedAvg

    class RecordingFedAvg(FedAvg):
        def configure_fit(self, rnd, parameters, nodes):
            self.t_sent = time.perf_counter()
            return super().configure_fit(rnd, parameters, nodes)

        def fit_accumulator(self, rnd, current):
            acc = super().fit_accumulator(rnd, current)
            finalize = acc.finalize

            def timed_finalize(failures):
                self.arrivals = [(fp, w) for _, fp, w in sorted(
                    acc.pairs, key=lambda p: p[0])]
                self.fit_losses.setdefault(rnd, []).extend(
                    float(r.metrics["train_loss"]) for r in self._fits)
                self._fits = []
                out = finalize(failures)
                self.round_s[rnd] = time.perf_counter() - self.t_sent
                self.round_out = out[0]
                return out

            add = acc.add

            def add_and_keep_metrics(node, res):
                self._fits.append(res)
                add(node, res)

            acc.finalize = timed_finalize
            acc.add = add_and_keep_metrics
            return acc

    strat = RecordingFedAvg()
    strat.round_s, strat.fit_losses, strat._fits = {}, {}, []
    strat.arrivals, strat.round_out = [], None
    return strat


def _compile_fit_step(ex, scale: str, mesh) -> float:
    """Compile the clients' shared fit step ahead of the run; the
    persistent cache hands it to the first real call."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.train.steps import abstract_train_state, get_train_step

    cfg, tcfg, _, _ = ex.scale_config(scale)
    state = abstract_train_state(build_model(cfg), tcfg)
    tok = jax.ShapeDtypeStruct((tcfg.global_batch, tcfg.seq_len), jnp.int32)
    t0 = time.perf_counter()
    get_train_step(cfg, tcfg, mesh=mesh).lower(
        state, {"tokens": tok, "labels": tok}).compile()
    return time.perf_counter() - t0


def _check_losses(tag: str, history, fit_losses) -> None:
    evals = [loss for _, loss in history.losses()]
    log(f"{tag}: eval loss per round {evals}")
    for rnd in sorted(fit_losses):
        log(f"{tag}: round {rnd} client mean train losses "
            f"{fit_losses[rnd]}")
    every = evals + [x for v in fit_losses.values() for x in v]
    if len(evals) != ROUNDS or not all(math.isfinite(x) for x in every):
        fail(f"{tag}: missing or non-finite losses")
    if not evals[-1] < evals[0]:
        fail(f"{tag}: eval loss did not fall ({evals[0]} -> {evals[-1]})")


def _fold_deviation(tag: str, pairs) -> np.ndarray:
    """On-chip fold of ``pairs`` vs the numpy fp64 fold, against the
    ``docs/INVARIANTS.md`` §1 bound.  Returns the on-chip fp64 vector."""
    from repro.fl import agg_kernels as K
    from repro.kernels import agg_reduce

    layout = pairs[0][0].layout
    # the Pallas backend raises rather than fall back here
    got = K.weighted_mean_f64(pairs, layout, backend="pallas")
    want = K.weighted_mean_f64(pairs, layout, backend="numpy")
    bound = K.fold_error_bound(pairs, layout)
    dev = np.abs(got - want)
    over = dev > bound
    ratio = float(np.max(dev / np.maximum(bound, np.finfo(float).tiny)))
    g32, w32 = got.astype(np.float32), want.astype(np.float32)
    ulps = int(np.max(np.abs(g32.view(np.int32).astype(np.int64)
                             - w32.view(np.int32).astype(np.int64))))
    words = agg_reduce._split_weights(np.array(K._scaled_weights(pairs)))
    log(f"fold check [{tag}]: {len(pairs)} arrivals x {layout.total_size} "
        f"elems; weights {[w for _, w in pairs]}; nonzero fp32 weight "
        f"words {int(np.count_nonzero(words))} of {words.size}; "
        f"max |on-chip - numpy fp64| = {float(dev.max())!r}; "
        f"max deviation / bound = {ratio!r}; "
        f"coordinates over bound = {int(over.sum())}; "
        f"max fp32 ULP distance = {ulps}")
    if over.any():
        fail(f"[{tag}] on-chip fold exceeds the docs/INVARIANTS.md §1 bound")
    return got


def _check_fold(strat) -> None:
    """The last q8 round: its own new global model is the on-chip fold of
    its arrivals, which stays within the bound of the numpy fp64 fold —
    with the round's weights (all sites report the same example count, so
    each is 1/4, a power of two) and again with weights 100..103, whose
    normalized values fill all four fp32 weight words."""
    from repro.fl.flat import FlatParams

    pairs = strat.arrivals
    got = _fold_deviation("round weights", pairs)
    out = FlatParams.from_arrays(strat.round_out, pairs[0][0].layout)
    same = np.array_equal(out.math_view().view(np.uint32),
                          got.astype(np.float32).view(np.uint32))
    log(f"fold check: the round's new global model is bitwise the fp32 "
        f"cast of the on-chip fold: {same}")
    if not same:
        fail("the round's new global model is not the on-chip fold")
    _fold_deviation("weights 100..103",
                    [(fp, float(100 + i)) for i, (fp, _) in enumerate(pairs)])


def one_chip(scale: str = "full") -> None:
    from repro.fl import agg_kernels as K
    from repro.kernels import agg_reduce
    from repro.kernels.platform import interpret_mode
    from repro.launch.mesh import make_local_mesh

    ex = _example()
    mesh = make_local_mesh()
    log(f"fit step compile: {_compile_fit_step(ex, scale, mesh)!r} s "
        f"(scale {scale})")

    # --- q8: the dense Pallas fold -------------------------------------
    K.reset_fallback_counts()
    strat = _recording_fedavg()
    t0 = time.perf_counter()
    hist = ex.run(scale, ROUNDS, LOCAL_STEPS, codec="q8", strategy=strat,
                  mesh=mesh)
    log(f"q8: run wall {time.perf_counter() - t0!r} s; per-round wall "
        f"(tasks sent -> new global model) {strat.round_s}")
    codecs = {r.metrics.get("wire_codec") for r in hist.rounds}
    if codecs != {"q8"}:
        fail(f"q8 run negotiated {codecs}")
    _check_losses("q8", hist, strat.fit_losses)
    _check_fold(strat)
    strat.arrivals = strat.round_out = None
    # read after the fold check, so its folds are counted too
    backend = K.default_backend()
    info = agg_reduce.wsum_fn.cache_info()
    fallbacks = K.fallback_counts()
    log(f"q8 fold: backend={backend} interpret={interpret_mode()} "
        f"kernel_calls={info.hits + info.misses} numpy_fallbacks="
        f"{sum(fallbacks.values())} {fallbacks}")
    if backend != "pallas" or interpret_mode() or info.misses == 0:
        fail("the dense fold did not run as a compiled Pallas kernel")
    if fallbacks:
        fail(f"dense fold fell back to numpy: {fallbacks}")

    # --- sparse: the 0xF5 scatter fold ---------------------------------
    strat = _recording_fedavg()
    t0 = time.perf_counter()
    hist = ex.run(scale, ROUNDS, LOCAL_STEPS, codec="sparse",
                  strategy=strat, mesh=mesh)
    log(f"sparse: run wall {time.perf_counter() - t0!r} s; per-round "
        f"wall {strat.round_s}")
    codecs = {r.metrics.get("wire_codec") for r in hist.rounds}
    if codecs != {"sparse"}:
        fail(f"sparse run negotiated {codecs}")
    _check_losses("sparse", hist, strat.fit_losses)
    log(f"sparse fold: device dequant programs compiled = "
        f"{agg_reduce.dequant_q8._cache_size()}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def _fit_step_on_mesh(ex, scale: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data.loader import FederatedDataLoader
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.steps import TrainState, get_train_step

    cfg, tcfg, _, _ = ex.scale_config(scale)
    model = build_model(cfg)
    params = model.init(jax.random.key(SEED))
    batch = FederatedDataLoader(cfg.vocab_size, tcfg.seq_len, num_sites=1,
                                batch_per_site=tcfg.global_batch,
                                seed=SEED).next_batch(0)
    out = {}
    for name, mesh in (("1 chip", make_local_mesh()),
                       ("data=4", make_local_mesh(data=4, model=1))):
        state = TrainState(params, make_optimizer(tcfg).init(params),
                           jnp.zeros((), jnp.int32))
        step = get_train_step(cfg, tcfg, mesh=mesh)
        t0 = time.perf_counter()
        new, m = step(state, batch)
        jax.block_until_ready(new)
        m = {k: float(v) for k, v in m.items()}
        out[name] = (new, m)
        log(f"fit step [{name}]: first call {time.perf_counter() - t0!r} s "
            f"loss={m['loss']!r} grad_norm={m['grad_norm']!r} param "
            f"devices={len(jax.tree.leaves(new.params)[0].devices())}")
    (a, ma), (b, mb) = out["1 chip"], out["data=4"]
    d_loss = abs(ma["loss"] - mb["loss"]) / abs(ma["loss"])
    d_gn = abs(ma["grad_norm"] - mb["grad_norm"]) / abs(ma["grad_norm"])
    # after the first step Adam's first moment is (1 - b1) * the clipped
    # gradient, coordinate by coordinate: a gradient that is misplaced or
    # permuted across shards moves a leaf by the order of its own norm,
    # while a different reduction order of the bf16 activations' products
    # moves it by about one bf16 rounding (2^-8); allow 4 of them
    lim = 2.0 * float(jnp.finfo(cfg.compute_dtype).eps)
    d_mu = []
    for x, y in zip(jax.tree.leaves(a.opt_state.mu),
                    jax.tree.leaves(b.opt_state.mu)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        d_mu.append(float(np.linalg.norm(x - y))
                    / max(float(np.linalg.norm(x)), np.finfo(float).tiny))
    log(f"fit step 1 chip vs data=4: loss rel diff {d_loss!r}, grad norm "
        f"rel diff {d_gn!r}, first Adam moment: largest per-leaf "
        f"||diff|| / ||1-chip|| {max(d_mu)!r} over {len(d_mu)} leaves "
        f"(limit {lim!r}: 4 roundings of {cfg.compute_dtype})")
    if d_loss > 1e-4 or d_gn > 1e-3 or max(d_mu) > lim:
        fail("the data=4 fit step does not match the one-chip step")


def _q8_arrivals(ex, scale: str, clients: int = 4):
    """One q8 round's arrivals, through the wire codec: int8 deltas of
    each site's result against the round base."""
    import jax

    from repro.fl.flat import FlatParams
    from repro.fl.messages import FitRes, decode_fit_res, encode_fit_res
    from repro.models import build_model

    cfg, _, _, _ = ex.scale_config(scale)
    base = [np.asarray(x) for x in jax.tree.leaves(
        build_model(cfg).init(jax.random.key(SEED)))]
    base_fp = FlatParams.from_arrays(base)
    rng = np.random.default_rng(SEED)
    pairs = []
    for c in range(clients):
        res = [(b + rng.normal(0, 1e-3, b.shape)).astype(b.dtype)
               for b in base]
        q = decode_fit_res(encode_fit_res(FitRes(res, 100 + c, {}),
                                          codec="q8", base=base_fp)).quant
        q.base = base_fp
        pairs.append((q, float(100 + c)))
    return pairs


def _sharded_fold(ex, scale: str) -> None:
    from repro.fl.agg_kernels import StreamingWeightedSum
    from repro.launch.mesh import make_agg_mesh

    pairs = _q8_arrivals(ex, scale)
    layout = pairs[0][0].layout
    folds = {}
    for name, kw in (("1 shard", {"shards": 1}),
                     ("mesh 4", {"mesh": make_agg_mesh(4)})):
        s = StreamingWeightedSum(layout, backend="pallas", overlap=False,
                                 **kw)
        t0 = time.perf_counter()
        for fp, w in pairs:
            s.add(fp, w)
        devs = s.shard_devices()
        folds[name] = s.finalize().math_view()
        log(f"fold [{name}]: {time.perf_counter() - t0!r} s; shard "
            f"accumulators on {[str(d) for d in devs]}")
        if name == "mesh 4" and len({str(d) for d in devs}) != 4:
            fail(f"shard accumulators not on 4 distinct devices: {devs}")
    same = np.array_equal(folds["1 shard"].view(np.uint32),
                          folds["mesh 4"].view(np.uint32))
    log(f"sharded fold (mesh 4) bitwise equal to 1-shard fold: {same}")
    if not same:
        fail("the 4-shard fold differs from the 1-shard fold")


def four_chips(scale: str = "full") -> None:
    import jax

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    ex = _example()
    _fit_step_on_mesh(ex, scale)
    _sharded_fold(ex, scale)


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU found: JAX's devices are {devices}; this smoke "
              f"test runs on a TPU only", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.platform import enable_compile_cache

    log(f"jax {jax.__version__}; device_kind {devices[0].device_kind}; "
        f"device count {len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
