"""The federation under test, driven through the program's normal path.

``ServerApp`` -> ``run_in_flare`` (a FLARE job with one client job per
site) -> the example's ``LMClient.fit`` / ``LMClient.evaluate`` (the
shared jitted train step) -> the negotiated wire codec -> FedAvg's fold
-> the new global model.  Nothing of the program is changed.  The
benchmark's own subclasses only add spans and records around the calls
into each layer:

- ``bench.fit`` / ``bench.eval`` around each client call (per site);
- ``bench.fold`` around each accumulator ``add`` and its ``finalize``;
- ``bench.round`` from ``configure_fit`` to the end of that round's
  evaluate phase (the server's thread).

Spans are ``jax.profiler.TraceAnnotation``\\ s, so they cost nothing unless
a trace is being taken, and they share the device trace's clock.

The window.  ``ServerApp.run`` has a fixed ``num_rounds``; the strategy
ends the window itself.  Round 1 is set-up: every program runs once and
the clients build their optimizer state.  When its evaluate phase has
ended (no site is stepping), ``end_of_setup`` copies site 1's carried
optimizer state to the host for the reference and runs once each program
that only a later round calls: the train step on a round's fresh weights
beside a carried optimizer state (a call signature of its own) and the
record's norm functions.  The window opens at round 2's
``configure_fit``.  A further round starts only while it is expected to
end inside ``seconds`` (by the last round's length); after that every
``configure_fit`` and ``configure_evaluate`` returns no tasks and the
fold hands back the current model, so the remaining rounds are empty.

The device layout follows the cell's chips (``layout``): on one chip,
the program's defaults; on several, each site's fit is FSDP-sharded over
a (data=chips) mesh and the fold's accumulator is split into one range
per chip.

What the check reads (``Record``) comes from the window's first round:
site 1's first steps through the window's own branch of ``fit`` (the
round's weights swapped into the carried state), its uplink, its
evaluate, and the round's fold.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import run_in_flare
from repro.fl import FedAvg, ServerApp, ServerConfig
from repro.fl.client import ClientApp
from repro.fl.messages import arrays_to_params
from repro.fl.strategy import FitAccumulator
from repro.runtime import FlareRuntime

from weights import leaf_path

ROOT = pathlib.Path(__file__).resolve().parents[2]
WARMUP_ROUNDS = 1
CHECKED_ROUND = WARMUP_ROUNDS + 1
RECORDED_STEPS = 3


def layout(chips: int):
    """``(mesh, shard_mesh)`` of a cell on ``chips`` chips.  One chip:
    neither, so each client takes the program's default (1,1) mesh and
    the fold its unsharded accumulator.  More: every site's train step
    on a (data=chips) mesh (the program's FSDP path: params and Adam
    moments sharded over the chips) and the fold's fp64 accumulator in
    one range per chip."""
    if chips == 1:
        return None, None
    from repro.launch.mesh import make_agg_mesh, make_local_mesh

    return make_local_mesh(data=chips, model=1), make_agg_mesh(chips)


def _example():
    spec = importlib.util.spec_from_file_location(
        "federated_llm_example", ROOT / "examples" / "federated_llm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name: str, **kw):
    return jax.profiler.TraceAnnotation(name, **kw)


@jax.jit
def _grad_norms(mu1, mu0, beta1):
    """Per-leaf norms of the gradient Adam's first moment took in:
    ``(mu1 - beta1 mu0) / (1 - beta1)``."""
    return jnp.stack([jnp.linalg.norm(((m1 - beta1 * m0) / (1 - beta1))
                                      .reshape(-1))
                      for m1, m0 in zip(jax.tree.leaves(mu1),
                                        jax.tree.leaves(mu0))])


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.linalg.norm((x - y).reshape(-1))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@dataclass
class Record:
    """What the check compares, from the window's first round.

    Site 1: the optimizer state it carried out of set-up (host copies),
    its first ``RECORDED_STEPS`` steps (the batches fed, each loss, the
    per-leaf norms of the first gradient as the optimizer received it and
    of the weights' change after the last step), its fit's output
    weights, and its evaluate's batch and loss.  The server: the global
    model the round started from, and the round's fold (its new model and
    the wire frames it folded)."""
    beta1: float
    carried: Optional[Dict[str, Any]] = None
    batches: List[Dict[str, np.ndarray]] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)
    grad_norms: Optional[Dict[str, float]] = None
    change_norms: Optional[Dict[str, float]] = None
    fit_out: Optional[List[np.ndarray]] = None
    eval_batch: Optional[Dict[str, np.ndarray]] = None
    eval_loss: Optional[float] = None
    start_model: Optional[List[np.ndarray]] = None
    fold: Optional[Dict[str, Any]] = None


def _paths(tree) -> List[str]:
    return [leaf_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        tree)[0]]


def _recording_step(client, rec: Record):
    orig = client._step_fn
    box = {}

    def step(state, batch):
        first = not rec.batches
        if first:
            box["p0"], box["mu0"] = state.params, state.opt_state.mu
        new, m = orig(state, batch)
        rec.batches.append(batch)
        rec.losses.append(float(m["loss"]))
        paths = _paths(new.params)
        if first:
            rec.grad_norms = dict(zip(paths, np.asarray(_grad_norms(
                new.opt_state.mu, box.pop("mu0"), rec.beta1)).tolist()))
        if len(rec.batches) == RECORDED_STEPS:
            rec.change_norms = dict(zip(paths, np.asarray(_diff_norms(
                new.params, box.pop("p0"))).tolist()))
            client._step_fn = orig
        return new, m

    return step


def end_of_setup(client, model) -> None:
    """Site 1 after set-up: copy its carried optimizer state to the host,
    and run once what only the window calls, on ``model`` (host arrays
    of the global model) as a round's fresh weights: the train step on
    them beside the carried state, and the record's norm functions.
    Their results are dropped; the state is not touched."""
    st, rec = client._state, client.record
    if rec is not None:
        paths = _paths(st.params)
        rec.carried = {
            "mu": dict(zip(paths, jax.device_get(jax.tree.leaves(
                st.opt_state.mu)))),
            "nu": dict(zip(paths, jax.device_get(jax.tree.leaves(
                st.opt_state.nu)))),
            "step": int(st.step)}
    fresh = arrays_to_params(model, client._like)
    shape = (client.tcfg.global_batch, client.tcfg.seq_len)
    batch = {"tokens": np.zeros(shape, np.int32),
             "labels": np.zeros(shape, np.int32)}
    _, m = client._step_fn(st._replace(params=fresh), batch)
    float(m["loss"])
    np.asarray(_grad_norms(st.opt_state.mu, st.opt_state.mu,
                           client.tcfg.beta1))
    np.asarray(_diff_norms(st.params, fresh))


def client_class(LMClient):
    class BenchClient(LMClient):
        """The example's client with spans around fit and evaluate; site
        1's also records what the check compares."""
        record: Optional[Record] = None

        def fit(self, parameters, config):
            checked = (self.record is not None
                       and config.get("round") == CHECKED_ROUND)
            if checked:
                self._step_fn = _recording_step(self, self.record)
            with span("bench.fit", site=self.site):
                out = super().fit(parameters, config)
            if checked:
                self.record.fit_out = out[0]
            return out

        def evaluate(self, parameters, config):
            rec = self.record
            if rec is None or config.get("round") != CHECKED_ROUND:
                with span("bench.eval", site=self.site):
                    return super().evaluate(parameters, config)
            loader = self.loader

            class Seen:
                def next_batch(self, site):
                    rec.eval_batch = loader.next_batch(site)
                    return rec.eval_batch

            self.loader = Seen()
            try:
                with span("bench.eval", site=self.site):
                    out = super().evaluate(parameters, config)
            finally:
                self.loader = loader
            rec.eval_loss = out[0]
            return out

    return BenchClient


class _KeepCurrent(FitAccumulator):
    """After the window: no results, the model stays as it is."""

    def finalize(self, failures):
        return self.current, {}


class WindowedFedAvg(FedAvg):
    """FedAvg whose rounds after set-up form the measured window."""

    def __init__(self, initial_parameters, seconds: float,
                 on_window_open: Optional[Callable[[], None]] = None,
                 on_window_close: Optional[Callable[[], None]] = None,
                 on_setup_done: Optional[Callable[[Any], None]] = None,
                 record: Optional[Record] = None):
        super().__init__(initial_parameters=initial_parameters)
        self.seconds = float(seconds)
        self.on_window_open = on_window_open
        self.on_window_close = on_window_close
        self.on_setup_done = on_setup_done
        self.record = record
        self.window_open_t: Optional[float] = None
        self.window_close_t: Optional[float] = None
        self.closed = False
        self.round_t: Dict[int, float] = {}         # round -> seconds
        self.window_rounds: List[int] = []
        self.attempted = 0
        self.fold_shards: Optional[int] = None    # of the last fold
        self._round_start: Dict[int, float] = {}
        self._round_span: Dict[int, Any] = {}
        self._model: Any = None

    def _in_window(self, rnd: int) -> bool:
        return rnd > WARMUP_ROUNDS and not self.closed

    def configure_fit(self, rnd, parameters, nodes):
        if rnd == WARMUP_ROUNDS + 1:
            if self.record is not None:
                self.record.start_model = [np.array(a) for a in parameters]
            if self.on_window_open is not None:
                self.on_window_open()
            self.window_open_t = time.perf_counter()
        elif rnd > WARMUP_ROUNDS + 1 and not self.closed:
            elapsed = time.perf_counter() - self.window_open_t
            if elapsed + self.round_t[rnd - 1] > self.seconds:
                self.closed = True
                if self.on_window_close is not None:
                    self.on_window_close()
        if self.closed:
            return {}
        self._round_start[rnd] = time.perf_counter()
        ann = span("bench.round", round=rnd)
        ann.__enter__()
        self._round_span[rnd] = ann
        tasks = super().configure_fit(rnd, parameters, nodes)
        if self._in_window(rnd):
            self.window_rounds.append(rnd)
            self.attempted += len(tasks)
        return tasks

    def fit_accumulator(self, rnd, current):
        if self.closed:
            return _KeepCurrent(self, rnd, current)
        acc = super().fit_accumulator(rnd, current)
        add, finalize = acc.add, acc.finalize
        arrivals = []

        def timed_add(node, res):
            with span("bench.fold", op="add"):
                add(node, res)
            arrivals.append((node, res))

        def timed_finalize(failures):
            with span("bench.fold", op="finalize"):
                out = finalize(failures)
            # the accumulator a sharded fold streams into; an unsharded
            # fold has none (0 ranges)
            streaming = getattr(acc, "_streaming", None)
            self.fold_shards = streaming.shards if streaming else 0
            self._model = out[0]
            if rnd == CHECKED_ROUND and self.record is not None:
                self.record.fold = {"out": out[0], "arrivals": sorted(
                    arrivals, key=lambda a: a[0])}
            return out

        acc.add, acc.finalize = timed_add, timed_finalize
        return acc

    def configure_evaluate(self, rnd, parameters, nodes):
        if self.closed:
            return {}
        tasks = super().configure_evaluate(rnd, parameters, nodes)
        if self._in_window(rnd):
            self.attempted += len(tasks)
        return tasks

    def aggregate_evaluate(self, rnd, results, failures):
        out = super().aggregate_evaluate(rnd, results, failures)
        if rnd == WARMUP_ROUNDS and self.on_setup_done is not None:
            self.on_setup_done(self._model)
        if rnd in self._round_span:
            self._round_span.pop(rnd).__exit__(None, None, None)
            now = time.perf_counter()
            self.round_t[rnd] = now - self._round_start[rnd]
            if self._in_window(rnd):
                self.window_close_t = now
        return out


def run(cfg, tcfg, mix: Dict, loader, initial_parameters, seconds: float,
        chips: int, on_window_open=None, on_window_close=None,
        record: Optional[Record] = None):
    """One federation on ``chips`` chips (``layout``): ``WARMUP_ROUNDS``
    of set-up, then the window.  Returns ``(history, strategy,
    clients)``."""
    ex = _example()
    mesh, shard_mesh = layout(chips)
    Client = client_class(ex.LMClient)
    sites = [f"site-{i + 1}" for i in range(mix["sites"])]
    clients: List[Any] = []
    first: List[Any] = []

    def client_app_fn(site):
        def make(cid):
            c = Client(site, cfg, tcfg, loader, mix["local_steps"],
                       mesh=mesh)
            if site == sites[0]:
                c.record = record
                first.append(c)
            clients.append(c)
            return c.to_client()
        return ClientApp(client_fn=make)

    def setup_done(model):
        end_of_setup(first[0], model)

    strategy = WindowedFedAvg(initial_parameters, seconds,
                              on_window_open=on_window_open,
                              on_window_close=on_window_close,
                              on_setup_done=setup_done, record=record)
    # an empty round costs microseconds: room for rounds of 10 ms
    rounds = WARMUP_ROUNDS + 1 + math.ceil(seconds / 0.01)
    rt = FlareRuntime(request_timeout=600.0)
    try:
        for s in sites:
            rt.provision_site(s)
        server = ServerApp(
            config=ServerConfig(num_rounds=rounds, round_timeout=3600,
                                codec=mix["codec"], shard_mesh=shard_mesh),
            strategy=strategy)
        history = run_in_flare(rt, server, client_app_fn, sites,
                               timeout=7200)
    finally:
        rt.shutdown()
    return history, strategy, clients
