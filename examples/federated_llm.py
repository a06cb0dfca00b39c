"""End-to-end driver (deliverable b): federated training of a transformer
LM across sites through the FLARE runtime.

Each site holds a non-IID synthetic corpus (its own Markov chain); clients
run real jitted, mesh-sharded train steps on the registry transformer
(fsdp "data"/"model" axes via ``launch.mesh.make_local_mesh`` — a (1,1)
mesh on a laptop, the same code path as a production mesh); the server
aggregates with FedAvg through the six-hop bridged path, and fit results
ship as structured-sparse 0xF5 TopK deltas by default (<<1% of the
full-weight wire bytes at --scale full).  At --scale full the model is
~126M params and runs a few hundred local steps total; the default is
laptop-sized so the example finishes in ~a minute on 1 CPU.

    PYTHONPATH=src python examples/federated_llm.py            # small
    PYTHONPATH=src python examples/federated_llm.py --scale full
    PYTHONPATH=src python examples/federated_llm.py --codec q8  # int8 wire

Two properties this file is careful about (pinned by
tests/test_federated_llm.py):

- the local optimizer state PERSISTS across rounds: ``fit`` replaces only
  the params in the running ``TrainState``, so Adam moments and the LR
  schedule's step counter stay continuous (re-initializing the moments
  every round while the step counter advanced silently destroyed the
  schedule/moment pairing);
- the compiled step is SHARED: every client with the same
  ``(cfg, tcfg, mesh)`` gets one jitted step from
  ``train.steps.get_train_step`` instead of tracing per client.
"""
import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TrainConfig, get_model_config
from repro.core import run_in_flare
from repro.data.loader import FederatedDataLoader
from repro.fl import FedAvg, ServerApp, ServerConfig
from repro.fl.client import ClientApp, NumPyClient
from repro.fl.messages import arrays_to_params, params_to_arrays
from repro.kernels.platform import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.runtime import FlareRuntime
from repro.train.steps import TrainState, get_train_step, make_eval_step

SITES = ["site-1", "site-2", "site-3", "site-4"]


class LMClient(NumPyClient):
    """A real JAX training client: local steps on the site's own corpus."""

    def __init__(self, site: str, cfg, tcfg, loader, local_steps: int,
                 mesh=None):
        self.site = site
        self.site_idx = int(site.rsplit("-", 1)[-1]) - 1
        self.model = build_model(cfg)
        self.tcfg = tcfg
        self.loader = loader
        self.local_steps = local_steps
        self.mesh = mesh if mesh is not None else make_local_mesh()
        # one compiled mesh-sharded step per (cfg, tcfg, mesh) in the
        # whole process — sites share it
        self._step_fn = get_train_step(cfg, tcfg, mesh=self.mesh)
        self._like = self.model.init(jax.random.key(0))
        from repro.optim import make_optimizer

        self._opt = make_optimizer(tcfg)
        # persistent local TrainState: moments + step survive across
        # rounds; fit() only swaps in the aggregated params
        self._state = None

    def get_parameters(self, config):
        return params_to_arrays(self._like)

    def fit(self, parameters, config):
        params = arrays_to_params(parameters, self._like)
        if self._state is None:
            self._state = TrainState(params, self._opt.init(params),
                                     jnp.zeros((), jnp.int32))
        else:
            self._state = self._state._replace(params=params)
        losses = []
        for _ in range(self.local_steps):
            batch = self.loader.next_batch(self.site_idx)
            self._state, m = self._step_fn(self._state, batch)
            losses.append(float(m["loss"]))
        n = self.local_steps * self.tcfg.global_batch * self.tcfg.seq_len
        return (params_to_arrays(self._state.params), n,
                {"train_loss": float(np.mean(losses))})

    def evaluate(self, parameters, config):
        params = arrays_to_params(parameters, self._like)
        batch = self.loader.next_batch(self.site_idx)
        loss = float(_eval_step(self.model.cfg)(params, batch)["loss"])
        return loss, batch["tokens"].size, {}


@functools.lru_cache(maxsize=None)
def _eval_step(cfg):
    """One jitted eval step per model config, shared by every site."""
    return jax.jit(make_eval_step(build_model(cfg)))


def scale_config(scale: str):
    """``(model config, train config, rounds, local steps)`` of a scale.

    ``full``: ~126M fp32 params (d_model 768, 12 layers, d_ff 3072, 12
    heads, vocab 8192), batch 8 x 256 tokens per site.  ``small``: the
    laptop size."""
    base = get_model_config("flower-quickstart")
    if scale == "full":
        cfg = base.replace(d_model=768, num_layers=12, d_ff=3072,
                           num_heads=12, num_kv_heads=12, vocab_size=8192)
        tcfg = TrainConfig(global_batch=8, seq_len=256, learning_rate=1e-3,
                           warmup_steps=20, total_steps=400)
        return cfg, tcfg, 5, 20                      # 400 steps total
    if scale == "small":
        cfg = base.replace(d_model=256, num_layers=4, d_ff=1024,
                           vocab_size=2048, remat=False)
        tcfg = TrainConfig(global_batch=8, seq_len=128, learning_rate=2e-3,
                           warmup_steps=10, total_steps=120)
        return cfg, tcfg, 3, 10
    raise ValueError(f"unknown scale {scale!r}")


def run(scale: str = "small", rounds: int = 0, local_steps: int = 0,
        codec: str = "sparse", sparse_frac: float = 0.05, strategy=None,
        mesh=None):
    """One federated run through the FLARE runtime: ``ServerApp`` ->
    ``run_in_flare`` -> ``LMClient.fit`` on every site -> wire codec ->
    server fold.  ``rounds``/``local_steps`` of 0 take the scale's
    defaults; ``strategy`` defaults to FedAvg.  Returns the History."""
    cfg, tcfg, d_rounds, d_steps = scale_config(scale)
    rounds, local_steps = rounds or d_rounds, local_steps or d_steps
    model = build_model(cfg)
    mesh = mesh if mesh is not None else make_local_mesh()
    print(f"federated LM: {model.param_count()/1e6:.1f}M params, "
          f"{len(SITES)} sites, {rounds} rounds x {local_steps} local "
          f"steps, mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"codec {codec}", flush=True)

    loader = FederatedDataLoader(cfg.vocab_size, tcfg.seq_len,
                                 num_sites=len(SITES),
                                 batch_per_site=tcfg.global_batch,
                                 seed=7, non_iid_alpha=0.5)

    def client_app_fn(site):
        return ClientApp(client_fn=lambda cid: LMClient(
            site, cfg, tcfg, loader, local_steps, mesh=mesh).to_client())

    rt = FlareRuntime(request_timeout=600.0)
    try:
        for s in SITES:
            rt.provision_site(s)
        server = ServerApp(
            config=ServerConfig(
                num_rounds=rounds, round_timeout=3600,
                codec=None if codec == "flat" else codec,
                sparse_frac=sparse_frac),
            strategy=strategy if strategy is not None else FedAvg())
        return run_in_flare(rt, server, client_app_fn, SITES, timeout=7200)
    finally:
        rt.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="small")
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--codec", choices=["flat", "bf16", "q8", "sparse"],
                    default="sparse",
                    help="negotiated uplink codec (default: 0xF5 "
                         "structured-sparse TopK deltas)")
    ap.add_argument("--sparse-frac", type=float, default=0.05,
                    help="TopK fraction for --codec sparse")
    args = ap.parse_args()
    enable_compile_cache()
    history = run(args.scale, args.rounds, codec=args.codec,
                  sparse_frac=args.sparse_frac)

    print("\nper-round federated eval loss:")
    for rec in history.rounds:
        extra = ""
        if "wire_codec" in rec.metrics:
            extra = f"  [wire={rec.metrics['wire_codec']}]"
        if rec.loss is not None:
            print(f"  round {rec.round}: {rec.loss:.4f}{extra}")
    first, last = history.losses()[0][1], history.losses()[-1][1]
    print(f"\nloss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    if last >= first:
        raise SystemExit("federated loss did not improve")


if __name__ == "__main__":
    main()
