"""Paper §5.2: hybrid integration — the Flower client uses FLARE's
experiment-tracking SummaryWriter (Listing 3); metrics from every client
stream to the server and are exported TensorBoard-style (Fig. 6).

    PYTHONPATH=src python examples/hybrid_tracking.py
"""
from repro.core import run_in_flare
from repro.fl import FedAvg, ServerApp, ServerConfig
from repro.fl.client import ClientApp
from repro.fl.quickstart import QuickstartClient
from repro.kernels.platform import enable_compile_cache
from repro.runtime import FlareRuntime

SITES = ["site-1", "site-2", "site-3"]


def client_app_fn(site):
    def with_ctx(ctx):
        writer = ctx.summary_writer()        # <- nvflare.client.tracking API
        return ClientApp(client_fn=lambda cid: QuickstartClient(
            site, writer=writer, lr=0.02, skew=0.2).to_client())
    return with_ctx


def main():
    enable_compile_cache()
    rt = FlareRuntime()
    for s in SITES:
        rt.provision_site(s)
    run_in_flare(rt, ServerApp(config=ServerConfig(num_rounds=3),
                               strategy=FedAvg()), client_app_fn, SITES)
    mc = rt.metrics(next(iter(rt._jobs)))
    print("streamed tags:", mc.tags())
    for tag in mc.tags():
        print(f"  {tag}: {[(s, round(v, 4)) for s, v in mc.series(tag)]}")
    out = mc.export_tensorboard_json("metrics_fig6.json")
    print(f"\nexported {len(out)} bytes to metrics_fig6.json (Fig. 6 artifact)")
    rt.shutdown()


if __name__ == "__main__":
    main()
