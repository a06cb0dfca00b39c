"""Paper §5.1: the quickstart app, run natively AND inside FLARE — no code
changes, identical results (Fig. 5).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import run_in_flare, run_native
from repro.fl import FedAdam, ServerApp, ServerConfig
from repro.fl.quickstart import make_client_app
from repro.kernels.platform import enable_compile_cache
from repro.runtime import FlareRuntime

SITES = ["site-1", "site-2", "site-3"]


def make_server_app():
    # paper Listing 1: strategy + ServerApp
    strategy = FedAdam(server_lr=0.1)
    return ServerApp(config=ServerConfig(num_rounds=3), strategy=strategy)


def main():
    enable_compile_cache()
    print("== running the Flower app natively (SuperLink + SuperNodes) ==")
    h_native = run_native(make_server_app(),
                          lambda s: make_client_app(s, lr=0.02, skew=0.2),
                          SITES)
    for rnd, loss in h_native.losses():
        print(f"  round {rnd}: eval loss {loss:.5f}")

    print("== running the SAME app inside the FLARE runtime ==")
    rt = FlareRuntime()
    for s in SITES:
        rt.provision_site(s)
    h_flare = run_in_flare(rt, make_server_app(),
                           lambda s: make_client_app(s, lr=0.02, skew=0.2),
                           SITES)
    rt.shutdown()
    for rnd, loss in h_flare.losses():
        print(f"  round {rnd}: eval loss {loss:.5f}")

    same = h_native.losses() == h_flare.losses() and all(
        np.array_equal(a, b) for a, b in zip(h_native.final_parameters,
                                             h_flare.final_parameters))
    print(f"\nFig. 5 check — curves and final params bitwise identical: {same}")
    assert same


if __name__ == "__main__":
    main()
