"""Secure + private federation: pairwise-mask SecAgg and local DP, composed
as client mods (the Flower built-ins the paper's FLARE users gain, §1),
with seeds derived from FLARE provisioning.

    PYTHONPATH=src python examples/secure_federation.py
"""
import numpy as np

from repro.core import run_native
from repro.fl import (DPMod, FedAvg, SecAggFedAvg, SecAggMod, ServerApp,
                      ServerConfig)
from repro.fl.quickstart import make_client_app
from repro.kernels.platform import enable_compile_cache
from repro.runtime.provision import Provisioner

SITES = ["site-1", "site-2", "site-3"]


def main():
    enable_compile_cache()
    prov = Provisioner("secure-fed-demo", secret=b"\x07" * 32)
    for s in SITES:
        prov.issue(s, "client")

    print("== plain FedAvg (server sees every update) ==")
    h_plain = run_native(
        ServerApp(config=ServerConfig(num_rounds=3), strategy=FedAvg()),
        lambda s: make_client_app(s, lr=0.02, skew=0.2), SITES)
    print("  losses:", [f"{l:.5f}" for _, l in h_plain.losses()])

    print("== SecAgg: server only ever sees masked shares ==")
    h_sec = run_native(
        ServerApp(config=ServerConfig(num_rounds=3), strategy=SecAggFedAvg()),
        lambda s: make_client_app(s, lr=0.02, skew=0.2, mods=[SecAggMod(
            site=s, peers=SITES, pairwise_seed_fn=prov.pairwise_seed)]),
        SITES)
    print("  losses:", [f"{l:.5f}" for _, l in h_sec.losses()])
    delta = max(float(np.abs(a.astype(np.float64) - b.astype(np.float64)).max())
                for a, b in zip(h_plain.final_parameters,
                                h_sec.final_parameters))
    print(f"  max param delta vs plain: {delta:.2e} "
          f"(fixed-point quantization only)")

    print("== SecAgg + local DP (clip 1.0, sigma 0.1) ==")
    h_dp = run_native(
        ServerApp(config=ServerConfig(num_rounds=3), strategy=SecAggFedAvg()),
        lambda s: make_client_app(s, lr=0.02, skew=0.2, mods=[
            DPMod(clip_norm=1.0, noise_multiplier=0.1,
                  site_id=int(s[-1]), seed=13),
            SecAggMod(site=s, peers=SITES,
                      pairwise_seed_fn=prov.pairwise_seed)]),
        SITES)
    print("  losses:", [f"{l:.5f}" for _, l in h_dp.losses()])


if __name__ == "__main__":
    main()
