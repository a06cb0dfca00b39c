"""Paper §3.1: FLARE's multi-job system — two independent FL experiments
run CONCURRENTLY over the same server/clients, without extra ports, each in
its own Job Network.

    PYTHONPATH=src python examples/multi_job.py
"""
import threading
import time

from repro.core.interop import _FlowerClientJob, _FlowerServerJob
from repro.fl import FedAvg, FedAdam, ServerApp, ServerConfig
from repro.fl.quickstart import make_client_app
from repro.kernels.platform import enable_compile_cache
from repro.runtime import FlareRuntime, JobSpec

SITES = ["site-1", "site-2", "site-3"]


def flower_jobspec(name, strategy, lr):
    server_app = ServerApp(config=ServerConfig(num_rounds=2,
                                               round_timeout=300),
                           strategy=strategy)
    return JobSpec(
        name=name,
        server_app_fn=lambda: _FlowerServerJob(server_app, len(SITES)),
        client_app_fn=lambda s: _FlowerClientJob(
            s, make_client_app(s, lr=lr, skew=0.2)),
        min_sites=len(SITES),
        resources={"gpu": 0.5},      # two jobs fit concurrently
    )


def main():
    enable_compile_cache()
    rt = FlareRuntime(request_timeout=300.0)
    for s in SITES:
        rt.provision_site(s)
    admin = rt.provisioner.issue("admin", "admin")

    t0 = time.time()
    j1 = rt.submit_job(flower_jobspec("fedavg-lr02", FedAvg(), 0.02), admin)
    j2 = rt.submit_job(flower_jobspec("fedadam-lr05", FedAdam(server_lr=0.1),
                                      0.05), admin)
    print(f"submitted jobs {j1} and {j2}; both RUNNING concurrently")
    r1 = rt.wait(j1, timeout=600)
    r2 = rt.wait(j2, timeout=600)
    dt = time.time() - t0
    print(f"\nboth done in {dt:.1f}s")
    for name, rec in (("fedavg ", r1), ("fedadam", r2)):
        print(f"  {name}: {rec.status.value:10s} "
              f"losses={[f'{l:.4f}' for _, l in rec.result.losses()]}")
    rt.shutdown()
    assert r1.status.value == "COMPLETED" and r2.status.value == "COMPLETED"


if __name__ == "__main__":
    main()
