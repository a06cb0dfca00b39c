"""One tiny run of the four-chip cell on four virtual CPU devices, the
harness's look for a chip skipped, sound or with one of ``test_faults``'s
faults planted, or one seed of its calibration with the control:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 benchmarks/chip/tests/four_chips.py [FAULT | control]

Prints one JSON object: the run's result, the device layout that
``federation.layout(4)`` gives, the sharded fold's accumulator ranges and
the sites' train states as ``run.state_layout`` reads them after the
window; or ``calibrate.one_seed``'s readings.  A process of its own,
since JAX fixes its device count when it starts."""
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[2] / "src"):
    sys.path.insert(0, str(p))

import pytest  # noqa: E402

import calibrate  # noqa: E402
import federation  # noqa: E402
import run  # noqa: E402
import test_faults  # noqa: E402
import tiny  # noqa: E402

WORKLOAD = "h2o-danube-1.8b.q8-local10.chips4"


def main() -> None:
    if sys.argv[1:] == ["control"]:
        cell = tiny.cell(WORKLOAD)
        print(json.dumps(calibrate.one_seed(cell, run.prepare(cell),
                                            2**31 + 5, True)), flush=True)
        return
    seen = {}
    federate = federation.run

    def spy(*a, **kw):
        history, strat, clients = federate(*a, **kw)
        seen["fold_shards"] = strat.fold_shards
        seen["states"] = run.state_layout(clients)
        return history, strat, clients

    mesh, shard_mesh = federation.layout(4)
    seen["layout"] = {"mesh": dict(mesh.shape),
                      "shard_mesh": dict(shard_mesh.shape)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(federation, "run", spy)
        if len(sys.argv) > 1:
            getattr(test_faults, sys.argv[1])(mp)
        res = run.run_cell(tiny.cell(WORKLOAD), test_faults.SEED, 1.0, False)
    print(json.dumps({**res, **seen}), flush=True)


if __name__ == "__main__":
    main()
