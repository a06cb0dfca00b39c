"""The four-chip cell at a tiny size on four virtual CPU devices, each
run a process of its own (``four_chips.py``): the layout, a sound run
whose sites' states span the four devices and whose fold has four
ranges, ``correct`` false for every fault the cell can have, the
exchange between chips left out among them, and the program's fit within
the cell's limits where the float8 control is not."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

import check
import federation
import run
import test_faults

SCRIPT = pathlib.Path(__file__).resolve().parent / "four_chips.py"


WORKLOAD = "h2o-danube-1.8b.q8-local10.chips4"


def four_chip_run(*args: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, str(SCRIPT), *args],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    return four_chip_run()


def test_one_chip_keeps_the_program_defaults():
    assert federation.layout(1) == (None, None)


def test_four_chips_shard_the_fit_and_the_fold(sound):
    assert sound["layout"] == {"mesh": {"data": 4, "model": 1},
                               "shard_mesh": {"data": 4}}


def test_a_sound_four_chip_run_is_sharded_and_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["fold_shards"] == 4
    assert sorted(sound["states"]) == [f"site-{i}" for i in range(1, 5)]
    for st in sound["states"].values():
        # every leaf on all four devices, about a quarter of the bytes
        # on each (the norm scales and the step are replicated)
        assert st["devices"] == 4
        assert 0.25 <= st["fullest_share"] < 0.26
    assert sound["attempted"] > 0 and sound["attempted"] % 8 == 0
    assert sound["failed"] == 0


@pytest.mark.parametrize("fault", [
    test_faults.state_unchanged, test_faults.half_batch,
    test_faults.fit_exchange_left_out, test_faults.fold_exchange_left_out,
    test_faults.update_doubled, test_faults.uplink_altered,
    test_faults.eval_half_batch], ids=lambda f: f.__name__)
def test_a_broken_four_chip_path_is_not_correct(fault):
    res = four_chip_run(fault.__name__)
    assert not res["correct"], res["checks"]


def test_four_chip_fit_agrees_and_the_float8_control_does_not():
    got = four_chip_run("control")
    limits = run.load_cell(WORKLOAD)["limits"]
    ok, table = check.verdict(got, limits)
    assert ok, table
    control = {k.split(".", 1)[1]: v for k, v in got.items()
               if k.startswith("control.")}
    ok, table = check.verdict(control, limits)
    assert not ok, table
