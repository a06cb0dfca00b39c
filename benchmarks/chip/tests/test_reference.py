"""The plain references against the program, on the CPU at a tiny size:
the program's fit steps agree with the float32 reference within the
cell's limits, and the same reference computed in float8 (the control)
does not; the reference's q8 codec is the wire codec bit for bit, and
each configuration file's shapes are the program's."""
import numpy as np
import pytest

import calibrate
import check
import run
import tiny

CELLS = ("h2o-danube-1.8b.q8-local10", "granite-moe-1b-a400m.q8-local10")


def test_q8_roundtrip_is_the_wire_codec():
    from repro.fl.messages import FitIns, decode_fit_ins, encode_fit_ins

    ref = run._import(run.HERE / "reference" / "decoder_lm.py", "ref_lm")
    rng = np.random.default_rng(3)
    # a ragged last window, an all-zero window, leaves across windows
    leaves = [rng.normal(0, 0.02, (3, 700)).astype(np.float32),
              np.zeros(1024, np.float32),
              rng.normal(0, 1.0, (5, 77)).astype(np.float32)]
    wire = decode_fit_ins(encode_fit_ins(FitIns(leaves, {}), codec="q8"))
    for a, b in zip(wire.parameters, ref.q8_roundtrip(leaves)):
        assert np.array_equal(np.asarray(a).view(np.uint32),
                              b.view(np.uint32))


@pytest.mark.parametrize("workload", CELLS)
def test_configuration_files_have_the_programs_shapes(workload):
    prep = run.prepare(run.load_cell(workload))
    assert prep["model"].param_count() == sum(
        int(np.prod(s)) for _, s, _ in prep["leaves"])


@pytest.mark.parametrize("workload", CELLS)
def test_program_fit_agrees_and_the_float8_control_does_not(workload):
    cell = tiny.cell(workload)
    got = calibrate.one_seed(cell, run.prepare(cell), 2**31 + 5, True)
    limits = cell["limits"]
    ok, table = check.verdict(got, limits)
    assert ok, table
    control = {k.split(".", 1)[1]: v for k, v in got.items()
               if k.startswith("control.")}
    ok, table = check.verdict(control, limits)
    assert not ok, table
