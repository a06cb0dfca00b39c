"""Compile the main path for a described TPU v5e chip (no chip attached).

Interpret mode runs the kernel bodies on the CPU but accepts layouts the
chip's compiler refuses (blocks off the (8, 128) tiling, slices of loaded
values, too much fast memory).  These tests compile, for one described
v5e chip, the kernels and the fit step that a federated round runs at
``examples/federated_llm.py --scale full``:

- the fold kernels (``weighted_sum`` at ~126M elements for fp32 and int8
  payloads and the accumulator-continuation form, ``sort_reduce`` and
  ``gram`` at 16 clients, the sparse dequant graph), and FedAvg's
  finalize over payloads staged on the device at the danube cell's size;
- ``flash_attention`` at 12 heads x 64 head dim x 256 tokens;
- the wire codec's slab quantizer at ``repro.fl.flat.SLAB``;
- the full-scale train step, whose compiled memory must fit 16 GB.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler's library.
"""
import importlib.util
import os
import pathlib
import sys

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import agg_reduce as A  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]

#: the --scale full model's parameter count, rounded up
N_FULL = 126_000_000
#: the h2o-danube-1.8b benchmark cell's parameter count
N_DANUBE = 111_418_880
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fold_operands(one_chip, geom, clients, dtype, q8):
    lanes, _, rows = geom
    args = [_shape(one_chip, (clients, rows, lanes), dtype)]
    if q8:
        args.append(_shape(one_chip, (clients, rows, 1), jnp.float32))
    return args


@pytest.mark.parametrize("payload", ["f32", "int8", "acc"])
def test_weighted_sum_compiles(one_chip, no_cache, payload):
    """4 sites' fp32 or int8 payloads in one fold, and one arrival folded
    into a running ``(hi, lo)`` accumulator (the streaming form)."""
    q8 = payload == "int8"
    clients = 1 if payload == "acc" else 4
    geom = A.geometry(N_FULL, clients, A.DEFAULT_QCHUNK if q8 else None)
    lanes, _, rows = geom
    args = [_shape(one_chip, (clients, 4), jnp.float32)]
    if payload == "acc":
        args += [_shape(one_chip, (rows, lanes), jnp.float32)] * 2
    args += _fold_operands(one_chip, geom, clients,
                           jnp.int8 if q8 else jnp.float32, q8)
    fn = A.wsum_fn(geom, clients, q8, payload == "acc", False)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_staged_fold_compiles_and_fits(one_chip, no_cache):
    """A FedAvg finalize at the danube cell's size (4 sites' int8 payloads
    of ``N_DANUBE`` elements, staged on the device as they arrived): the
    on-device stack and padding of the payloads and of their scales, and
    the kernel.  The staged vectors, the stacks and the ``(hi, lo)`` pair
    fit one chip's HBM together."""
    clients, qchunk = 4, A.DEFAULT_QCHUNK
    geom = A.geometry(N_DANUBE, clients, qchunk)
    lanes, _, rows = geom
    data = tuple(_shape(one_chip, (N_DANUBE,), jnp.int8)
                 for _ in range(clients))
    scales = tuple(_shape(one_chip, (-(-N_DANUBE // qchunk),), jnp.float32)
                   for _ in range(clients))
    programs = [
        A._device_rows.lower(data, 1, (rows, lanes), 0).compile(),
        A._device_rows.lower(scales, qchunk // lanes, (rows, 1), 1)
        .compile(),
        A.wsum_fn(geom, clients, True, False, False).lower(
            _shape(one_chip, (clients, 4), jnp.float32),
            *_fold_operands(one_chip, geom, clients, jnp.int8, True))
        .compile()]
    assert "tpu_custom_call" in programs[-1].as_text()
    used = 0
    for compiled in programs:
        mem = compiled.memory_analysis()
        used += (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # at least the int8 staged payloads, their stack and the fp32 pair
    assert N_DANUBE * (2 * clients + 2 * 4) <= used < HBM_BYTES, used


@pytest.mark.parametrize("kind", ["median", "trim_sum"])
@pytest.mark.parametrize("q8", [False, True])
def test_sort_reduce_compiles(one_chip, no_cache, kind, q8):
    geom = A.geometry(N_FULL, 16, A.DEFAULT_QCHUNK if q8 else None)
    fn = A.sort_fn(geom, 16, q8, kind, 3 if kind == "trim_sum" else 0,
                   False)
    fn.lower(*_fold_operands(one_chip, geom, 16,
                             jnp.int8 if q8 else jnp.float32, q8)).compile()


@pytest.mark.parametrize("q8", [False, True])
def test_gram_compiles(one_chip, no_cache, q8):
    geom = A.geometry(N_FULL, 16, A.DEFAULT_QCHUNK if q8 else None,
                      max_rows=A._GRAM_ROWS)
    fn = A.gram_fn(geom, 16, q8, False)
    fn.lower(*_fold_operands(one_chip, geom, 16,
                             jnp.int8 if q8 else jnp.float32, q8)).compile()


def test_sparse_dequant_compiles(one_chip, no_cache):
    n = 1 << 22
    A.dequant_q8.lower(_shape(one_chip, (n,), jnp.int8),
                       _shape(one_chip, (n,), jnp.float32)).compile()


def test_slab_quantizer_compiles_and_fits(one_chip, no_cache):
    """The wire codec's device q8 engine at the module's ``SLAB``: its two
    programs (window maxima, then ``q``) hold under the 2 x 80 MiB that
    two slabs in flight may take."""
    from repro.fl import flat

    x = _shape(one_chip, (flat.SLAB,), jnp.float32)
    s = _shape(one_chip, (flat.SLAB // flat.QCHUNK,), jnp.float32)
    used = 0
    for compiled in (flat._slab_amax.lower(x, flat.QCHUNK).compile(),
                     flat._slab_q.lower(x, s).compile()):
        mem = compiled.memory_analysis()
        used += (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < 2 * 80 * 1024 ** 2, used


def test_flash_attention_compiles(one_chip, no_cache):
    from repro.kernels.flash_attention import flash_attention

    B, S, H, hd = 8, 256, 12, 64
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 interpret=False))
    q = _shape(one_chip, (B, S, H, hd), jnp.float32)
    compiled = fn.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _example():
    spec = importlib.util.spec_from_file_location(
        "federated_llm_example", _ROOT / "examples" / "federated_llm.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("federated_llm_example", mod)
    spec.loader.exec_module(mod)
    return mod


def test_full_scale_train_step_compiles_and_fits(one_chip, no_cache):
    """The --scale full fit step from ``eval_shape`` shapes: the compiled
    program's arguments, outputs and temporaries fit one chip's HBM."""
    from repro.models import build_model
    from repro.train.steps import abstract_train_state, make_train_step

    cfg, tcfg, _, _ = _example().scale_config("full")
    model = build_model(cfg)
    state = jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype),
        abstract_train_state(model, tcfg))
    tok = _shape(one_chip, (tcfg.global_batch, tcfg.seq_len), jnp.int32)
    step = jax.jit(make_train_step(model, tcfg))
    compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
