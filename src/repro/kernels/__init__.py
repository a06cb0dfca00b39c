"""Pallas TPU kernels for the substrate's compute hot spots.

Three kernels (DESIGN.md §6), each with a pure-jnp oracle in ``ref.py`` and
a jit'd public wrapper in ``ops.py``:

- ``flash_attention``: blockwise causal/GQA/sliding-window attention with
  online softmax (HBM->VMEM streaming of K/V blocks, MXU-aligned tiles).
- ``secagg_mask``: fused fixed-point quantize + pairwise-mask reduction for
  secure aggregation — the elementwise hot path of every FL upload.
- ``rglru_scan``: chunked RG-LRU linear recurrence h_t = a_t*h_{t-1} + b_t.

Plus ``agg_reduce``: the server-side aggregation reductions (fused
weighted sum, sorted median/trimmed tiles, Krum Gram matrix) over flat
parameter buffers, differential-tested against the numpy kernels in
:mod:`repro.fl.agg_kernels` (its reference path and dispatch layer).

On a TPU the kernels compile; elsewhere the same bodies run in interpret
mode (``repro.kernels.platform`` decides, in one place), which is how the
CPU test suite checks them.  ``tests/test_chip_compile.py`` compiles the
main-path kernels for a described v5e chip.
"""
from repro.kernels import ops, ref  # noqa: F401
