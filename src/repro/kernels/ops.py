"""Public jit'd wrappers around the Pallas kernels."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.secagg_mask import secagg_mask as _secagg
from repro.kernels.rglru_scan import rglru_scan as _rglru


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("quant_bits", "block"))
def secagg_mask(x, masks, weight, *, quant_bits: int = 16, block: int = 4096):
    return _secagg(x, masks, weight, quant_bits=quant_bits, block=block)


@functools.partial(jax.jit, static_argnames=("block_s", "block_w"))
def rglru_scan(a, b, h0, *, block_s: int = 256, block_w: int = 512):
    return _rglru(a, b, h0, block_s=block_s, block_w=block_w)
