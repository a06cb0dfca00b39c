"""Fused quantize + pairwise-mask kernel for secure aggregation.

Every FL upload in the SecAgg protocol (fl/mods.py) runs:
    q   = round(x * weight * 2^bits)  as int32 (two's complement wrap)
    out = q + sum_p mask_p            (mod 2^32, masks cancel server-side)

Done naively that is P+1 HBM round-trips over a multi-GB update; the kernel
fuses quantization and the P-peer mask reduction into one pass with
(block,)-sized VMEM tiles.  Grid: (num_blocks,); the peer loop runs inside
the kernel over the (P, block) mask tile.

TPU note: int32 add wraps (two's complement) on the VPU, matching the
mod-2^32 field the protocol needs; the uint64 variant in fl/mods.py is the
host-side reference field (tests map between them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode


def _kernel(x_ref, m_ref, w_ref, o_ref, *, quant_bits: int):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[0]
    q = jnp.round(x * w * (1 << quant_bits))
    # clamp to int32 range before cast (jnp cast of out-of-range is UB-ish);
    # the protocol guarantees |q| < 2^31 by clipping updates client-side.
    q = jnp.clip(q, -(2.0 ** 31), 2.0 ** 31 - 1).astype(jnp.int32)
    total = jnp.sum(m_ref[...], axis=0, dtype=jnp.int32)
    o_ref[...] = q + total


def secagg_mask(x, masks, weight, *, quant_bits: int = 16, block: int = 4096):
    """x: (N,) float32; masks: (P, N) int32; weight: scalar -> (N,) int32."""
    N = x.shape[0]
    P = masks.shape[0] if masks.size else 0
    block = min(block, N)
    while N % block:
        block -= 1
    grid = (N // block,)
    if P == 0:
        masks = jnp.zeros((1, N), jnp.int32)
        P = 1
    w = jnp.asarray(weight, jnp.float32).reshape(1)

    return pl.pallas_call(
        functools.partial(_kernel, quant_bits=quant_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((P, block), lambda i: (0, i)),
            pl.BlockSpec((1,), lambda i: (0,)),   # scalar weight, broadcast
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        interpret=interpret_mode(),
    )(x, masks, w)
