"""Chunked RG-LRU linear recurrence kernel: h_t = a_t * h_{t-1} + b_t.

Grid: (batch, width_blocks, seq_blocks) with the SEQ axis minor/sequential
("arbitrary" semantics): the carry h lives in VMEM scratch and persists
across seq blocks; within a block the kernel runs a fori_loop over the
block's timesteps entirely in VMEM.  This is the TPU-native adaptation of
the recurrence (a GPU impl would parallel-scan across SMs; on TPU the
block-sequential scan with the 8x128 VPU lanes across width is the natural
layout — DESIGN.md §6).

a, b: (B, S, W) fp32;  h0: (B, W) fp32  ->  (ys (B,S,W), h_final (B,W)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


def _kernel(h0_ref, a_ref, b_ref, y_ref, hout_ref, h_ref, *,
            block_s: int, num_seq_blocks: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0]                       # (1, bw) -> (bw,) carry

    a = a_ref[0]                                     # (bs, bw)
    b = b_ref[0]

    def step(t, h):
        h = a[t] * h + b[t]
        y_ref[0, t, :] = h
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(si == num_seq_blocks - 1)
    def _finalize():
        hout_ref[0] = h


def rglru_scan(a, b, h0, *, block_s: int = 256, block_w: int = 512):
    B, S, W = a.shape
    block_s = min(block_s, S)
    while S % block_s:
        block_s -= 1
    block_w = min(block_w, W)
    while W % block_w:
        block_w -= 1
    ns, nw = S // block_s, W // block_w

    ys, hf = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, num_seq_blocks=ns),
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_w), lambda bi, wi, si: (bi, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi, si: (bi, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(h0, a, b)
    return ys, hf
