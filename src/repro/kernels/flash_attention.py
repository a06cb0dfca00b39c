"""Flash attention Pallas TPU kernel (causal / GQA / sliding-window).

Grid: (batch * q_heads, num_q_blocks, num_kv_blocks) — the kv dimension is
the minor (sequential / "arbitrary") grid axis, so the kernel revisits the
same output block while streaming K/V blocks HBM->VMEM and maintaining the
online-softmax state (m, l, acc) in VMEM scratch.  Tiles are MXU-aligned
(block_q x head_dim and block_kv x head_dim, multiples of 128 at real
sizes; tests use smaller shapes, which interpret mode permits).

VMEM working set per step:
    q block   block_q  * hd * 4
    k,v block block_kv * hd * 4 * 2
    acc/m/l   block_q * (hd + 2) * 4
e.g. block_q=block_kv=512, hd=128: ~1.6 MB — well inside the ~16MB VMEM.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _choose_block(S: int, requested: int) -> int:
    """Pick a block size for a sequence of length ``S``.

    Prefer the largest divisor of ``S`` that is <= ``requested`` and keeps
    tiles lane-aligned (multiple of 8), falling back to ``S`` itself when it
    is small. If no aligned divisor exists (prime/odd ``S``), keep the
    requested block and let the caller pad the sequence up to a multiple of
    it — never degrade toward block size 1, which serializes the grid.
    """
    b = max(1, min(requested, S))
    if S % b == 0:
        return b
    for cand in range(b, 7, -1):
        if S % cand == 0 and cand % 8 == 0:
            return cand
    return b


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, block_q: int, block_kv: int,
            num_kv_blocks: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bkv, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T)                              # (bq, bkv) on the MXU
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_len                # padded kv positions contribute 0
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be 1)
    row_any = jnp.any(mask, axis=1, keepdims=True)
    p = jnp.where(row_any, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: Optional[bool] = None):
    """q: (B,S,H,hd) pre-scaled; k/v: (B,S,KV,hd) -> (B,S,H,hd_v)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    hdv = v.shape[-1]
    block_q = _choose_block(S, block_q)
    block_kv = _choose_block(S, block_kv)
    # smallest common padded length (equals S whenever both blocks divide S)
    l = math.lcm(block_q, block_kv)
    S_pad = -(-S // l) * l
    nq = S_pad // block_q
    nk = S_pad // block_kv

    # flatten (B, H) into the major grid axis; kv head = q head // g
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * KV, S, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * KV, S, hdv)
    if S_pad != S:
        pad = ((0, 0), (0, S_pad - S), (0, 0))
        qf, kf, vf = (jnp.pad(x, pad) for x in (qf, kf, vf))

    def q_index(h, i, j):
        return (h, i, 0)

    def kv_index(h, i, j):
        return ((h // g), j, 0)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, num_kv_blocks=nk, seq_len=S)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_index),
            pl.BlockSpec((1, block_kv, hd), kv_index),
            pl.BlockSpec((1, block_kv, hdv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hdv), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, S_pad, hdv), q.dtype),
        scratch_shapes=[
            # online-softmax state persists across the kv (minor) grid axis
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, hdv), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(qf, kf, vf)
    return jnp.moveaxis(out[:, :S].reshape(B, H, S, hdv), 1, 2)
