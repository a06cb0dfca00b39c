"""Attention contraction implementations.

``impl="xla"`` — einsum + masked softmax; lowers on every backend and is
what the 512-device dry-run compiles.  ``impl="pallas"`` — the flash
attention TPU kernel from ``repro.kernels`` (compiled on a TPU, interpret
mode elsewhere — ``repro.kernels.platform.interpret_mode``).
Both satisfy the same contract and are cross-checked in tests.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _expand_kv(k, heads_per_kv: int):
    if heads_per_kv == 1:
        return k
    B, S, KV, hd = k.shape
    return jnp.repeat(k, heads_per_kv, axis=2)


def _chunk_size(S: int, target: int = 1024) -> int:
    for c in range(min(target, S), 0, -1):
        if S % c == 0:
            return c
    return S


def causal_attention(q, k, v, *, window: int = 0, impl: str = "xla",
                     causal: bool = True, chunk: int = 1024):
    """q: (B,S,H,hd) (pre-scaled), k/v: (B,S,KV,hd); returns (B,S,H,hd_v).

    The XLA path processes queries in chunks (lax.scan) so the score matrix
    materializes as (B,KV,g,chunk,S) instead of (B,KV,g,S,S) — the pure-XLA
    stand-in for flash attention (the Pallas kernel is the TPU fast path).
    """
    if impl == "pallas":
        from repro.kernels import ops

        return ops.flash_attention(q, k, v, causal=causal, window=window)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    from repro.sharding import constrain_scores, model_axis_size

    # GQA -> MHA expansion when the q-head count shards over "model" but
    # the kv-head count does not: each model shard then owns its heads'
    # scores with zero attention collectives, at the cost of replicating
    # the small (B,S,KV,hd) K/V (§Perf iteration C-1'')
    msz = model_axis_size()
    if msz > 1 and g > 1 and H % msz == 0 and KV % msz != 0:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        KV, g = H, 1
    qg = q.reshape(B, S, KV, g, hd)
    C = _chunk_size(S, chunk)

    def one_chunk(start, q_chunk):
        scores = jnp.einsum("bqkgh,bskh->bkgqs", q_chunk, k,
                            preferred_element_type=jnp.float32)
        # Sk stays model-sharded: local partial QK^T + tiny softmax
        # reductions, no K/V gather and no replicated score matrix
        scores = constrain_scores(scores)
        kpos = jnp.arange(S)[None, :]
        qpos = start + jnp.arange(C)[:, None]
        mask = jnp.ones((C, S), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        scores = constrain_scores(scores)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskh->bqkgh", w, v)

    if C == S:
        ctx = one_chunk(0, qg)
    else:
        n = S // C
        qs = jnp.moveaxis(qg.reshape(B, n, C, KV, g, hd), 1, 0)

        # checkpoint the chunk body: otherwise scan's backward stacks every
        # chunk's fp32 scores/softmax weights (flash attention recomputes
        # them per block; this is the XLA equivalent)
        chunk_fn = jax.checkpoint(one_chunk)

        def body(_, xs):
            i, qc = xs
            return (), chunk_fn(i * C, qc)

        _, ctx = jax.lax.scan(body, (), (jnp.arange(n), qs))
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, S, KV, g, v.shape[-1])
    return ctx.reshape(B, S, H, v.shape[-1])


def decode_attention(q, k_cache, v_cache, *, slot_pos, query_pos, window: int = 0):
    """One-token attention against a (possibly rotating) cache.

    q: (B,1,H,hd) pre-scaled; k/v_cache: (B,S,KV,hd); slot_pos: (B,S) absolute
    position held in each slot (-1 = empty); query_pos: (B,).
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    g = H // KV
    qg = q[:, 0].reshape(B, KV, g, hd)
    # preferred_element_type (NOT a post-cast): an explicit convert of the
    # cache gets hoisted out of the layer scan by XLA, materializing a full
    # f32 cache copy (observed +8.6GB/device)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    valid = (slot_pos >= 0) & (slot_pos <= query_pos[:, None])
    if window:
        valid &= slot_pos > (query_pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bkgs,bskh->bkgh", w, v_cache)
    return ctx.reshape(B, 1, H, v_cache.shape[-1])
