"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + properties.

All kernels run in interpret mode (CPU container); BlockSpecs/grids are the
TPU configuration under test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare env: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(1234)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # B, S, H, KV, hd, window, causal, dtype
    (2, 64, 4, 2, 16, 0, True, jnp.float32),
    (1, 128, 8, 8, 32, 0, True, jnp.float32),
    (2, 96, 4, 1, 16, 24, True, jnp.float32),    # MQA + sliding window
    (1, 64, 4, 4, 16, 0, False, jnp.float32),    # bidirectional (encoder)
    (1, 64, 8, 2, 64, 0, True, jnp.bfloat16),
    (2, 80, 2, 2, 8, 16, True, jnp.float32),     # non-pow2 seq
]


@pytest.mark.parametrize("B,S,H,KV,hd,window,causal,dtype", FLASH_CASES)
def test_flash_attention_matches_ref(B, S, H, KV, hd, window, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype) / np.sqrt(hd)
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_kv=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("S,causal", [(509, True), (509, False), (127, True),
                                      (33, True)])
def test_flash_attention_prime_seq_len(S, causal):
    """Regression: prime/odd S must not degrade the block size to 1 (the
    old `while S % block_q: block_q -= 1` loop); the kernel now pads the
    sequence to a multiple of an aligned block and masks the tail."""
    from repro.kernels.flash_attention import _choose_block

    assert _choose_block(509, 64) == 64        # pads, never collapses to 1
    assert _choose_block(80, 32) == 16         # largest aligned divisor
    assert _choose_block(128, 512) == 128      # short seqs use one block
    B, H, KV, hd = 1, 2, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32) / 4
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bkv", [(16, 16), (32, 64), (64, 32), (128, 128)])
def test_flash_attention_block_shape_invariance(bq, bkv):
    """Output must not depend on the tiling choice."""
    B, S, H, KV, hd = 1, 128, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=bq, block_kv=bkv)
    b = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 1),
       st.sampled_from([16, 32, 48]), st.integers(0, 20))
def test_flash_attention_property(b, kv_groups, mqa, seq_mult, window):
    """Property: any (B, group-structure, S, window) agrees with the oracle."""
    KV = 1 if mqa else 2
    H = KV * kv_groups
    S = 16 * seq_mult
    hd = 8
    rng = np.random.default_rng(b * 1000 + H * 10 + S + window)
    q = jnp.asarray(rng.normal(size=(b, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, S, KV, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, window=window, block_q=16, block_kv=16)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# secagg quantize+mask
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,P,block", [(1000, 3, 128), (4096, 1, 4096),
                                       (513, 5, 64), (64, 0, 64)])
def test_secagg_mask_matches_ref(N, P, block):
    x = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    masks = jnp.asarray(
        RNG.integers(-2 ** 31, 2 ** 31 - 1, size=(max(P, 1), N)), jnp.int32)
    if P == 0:
        masks = masks[:0]
    got = ops.secagg_mask(x, masks, 3.0, block=block)
    want = ref.secagg_mask_ref(x, masks, 3.0)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_secagg_masks_cancel():
    """Pairwise +m / -m masks cancel exactly in the int32 field."""
    N = 256
    x1 = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    x2 = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    m = jnp.asarray(RNG.integers(-2 ** 31, 2 ** 31 - 1, size=(1, N)), jnp.int32)
    a = ops.secagg_mask(x1, m, 1.0, block=64)
    b = ops.secagg_mask(x2, -m, 1.0, block=64)
    plain = (ref.secagg_mask_ref(x1, m[:0], 1.0)
             + ref.secagg_mask_ref(x2, m[:0], 1.0))
    assert np.array_equal(np.asarray(a + b), np.asarray(plain))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(0, 4),
       st.floats(0.25, 1000.0, allow_nan=False))
def test_secagg_property(nmult, P, weight):
    N = 16 * nmult
    rng = np.random.default_rng(N + P)
    x = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    masks = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31 - 1, size=(max(P, 1), N)),
                        jnp.int32)[: P]
    got = ops.secagg_mask(x, masks, weight, block=16)
    want = ref.secagg_mask_ref(x, masks, weight)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,W,bs,bw", [(2, 64, 96, 16, 32),
                                         (1, 128, 64, 128, 64),
                                         (3, 48, 32, 8, 32),
                                         (2, 96, 128, 24, 64)])
def test_rglru_scan_matches_ref(B, S, W, bs, bw):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, size=(B, S, W)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, S, W)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, W)), jnp.float32)
    ys, hf = ops.rglru_scan(a, b, h0, block_s=bs, block_w=bw)
    ys_r, hf_r = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_r), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_r), rtol=1e-5,
                               atol=1e-5)


def test_rglru_carry_across_seq_blocks():
    """Final state from chunked kernel == running the chain in one block."""
    B, S, W = 1, 64, 32
    a = jnp.asarray(RNG.uniform(0.9, 0.999, size=(B, S, W)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, S, W)), jnp.float32)
    h0 = jnp.zeros((B, W), jnp.float32)
    _, hf_chunked = ops.rglru_scan(a, b, h0, block_s=8, block_w=32)
    _, hf_single = ops.rglru_scan(a, b, h0, block_s=64, block_w=32)
    np.testing.assert_allclose(np.asarray(hf_chunked), np.asarray(hf_single),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.integers(1, 8), st.integers(1, 4))
def test_rglru_property(B, smult, wmult):
    S, W = 8 * smult, 8 * wmult
    rng = np.random.default_rng(S * 100 + W)
    a = jnp.asarray(rng.uniform(0.5, 1.0, size=(B, S, W)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, S, W)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    ys, hf = ops.rglru_scan(a, b, h0, block_s=8, block_w=8)
    ys_r, hf_r = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_r), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# kernel <-> model-path agreement
# ---------------------------------------------------------------------------
def test_pallas_path_matches_xla_path_in_model():
    from repro.models import attention_impl

    B, S, H, KV, hd = 1, 64, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32) / 4
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)), jnp.float32)
    a = attention_impl.causal_attention(q, k, v, impl="xla")
    b = attention_impl.causal_attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# platform: interpret mode and the compile cache
# ---------------------------------------------------------------------------
def test_interpret_mode_follows_the_platform():
    from repro.kernels import platform

    assert platform.interpret_mode() is (jax.default_backend() != "tpu")
    assert platform.interpret_mode(True) is True
    assert platform.interpret_mode(False) is False


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to one fixed, git-ignored directory in the checkout."""
    import pathlib

    from repro.kernels import platform

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert platform.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = pathlib.Path(platform.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == str(path)
            root = pathlib.Path(__file__).resolve().parents[1]
            assert path.parent == root
            ignored = (root / ".gitignore").read_text().split()
            assert f"{path.name}/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
