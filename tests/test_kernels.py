"""Pallas kernels vs jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels import ref as R


@pytest.mark.parametrize("N,U,M", [(16, 8, 5), (64, 128, 64), (33, 256, 17),
                                   (128, 512, 200)])
@pytest.mark.parametrize("dt", [np.float32, np.int32, "bfloat16"])
def test_pack_sweep(N, U, M, dt, rng):
    data = rng.standard_normal((N, U)).astype(np.float32)
    data = jnp.asarray(data).astype(dt)
    idx = jnp.asarray(rng.integers(0, N, M).astype(np.int32))
    out = K.sf_pack(data, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(R.pack_ref(data, idx)))


@pytest.mark.parametrize("dims,strides,start", [
    ((4, 3, 2), (1, 8, 48), 2),
    ((8, 1, 1), (1, 8, 8), 0),
    ((2, 5, 4), (1, 16, 80), 7),
])
def test_pack_strided_sweep(dims, strides, start, rng):
    n_rows = start + strides[2] * dims[2] + strides[1] * dims[1] + dims[0] + 4
    data = jnp.asarray(rng.standard_normal((n_rows, 128)).astype(np.float32))
    out = K.sf_pack_strided(data, start=start, dims=dims, strides=strides)
    want = R.pack_strided_ref(data, start, dims, strides)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("op", ["sum", "max", "min", "prod"])
@pytest.mark.parametrize("M,U,S", [(37, 16, 9), (128, 128, 20), (5, 8, 1)])
def test_unpack_sweep(op, M, U, S, rng):
    buf = rng.standard_normal((M, U)).astype(np.float32)
    if S > 1:
        cuts = np.sort(rng.choice(np.arange(1, M), S - 1, replace=False))
    else:
        cuts = np.zeros(0, np.int64)
    seg_start = np.concatenate([[0], cuts]).astype(np.int64)
    seg_end = np.concatenate([cuts, [M]]).astype(np.int64)
    seg_len = seg_end - seg_start
    seg_dst = rng.permutation(64)[:S]
    target = rng.standard_normal((64, U)).astype(np.float32)
    got = K.sf_unpack(jnp.asarray(target), jnp.asarray(buf), seg_start,
                      seg_len, seg_dst, op=op)
    seg_ids = np.repeat(np.arange(S), seg_len)
    red = np.asarray(R.unpack_segment_ref(jnp.asarray(buf),
                                          jnp.asarray(seg_ids), S, op))
    want = target.copy()
    for s in range(S):
        if op == "sum":
            want[seg_dst[s]] += red[s]
        elif op == "max":
            want[seg_dst[s]] = np.maximum(want[seg_dst[s]], red[s])
        elif op == "min":
            want[seg_dst[s]] = np.minimum(want[seg_dst[s]], red[s])
        else:
            want[seg_dst[s]] *= red[s]
    # atol: kernel panel reductions re-associate float sums vs the oracle
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("Sq,Skv,H,Hkv,D,causal,window", [
    (128, 128, 4, 2, 64, True, None),
    (100, 100, 2, 2, 32, True, None),
    (1, 96, 4, 1, 64, True, None),       # decode against prefix cache
    (64, 192, 8, 4, 64, True, 48),       # sliding window + prefix
    (128, 128, 2, 1, 128, False, None),  # bidirectional
    (73, 129, 3, 3, 64, True, None),     # ragged tails
])
def test_flash_attention_sweep(Sq, Skv, H, Hkv, D, causal, window, rng):
    q = jnp.asarray(rng.standard_normal((Sq, H, D)).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.standard_normal((Skv, Hkv, D)).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.standard_normal((Skv, Hkv, D)).astype(np.float32))
    got = K.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=32, block_k=32)
    want = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.standard_normal((64, 4, 64)), jnp.bfloat16) * 0.3
    k = jnp.asarray(rng.standard_normal((64, 2, 64)), jnp.bfloat16) * 0.3
    v = jnp.asarray(rng.standard_normal((64, 2, 64)), jnp.bfloat16)
    got = K.flash_attention(q, k, v, block_q=32, block_k=32)
    want = R.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("N,Kd,Nx", [(50, 7, 40), (256, 16, 300), (8, 1, 8)])
def test_spmv_ell_sweep(N, Kd, Nx, rng):
    data = jnp.asarray(rng.standard_normal((N, Kd)).astype(np.float32))
    cols = jnp.asarray(rng.integers(0, Nx, (N, Kd)).astype(np.int32))
    x = np.zeros(Nx + 1, np.float32)
    x[:Nx] = rng.standard_normal(Nx)
    x = jnp.asarray(x)
    got = K.spmv_ell(data, cols, x, block_rows=64)
    want = R.spmv_ell_ref(data, cols, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flash_matches_chunked_training_path(rng):
    """Pallas kernel == the differentiable chunked-scan implementation."""
    from repro.models.layers import _chunked_attn
    B, S, H, Hkv, D = 2, 96, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32) * .3)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32) * .3)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)).astype(np.float32))
    chunked = _chunked_attn(q, k, v, qpos0=0, causal=True, window=None,
                            chunk=32)
    kernel = jax.vmap(lambda qq, kk, vv: K.flash_attention(
        qq, kk, vv, causal=True, block_q=32, block_k=32))(q, k, v)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(chunked),
                               rtol=2e-4, atol=2e-5)


def test_flash_heads_real_lengths_and_value_dim(rng):
    """Head-major kernel with values of another head dim (latent
    attention: 24 against 16 here) and a real length per head row: the
    real rows equal attention over the real prefix alone, and query blocks
    wholly past it come out zero (skipped)."""
    from repro.kernels.flash_attention import flash_attention_heads
    H, S, Dq, Dv, blk = 3, 80, 24, 16, 16
    q = jnp.asarray(rng.standard_normal((H, S, Dq)).astype(np.float32) * .3)
    k = jnp.asarray(rng.standard_normal((H, S, Dq)).astype(np.float32) * .3)
    v = jnp.asarray(rng.standard_normal((H, S, Dv)).astype(np.float32))
    lengths = np.array([80, 37, 5], np.int32)
    got = np.asarray(flash_attention_heads(q, k, v, jnp.asarray(lengths),
                                           scale=0.2, block_q=blk,
                                           block_k=blk))
    for h, n in enumerate(lengths):
        want = R.flash_attention_ref(q[h, :n, None], k[h, :n, None],
                                     v[h, :n, None], scale=0.2)[:, 0]
        np.testing.assert_allclose(got[h, :n], np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        past = -(-n // blk) * blk
        assert not np.any(got[h, past:])
