"""Pallas TPU kernel: flash attention forward (GQA, causal, sliding window).

The LM serving path's compute hot-spot.  Classic online-softmax tiling
adapted to the TPU memory hierarchy: Q/K/V stream HBM→VMEM in
(block_q × head_dim) / (block_k × head_dim) panels sized for the MXU
(block sizes are multiples of 128 lanes); the running max/denominator and the
output accumulator live in VMEM scratch across the innermost KV-block grid
dimension (the TPU grid is sequential, which replaces the CUDA version's
per-CTA shared-memory state).

Positions are end-aligned (q row i has absolute position Skv - Sq + i) so the
same kernel serves full self-attention (Sq == Skv), chunked prefill and
single-step decode with a prefix KV cache.  GQA is handled by pointing the
K/V block index map at head h // (H // Hkv).

Forward only: training uses the differentiable chunked-jnp reference
(`repro.kernels.ref.flash_attention_ref` / models.attention); the kernel is
wired into the serving path where backward passes never run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import resolve_interpret

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _make_kernel(scale: float, causal: bool, window, Sq: int, Skv: int,
                 block_q: int, block_k: int, nk: int):
    # Sq/Skv are the REAL (unpadded) lengths; padded q rows produce garbage
    # that the wrapper slices off, padded k rows are masked via kpos < Skv.

    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        jk = pl.program_id(2)
        iq = pl.program_id(1)

        @pl.when(jk == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q = q_ref[...].astype(jnp.float32)           # (Bq, D)
        k = k_ref[...].astype(jnp.float32)           # (Bk, D)
        v = v_ref[...].astype(jnp.float32)           # (Bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        qpos = (iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)) + (Skv - Sq)
        kpos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < Skv                            # drop padded k rows
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                          # (Bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (Bq, Bk)
        corr = jnp.exp(m_prev - m_new)               # (Bq, 1)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

        @pl.when(jk == nk - 1)
        def _finish():
            l = l_scr[...]
            safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "block_q",
                              "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = None
                    ) -> jnp.ndarray:
    """q: (Sq, H, D); k, v: (Skv, Hkv, D) with Hkv | H.  Returns (Sq, H, D)."""
    interpret = resolve_interpret(interpret)
    Sq, H, D = (int(x) for x in q.shape)
    Skv, Hkv, _ = (int(x) for x in k.shape)
    rep = H // Hkv
    scale_v = float(scale) if scale is not None else float(1.0 / np.sqrt(D))

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Skv, 8))
    Sq_p = ((Sq + bq - 1) // bq) * bq
    Skv_p = ((Skv + bk - 1) // bk) * bk
    # Pad both at the END; positions are computed against the REAL lengths,
    # padded k rows are masked (kpos < Skv) and padded q rows sliced off.
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, Sq_p - Sq), (0, 0), (0, 0)))
    if Skv_p != Skv:
        k = jnp.pad(k, ((0, Skv_p - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, Skv_p - Skv), (0, 0), (0, 0)))

    # q/k/v laid out (S, H, D); grid (H, Sq/bq, Skv/bk)
    grid = (H, Sq_p // bq, Skv_p // bk)
    kernel = _make_kernel(scale_v, causal, window, Sq, Skv, bq, bk,
                          Skv_p // bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, None, D), lambda h, i, j: (i, h, 0)),
            pl.BlockSpec((bk, None, D), lambda h, i, j: (j, h // rep, 0)),
            pl.BlockSpec((bk, None, D), lambda h, i, j: (j, h // rep, 0)),
        ],
        out_specs=pl.BlockSpec((bq, None, D), lambda h, i, j: (i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((Sq_p, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:Sq]
