"""Pallas TPU kernel: flash attention forward (GQA, causal, sliding window).

The LM serving path's compute hot-spot.  Classic online-softmax tiling
adapted to the TPU memory hierarchy: Q/K/V stream HBM→VMEM in
(block_q × head_dim) / (block_k × head_dim) panels sized for the MXU; the
running max/denominator and the output accumulator live in VMEM scratch
across the innermost KV-block grid dimension (the TPU grid is sequential,
which replaces the CUDA version's per-CTA shared-memory state).

The kernel works head-major, (heads, S, D), so each block's last two dims
are (rows, head_dim) as Mosaic tiles them.  Values may have another head
dim than queries and keys (latent attention: 192 against 128).  Products
run in the inputs' dtype with float32 accumulation (bf16 inputs: the
probabilities are rounded to bf16 for the value product, as flash
attention does).  Under a causal mask the KV blocks above the diagonal are
skipped: their index map repeats the last needed block (no copy) and the
body does nothing for them.  ``lengths`` (one per head row, a scalar
prefetch) marks how many KV rows are real: blocks past it are skipped the
same way, and so are query blocks that lie wholly past it (a bucketed
prompt's pad tail; their output rows are zero).

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

__all__ = ["flash_attention", "flash_attention_heads"]

_NEG_INF = -1e30


def _last_block(i, n, causal: bool, Sq: int, Skv: int, block_q: int,
                block_k: int, nk: int):
    """The last KV block q block ``i`` attends to, of a head row with ``n``
    real KV rows (-1: none)."""
    last = (n - 1) // block_k
    if causal:
        last = jnp.minimum(
            last, (i * block_q + block_q - 1 + (Skv - Sq)) // block_k)
    return jnp.minimum(last, nk - 1)


def _make_kernel(scale: float, causal: bool, window, Sq: int, Skv: int,
                 block_q: int, block_k: int, nk: int):
    # Sq/Skv are the REAL (unpadded) lengths; padded q rows produce garbage
    # that the wrapper slices off, padded k rows are masked via kpos < Skv.

    def kernel(n_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        jk = pl.program_id(2)
        iq = pl.program_id(1)
        n = n_ref[pl.program_id(0)]

        @pl.when(jk == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # a q block wholly past the real rows (end-aligned positions) and
        # the KV blocks past its last needed one do nothing
        @pl.when((iq * block_q + (Skv - Sq) < n)
                 & (jk <= _last_block(iq, n, causal, Sq, Skv, block_q,
                                      block_k, nk)))
        def _step():
            q = q_ref[...]                               # (Bq, Dq)
            k = k_ref[...]                               # (Bk, Dq)
            v = v_ref[...]                               # (Bk, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ) * scale
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
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
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
def flash_attention_heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          lengths: jnp.ndarray | None = None, *,
                          causal: bool = True, window: int | None = None,
                          scale: float | None = None, block_q: int = 512,
                          block_k: int = 512, interpret: bool = None
                          ) -> jnp.ndarray:
    """Head-major: q (H, Sq, Dq); k (Hkv, Skv, Dq); v (Hkv, Skv, Dv) with
    Hkv | H; ``lengths`` (H,): real KV rows of each head row (default
    Skv).  Returns (H, Sq, Dv)."""
    interpret = resolve_interpret(interpret)
    H, Sq, D = (int(x) for x in q.shape)
    Hkv, Skv, _ = (int(x) for x in k.shape)
    Dv = int(v.shape[-1])
    rep = H // Hkv
    scale_v = float(scale) if scale is not None else float(1.0 / np.sqrt(D))

    bq = min(block_q, -(-max(Sq, 8) // 8) * 8)
    bk = min(block_k, -(-max(Skv, 8) // 8) * 8)
    Sq_p = ((Sq + bq - 1) // bq) * bq
    Skv_p = ((Skv + bk - 1) // bk) * bk
    # Pad both at the END; positions are computed against the REAL lengths,
    # padded k rows are masked (kpos < Skv) and padded q rows sliced off.
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    if Skv_p != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0)))

    nk = Skv_p // bk
    grid = (H, Sq_p // bq, nk)
    kernel = _make_kernel(scale_v, causal, window, Sq, Skv, bq, bk, nk)
    if lengths is None:
        lengths = jnp.full((H,), Skv, jnp.int32)

    def kv_map(h, i, j, n_ref):
        # blocks past the last one q block i needs repeat it: no new copy
        last = _last_block(i, n_ref[h], causal, Sq, Skv, bq, bk, nk)
        return (h // rep, jnp.maximum(jnp.minimum(j, last), 0), 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda h, i, j, n: (h, i, 0)),
                pl.BlockSpec((None, bk, D), kv_map),
                pl.BlockSpec((None, bk, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((None, bq, Dv),
                                   lambda h, i, j, n: (h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((H, Sq_p, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), q, k, v)
    return out[:, :Sq]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = None
                    ) -> jnp.ndarray:
    """q: (Sq, H, D); k, v: (Skv, Hkv, D) with Hkv | H.  Returns (Sq, H, D)
    in q's dtype (``flash_attention_heads`` on the head-major transposes,
    with float32 products whatever the inputs' dtype)."""
    def heads(a):
        return a.astype(jnp.float32).transpose(1, 0, 2)
    out = flash_attention_heads(
        heads(q), heads(k), heads(v), causal=causal, window=window,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return out.transpose(1, 0, 2).astype(q.dtype)
