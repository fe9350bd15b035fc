"""Pallas TPU kernel: local sparse matrix-vector product (ELL format).

The local-compute half of the paper's §4.1 SpMV use case (y = A x_local while
the SF bcast is in flight).  CSR with row-pointer indirection is hostile to
the VPU's regular lanes, so the TPU adaptation stores the local blocks in
ELLPACK: every row padded to K nonzeros, column indices pointing at a
trailing zero entry of x for padding.  Each grid step processes a
(block_rows × K) panel: values and column indices stream through VMEM, the
(gathered) x stays fully VMEM-resident (local vectors in the CG/SpMV use
case are per-device shards — well within the ~16 MB of v5e VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import resolve_interpret

__all__ = ["spmv_ell"]


def _spmv_kernel(data_ref, cols_ref, x_ref, y_ref):
    d = data_ref[...]                      # (Bn, K)
    c = cols_ref[...]                      # (Bn, K) int32
    x = x_ref[...]                         # (Nx, 1) resident
    g = jnp.take(x[:, 0], c, axis=0)       # VMEM gather
    y_ref[...] = jnp.sum(d * g, axis=1, keepdims=True).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def spmv_ell(data: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray, *,
             block_rows: int = 256, interpret: bool = None) -> jnp.ndarray:
    """y[i] = Σ_k data[i,k] * x[cols[i,k]].

    data/cols: (N, K); x: (Nx,) — the caller appends one trailing zero and
    points padding columns at it.  Returns (N,).
    """
    interpret = resolve_interpret(interpret)
    N, K = (int(s) for s in data.shape)
    Bn = min(block_rows, N)
    N_p = ((N + Bn - 1) // Bn) * Bn
    if N_p != N:
        data = jnp.pad(data, ((0, N_p - N), (0, 0)))
        cols = jnp.pad(cols, ((0, N_p - N), (0, 0)))
    x2 = x[:, None]
    Nx = int(x2.shape[0])
    out = pl.pallas_call(
        _spmv_kernel,
        grid=(N_p // Bn,),
        in_specs=[
            pl.BlockSpec((Bn, K), lambda i: (i, 0)),
            pl.BlockSpec((Bn, K), lambda i: (i, 0)),
            pl.BlockSpec((Nx, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N_p, 1), data.dtype),
        interpret=interpret,
    )(data, cols.astype(jnp.int32), x2)
    return out[:N, 0]
