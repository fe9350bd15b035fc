"""Pallas TPU kernel: SF unpack-with-reduction (the CUDA-atomics replacement).

Paper §5.3: GPU unpacks run one CUDA thread per packed entry and need atomics
when leaf/root indices repeat (e.g. SFReduce in MatMultTranspose).  TPU has
no global atomics and hates scattered stores, so the TPU-native design
(DESIGN.md §3.3) is:

  1. at *setup* time, sort the packed-slot order by destination row
     (amortized over every operation on the SF template, like all PetscSF
     index analysis);
  2. at run time, a grid step fetches a bounded window of sorted rows per
     segment and reduces each destination *segment* entirely in VMEM/VREGs,
     emitting one dense row per segment;
  3. the caller scatters the per-segment results to their destination rows
     with a *duplicate-free* scatter (trivially deterministic).

The kernel below implements step 2: a segment reduction over a sorted buffer
with per-segment (start, length) metadata in SMEM.  The sorted buffer stays
in HBM in the lane-dense row view of :func:`repro.kernels.sf_pack.row_view`;
each segment's rows are DMA'd as the aligned window of whole ``t``-row tiles
that covers them (``Lmax`` bounds the window), masked to the segment, and
reduced in a 32-bit accumulator.

Supported ops: sum, max, min, prod (replace is handled by the caller via the
precomputed last-writer trick and never reaches this kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sf_pack import (VMEM_BUDGET, _acc_dtype, _chunked, _lane_width,
                      _round_up, row_view, sublane_rows)
from .tuning import resolve_interpret

__all__ = ["unpack_segments", "segment_reduce_sorted",
           "segment_reduce_blocked", "seg_window_rows", "seg_block_ok"]

_REDUCE = {"sum": jnp.sum, "prod": jnp.prod, "max": jnp.max, "min": jnp.min}


def _identity(op: str, acc):
    if op == "sum":
        return 0
    if op == "prod":
        return 1
    big = jnp.inf if acc == jnp.float32 else jnp.iinfo(jnp.int32).max
    return -big if op == "max" else big


def seg_window_rows(Lmax: int, dtype) -> int:
    """Rows of the whole-tile window that covers any segment of at most
    ``Lmax`` rows, wherever it starts."""
    t = sublane_rows(dtype)
    return ((max(int(Lmax), 1) - 1) // t + 2) * t


def seg_block_ok(segs_per_block: int, Lmax: int, unit, dtype) -> bool:
    """A segment block fits the VMEM budget: one window per segment."""
    return (segs_per_block % sublane_rows(dtype) == 0
            and segs_per_block * seg_window_rows(Lmax, dtype)
            * _lane_width(unit) * np.dtype(dtype).itemsize <= VMEM_BUDGET)


def _make_kernel(op: str, SB: int, t: int, W: int):
    reduce = _REDUCE[op]

    def kernel(meta_ref, buf_hbm, out_ref, win, sem):
        # meta_ref: (2, chunk) SMEM — row 0: segment first row, row 1:
        # length; this step reduces SB of the chunk's segments
        base = pl.program_id(0) * SB

        def dma(j):
            g = meta_ref[0, base + j] // t
            return pltpu.make_async_copy(
                buf_hbm.at[pl.ds(pl.multiple_of(g * t, t), W)], win.at[j], sem)

        jax.lax.fori_loop(0, SB, lambda j, c: (dma(j).start(), c)[1], 0)
        jax.lax.fori_loop(0, SB, lambda j, c: (dma(j).wait(), c)[1], 0)

        acc_dt = _acc_dtype(out_ref.dtype)
        ident = _identity(op, acc_dt)
        Up = out_ref.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (W, Up), 0)
        tile_rows = jax.lax.broadcasted_iota(jnp.int32, (t, Up), 0)

        def tile(q, c):
            acc = jnp.zeros((t, Up), acc_dt)
            for jj in range(t):
                j = q * t + jj
                first = meta_ref[0, base + j]
                off = first - first // t * t
                inseg = (rows >= off) & (rows < off + meta_ref[1, base + j])
                vals = jnp.where(inseg, win[j].astype(acc_dt), ident)
                red = reduce(vals, axis=0, keepdims=True)
                acc = jnp.where(tile_rows == jj, red, acc)
            out_ref[pl.ds(pl.multiple_of(q * t, t), t), :] = \
                acc.astype(out_ref.dtype)
            return c

        jax.lax.fori_loop(0, SB // t, tile, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "Lmax", "segs_per_block",
                                    "op", "interpret"))
def segment_reduce_blocked(buf: jnp.ndarray, seg_start: jnp.ndarray,
                           seg_len: jnp.ndarray, *, num_segments: int,
                           Lmax: int, segs_per_block: int, op: str = "sum",
                           interpret: bool = None) -> jnp.ndarray:
    """Reduce sorted rows into per-segment rows, ``segs_per_block``
    segments per grid step (rounded up to a whole number of tiles).

    buf:       (M, *unit) rows sorted by destination, any unit rank >= 1.
    seg_start: (S,) first row of each segment.
    seg_len:   (S,) segment length (<= Lmax); a zero-length segment emits
               the op's identity.
    Returns (num_segments, *unit).  Which block size wins — or whether the
    XLA segment ops win — is decided by the autotuner in
    :mod:`repro.kernels.tuning`.
    """
    interpret = resolve_interpret(interpret)
    S = int(num_segments)
    unit = tuple(int(d) for d in buf.shape[1:])
    U = int(np.prod(unit)) if unit else 1
    t = sublane_rows(buf.dtype)
    W = seg_window_rows(Lmax, buf.dtype)
    SB = _round_up(max(1, min(int(segs_per_block), S)), t)
    G = -(-S // SB)
    x = row_view(buf, t, extra_rows=W)       # every window stays in bounds
    Up = int(x.shape[1])
    pad = G * SB - S
    meta = jnp.stack([jnp.pad(seg_start.astype(jnp.int32), (0, pad)),
                      jnp.pad(seg_len.astype(jnp.int32), (0, pad))], axis=0)

    def call(chunk):
        return pl.pallas_call(
            _make_kernel(op, SB, t, W),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(chunk.shape[1] // SB,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((SB, Up), lambda s, meta_ref: (s, 0)),
                scratch_shapes=[pltpu.VMEM((SB, W, Up), buf.dtype),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct((chunk.shape[1], Up), buf.dtype),
            interpret=interpret,
        )(chunk, x)

    out = _chunked(call, meta, SB)
    return out[:S, :U].reshape((S,) + unit)


def segment_reduce_sorted(buf: jnp.ndarray, seg_start: jnp.ndarray,
                          seg_len: jnp.ndarray, *, num_segments: int,
                          Lmax: int, op: str = "sum", interpret: bool = None
                          ) -> jnp.ndarray:
    """:func:`segment_reduce_blocked` at the smallest block: one output
    tile of segments per grid step."""
    return segment_reduce_blocked(
        buf, seg_start, seg_len, num_segments=num_segments, Lmax=Lmax,
        segs_per_block=sublane_rows(buf.dtype), op=op, interpret=interpret)


def unpack_segments(target: jnp.ndarray, buf_sorted: jnp.ndarray,
                    seg_start: np.ndarray, seg_len: np.ndarray,
                    seg_dst: np.ndarray, *, op: str = "sum",
                    interpret: bool = None) -> jnp.ndarray:
    """Full unpack: segment-reduce the sorted buffer, then one duplicate-free
    scatter into ``target`` rows ``seg_dst`` with reduction ``op``.

    Setup-time metadata (seg_start/len/dst) comes from the SF plan's sorted
    slot machinery (:mod:`repro.core.plan`).
    """
    S = int(seg_dst.shape[0])
    if S == 0:
        return target
    Lmax = max(int(np.max(seg_len)), 1)
    red = segment_reduce_sorted(buf_sorted, jnp.asarray(seg_start),
                                jnp.asarray(seg_len), num_segments=S,
                                Lmax=Lmax, op=op, interpret=interpret)
    at = target.at[seg_dst]
    method = {"sum": at.add, "prod": at.multiply, "max": at.max,
              "min": at.min}[op]
    return method(red.astype(target.dtype), unique_indices=True)
