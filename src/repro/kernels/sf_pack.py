"""Pallas TPU kernel: SF pack (gather rows into a contiguous send buffer).

Paper §5.2/§5.3: ``rootbuf[i] = rootdata[rootidx[i]]`` executed as a device
kernel.  TPU formulation: the data stays in HBM (``pl.ANY``) and the index
list rides in SMEM; each output row is fetched by a DMA driven by its scalar
index.  The TPU's HBM layout tiles every 2-D array in ``(t, 128)`` tiles
(``t`` = 8 rows for 32-bit dtypes, 16 for 16-bit), and a DMA moves whole
tiles, so a row is fetched as the aligned ``t``-row slab that holds it and
picked out in VMEM.  The kernels therefore work on a *lane-dense row view*:
the payload ``(N, *unit)`` is flattened to ``(N, U)`` and padded to
``(N', U')`` with ``N' % t == 0`` and ``U' % 128 == 0`` (:func:`row_view`).

Unit awareness (paper §3.2: every SF op takes an ``MPI_Datatype unit``): rows
are dof *blocks* ``(*unit)`` of any rank, flattened into the lane dimension.

Variants:
  * ``pack_blocked``  — row-blocked pack: each grid step gathers
                        ``block_rows`` rows (a multiple of ``t``) with one
                        slab DMA per row, all in flight together.  Which
                        block size (or whether the XLA gather wins outright)
                        is decided by the autotuner in
                        :mod:`repro.kernels.tuning`.
  * ``pack``          — the same kernel at the smallest block, one output
                        tile per grid step.
  * ``pack_strided``  — paper §5.2 ¶3 parametric 3D-subdomain pack: row
                        addresses are *computed* from (start, dims, strides);
                        no index array exists anywhere.  Each grid step moves
                        one contiguous ``dx``-row panel, fetched as the
                        aligned window of tiles that covers it (Mosaic
                        needs tile-aligned DMA offsets, ``pl.Element``
                        offsets included) and rotated into place.
  * ``bcast_fused``   — fused pack→unpack for local-only edges (paper §5.2's
                        local/remote split): ``leaf[gl[e]] = root[gr[e]]`` in
                        ONE kernel writing row updates into the aliased leaf
                        buffer, so self-communication never materializes an
                        intermediate packed leaf buffer.

Which shapes a kernel may serve is a rule on the shapes
(:func:`kernel_dtype_ok`, :func:`pack_block_ok`, :func:`strided_ok`), applied
by the candidate sets in :mod:`repro.kernels.ops` — never a caught compile
error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import resolve_interpret

__all__ = ["pack", "pack_blocked", "pack_strided", "bcast_fused",
           "sublane_rows", "kernel_dtype_ok", "pack_block_ok", "strided_ok",
           "row_view", "VMEM_BUDGET"]

LANES = 128
# scratch bytes a kernel may hold in VMEM (the default scoped limit is
# 16 MiB on v5e; the pipeline's double-buffered blocks need the rest)
VMEM_BUDGET = 4 * 2 ** 20
# index words a kernel keeps in SMEM at once (SMEM holds 1 MiB on v5e)
SMEM_INDEX_WORDS = 64 * 1024


def sublane_rows(dtype) -> int:
    """Rows per HBM tile of ``dtype``: 8 for 32-bit, 16 for 16-bit."""
    return 8 * (4 // np.dtype(dtype).itemsize)


def kernel_dtype_ok(dtype) -> bool:
    """The kernels move 32-bit float/int rows and bfloat16 rows."""
    dt = np.dtype(dtype)
    return dt == jnp.bfloat16 or (dt.kind in "fiu" and dt.itemsize == 4)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _lane_width(unit) -> int:
    return _round_up(max(int(np.prod(unit)) if unit else 1, 1), LANES)


def pack_block_ok(block_rows: int, unit, dtype) -> bool:
    """A pack block fits the VMEM budget: one ``t``-row slab per row."""
    t = sublane_rows(dtype)
    return (block_rows % t == 0 and block_rows * t * _lane_width(unit)
            * np.dtype(dtype).itemsize <= VMEM_BUDGET)


def strided_ok(dims, dtype) -> bool:
    """``pack_strided`` output panels are whole tiles — ``dx`` rows a
    multiple of the tile height, or the whole output — of a 32-bit dtype
    (the in-VMEM rotation that aligns a panel moves 32-bit rows only)."""
    dx, dy, dz = (int(d) for d in dims)
    return (np.dtype(dtype).itemsize == 4 and kernel_dtype_ok(dtype)
            and (dx % sublane_rows(dtype) == 0 or dy * dz == 1))


def row_view(x: jnp.ndarray, t: int, extra_rows: int = 0) -> jnp.ndarray:
    """``(N, *unit)`` -> lane-dense ``(N', U')``: rows padded to a multiple
    of ``t`` (plus ``extra_rows``), lanes to a multiple of 128."""
    N = int(x.shape[0])
    U = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
    x2 = x.reshape(N, U)
    Np = _round_up(max(N, 1), t) + extra_rows
    Up = _round_up(U, LANES)
    if (Np, Up) != (N, U):
        x2 = jnp.pad(x2, ((0, Np - N), (0, Up - U)))
    return x2


def _chunked(call, idx: jnp.ndarray, block: int) -> jnp.ndarray:
    """Run ``call`` over slices of the index list small enough for SMEM
    (a whole number of blocks each) and concatenate the row outputs."""
    step = max(SMEM_INDEX_WORDS // block, 1) * block
    n = int(idx.shape[-1])
    parts = [call(idx[..., c:c + step]) for c in range(0, n, step)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _acc_dtype(dtype):
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32


def pick_row(slab: jnp.ndarray, r) -> jnp.ndarray:
    """``slab[r]`` (keepdims) for a traced ``r``: a masked max over the
    slab's rows in the 32-bit accumulator dtype.  Exact for every value,
    -0.0 and NaN included, since the other rows are the max identity."""
    acc = _acc_dtype(slab.dtype)
    w = slab.astype(acc)
    low = -jnp.inf if acc == jnp.float32 else jnp.iinfo(jnp.int32).min
    rows = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    return jnp.max(jnp.where(rows == r, w, low), axis=0, keepdims=True)


def _gather_kernel(B: int, t: int):
    def kernel(idx_ref, data_hbm, out_ref, slabs, sem):
        # idx_ref: (chunk,) SMEM — source rows; this step gathers B of them
        base = pl.program_id(0) * B

        def dma(j):
            g = idx_ref[base + j] // t
            return pltpu.make_async_copy(
                data_hbm.at[pl.ds(pl.multiple_of(g * t, t), t)],
                slabs.at[j], sem)

        jax.lax.fori_loop(0, B, lambda j, c: (dma(j).start(), c)[1], 0)
        jax.lax.fori_loop(0, B, lambda j, c: (dma(j).wait(), c)[1], 0)

        shape = (t, out_ref.shape[1])
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

        def tile(q, c):
            acc = jnp.zeros(shape, _acc_dtype(out_ref.dtype))
            for jj in range(t):
                j = q * t + jj
                row = pick_row(slabs[j], idx_ref[base + j] % t)
                acc = jnp.where(rows == jj, row, acc)
            out_ref[pl.ds(pl.multiple_of(q * t, t), t), :] = \
                acc.astype(out_ref.dtype)
            return c

        jax.lax.fori_loop(0, B // t, tile, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pack_blocked(data: jnp.ndarray, idx: jnp.ndarray, *, block_rows: int,
                 interpret: bool = None) -> jnp.ndarray:
    """Row-blocked gather pack: ``out[i] = data[idx[i]]``, ``block_rows``
    rows per grid step (rounded up to a whole number of tiles).

    ``data`` is ``(N, *unit)`` with any unit rank >= 1.  ``M`` is padded up
    to a block multiple (pad rows gather row 0 and are sliced off), so any
    M works.
    """
    interpret = resolve_interpret(interpret)
    M = int(idx.shape[0])
    unit = tuple(int(d) for d in data.shape[1:])
    U = int(np.prod(unit)) if unit else 1
    t = sublane_rows(data.dtype)
    B = _round_up(max(1, min(int(block_rows), M)), t)
    G = -(-M // B)
    x = row_view(data, t)
    Up = int(x.shape[1])
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, G * B - M))

    def call(chunk):
        return pl.pallas_call(
            _gather_kernel(B, t),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(chunk.shape[0] // B,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((B, Up), lambda i, idx_ref: (i, 0)),
                scratch_shapes=[pltpu.VMEM((B, t, Up), data.dtype),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=jax.ShapeDtypeStruct((chunk.shape[0], Up), data.dtype),
            interpret=interpret,
        )(chunk, x)

    out = _chunked(call, idx_p, B)
    return out[:M, :U].reshape((M,) + unit)


def pack(data: jnp.ndarray, idx: jnp.ndarray, *, interpret: bool = None
         ) -> jnp.ndarray:
    """``out[i] = data[idx[i]]``, one output tile per grid step.
    data: (N, *unit), idx: (M,) -> out: (M, *unit)."""
    return pack_blocked(data, idx, block_rows=sublane_rows(data.dtype),
                        interpret=interpret)


def _fused_bcast_kernel(t_r: int, t_l: int):
    def kernel(idx_ref, root_hbm, leaf_hbm, out_hbm, rslab, lslab, sem):
        # idx_ref: (2, E) SMEM — row 0: root rows, row 1: leaf rows.
        # out_hbm aliases leaf_hbm: each edge reads the leaf slab that holds
        # its destination row, replaces that row and writes the slab back.
        del leaf_hbm
        rows = jax.lax.broadcasted_iota(jnp.int32, lslab.shape, 0)

        def edge(e, c):
            gr, gl = idx_ref[0, e], idx_ref[1, e]
            src = pltpu.make_async_copy(
                root_hbm.at[pl.ds(pl.multiple_of(gr // t_r * t_r, t_r), t_r)],
                rslab, sem)
            dst = out_hbm.at[pl.ds(pl.multiple_of(gl // t_l * t_l, t_l), t_l)]
            cur = pltpu.make_async_copy(dst, lslab, sem)
            src.start()
            cur.start()
            src.wait()
            cur.wait()
            row = pick_row(rslab[...], gr % t_r)
            lslab[...] = jnp.where(rows == gl % t_l,
                                   row.astype(lslab.dtype), lslab[...])
            back = pltpu.make_async_copy(lslab, dst, sem)
            back.start()
            back.wait()
            return c

        jax.lax.fori_loop(0, idx_ref.shape[1], edge, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def bcast_fused(rootdata: jnp.ndarray, leafdata: jnp.ndarray,
                gr: jnp.ndarray, gl: jnp.ndarray, *,
                interpret: bool = None) -> jnp.ndarray:
    """Fused local pack→unpack: returns ``leafdata`` with
    ``out[gl[e]] = rootdata[gr[e]]`` executed as ONE kernel.

    For local-only edges (paper §5.2's local/remote split) the packed
    intermediate buffer of the two-kernel pack→scatter path is pure waste —
    here each edge's row goes straight from the root slab to the leaf slab,
    with both index lists in SMEM.  Leaf rows not named by ``gl`` pass
    through unchanged; ``gl`` must be duplicate-free (every leaf has one
    root), which SF bcasts guarantee.
    """
    interpret = resolve_interpret(interpret)
    Nl = int(leafdata.shape[0])
    unit = tuple(int(d) for d in leafdata.shape[1:])
    U = int(np.prod(unit)) if unit else 1
    t_r, t_l = sublane_rows(rootdata.dtype), sublane_rows(leafdata.dtype)
    root = row_view(rootdata, t_r)
    leaf = row_view(leafdata, t_l)
    idx = jnp.stack([gr.astype(jnp.int32), gl.astype(jnp.int32)], axis=0)
    # edge chunks small enough for SMEM run one after another on the leaf
    step = SMEM_INDEX_WORDS // 2
    for c in range(0, int(idx.shape[1]), step):
        leaf = pl.pallas_call(
            _fused_bcast_kernel(t_r, t_l),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
            scratch_shapes=[pltpu.VMEM((t_r, root.shape[1]), root.dtype),
                            pltpu.VMEM((t_l, leaf.shape[1]), leaf.dtype),
                            pltpu.SemaphoreType.DMA(())],
            input_output_aliases={2: 0},
            interpret=interpret,
        )(idx[:, c:c + step], root, leaf)
    return leaf[:Nl, :U].reshape((Nl,) + unit)


def _strided_kernel(start: int, dims, strides, t: int, W: int):
    dx, dy, _ = dims
    _, sy, sz = strides

    def kernel(data_hbm, out_ref, win, sem):
        first = start + pl.program_id(0) * sy + pl.program_id(1) * sz
        g = first // t
        cp = pltpu.make_async_copy(
            data_hbm.at[pl.ds(pl.multiple_of(g * t, t), W)], win, sem)
        cp.start()
        cp.wait()
        # the panel starts ``first - g*t`` rows into the aligned window:
        # rotate it to the top
        shift = (W - (first - g * t)) % W
        out_ref[...] = pltpu.roll(win[...], shift, 0)[:dx]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("start", "dims", "strides", "interpret"))
def pack_strided(data: jnp.ndarray, *, start: int, dims, strides,
                 interpret: bool = None) -> jnp.ndarray:
    """Pack rows ``start + i*sx + j*sy + k*sz`` for (i,j,k) < dims, sx == 1.

    ``data`` is ``(N, *unit)`` with any unit rank; each grid step moves one
    contiguous ``(dx, U')`` row panel — face/pencil subdomains of a regular
    grid move as whole panels, the same win the paper's multi-strided packs
    get from fewer indirections.  Panel starts are not multiples of the
    tile height, so each panel is fetched as the aligned window of whole
    tiles that covers it and rotated into place in VMEM.
    """
    interpret = resolve_interpret(interpret)
    dx, dy, dz = (int(d) for d in dims)
    sx, sy, sz = (int(s) for s in strides)
    if sx != 1:
        raise ValueError("pack_strided requires unit inner stride")
    unit = tuple(int(d) for d in data.shape[1:])
    U = int(np.prod(unit)) if unit else 1
    t = sublane_rows(data.dtype)
    W = ((dx - 1) // t + 2) * t
    x = row_view(data, t, extra_rows=W)      # every window stays in bounds
    Up = int(x.shape[1])
    out = pl.pallas_call(
        _strided_kernel(int(start), (dx, dy, dz), (sx, sy, sz), t, W),
        grid=(dy, dz),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((dx, Up), lambda j, k: (j + k * dy, 0)),
        out_shape=jax.ShapeDtypeStruct((dx * dy * dz, Up), data.dtype),
        scratch_shapes=[pltpu.VMEM((W, Up), data.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(x)
    return out[:, :U].reshape((dx * dy * dz,) + unit)
