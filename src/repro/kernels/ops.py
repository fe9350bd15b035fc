"""Jitted public wrappers for the Pallas kernels.

The SF hot-path entry points (``pack_rows``, ``segment_reduce_rows``,
``local_bcast_rows``) are *autotuned*: each has several candidate lowerings
(pure-XLA gather/segment ops, position-wise combines with one row gather
for short segments, row-blocked DMA kernels at several block sizes, the
fused local-exchange kernel) and :mod:`repro.kernels.tuning`
sweeps them once per problem signature, memoizing the winner so repeated
exchanges never re-sweep or re-trace.  A kernel is a candidate only where
the shape rules of :mod:`repro.kernels.sf_pack` /
:mod:`repro.kernels.sf_unpack` say it compiles (dtype, tile alignment,
VMEM budget), so every candidate offered on a TPU compiles under Mosaic.

Interpret-vs-compiled is decided in exactly one place —
``tuning.resolve_interpret`` (env override ``REPRO_SF_INTERPRET``, then
platform detection) — shared by these wrappers, the pallas backend, and the
DistSF general path.  Each wrapper has a pure-jnp oracle in
:mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref, tuning
from .flash_attention import flash_attention as _flash
from .sf_pack import (bcast_fused as _bcast_fused, kernel_dtype_ok,
                      pack as _pack, pack_block_ok,
                      pack_blocked as _pack_blocked,
                      pack_strided as _pack_strided, strided_ok, sublane_rows)
from .sf_unpack import (seg_block_ok, segment_reduce_blocked,
                        segment_reduce_gather, unpack_segments)
from .spmv_ell import spmv_ell as _spmv_ell
from .tuning import resolve_interpret

__all__ = [
    "default_interpret", "sf_pack", "sf_pack_strided", "sf_unpack",
    "pack_rows", "segment_reduce_rows", "local_bcast_rows",
    "flash_attention", "spmv_ell", "ref", "tuning",
]


def default_interpret() -> bool:
    """Back-compat alias for :func:`repro.kernels.tuning.resolve_interpret`
    with no explicit override."""
    return resolve_interpret()


def _platform() -> str:
    return jax.default_backend()


# Per-signature jitted dispatch closures: once the autotuner has picked a
# winner, repeat calls must cost one jit dispatch — the eager asarray /
# reshape plumbing around the winner otherwise dominates small exchanges.
# Each is named for its trace (``jit(sf_pack_rows)``, ...) and opens its SF
# device scope (``sf.pack``, ``sf.combine``, ``sf.unpack``).
_DISPATCH: dict = {}
tuning.register_cache(_DISPATCH)


def _sflog():
    """:mod:`repro.core.sflog`, imported at trace time, since
    ``repro.core`` imports this module while it initialises."""
    from ..core import sflog
    return sflog


def _scope(name: str):
    return _sflog().scope(name)


# --------------------------------------------------------------------------
# pack: tuned row gather
# --------------------------------------------------------------------------
def _blocks(n: int, sizes, t: int, single_step_max: int) -> list:
    """Kernel block sizes for ``n`` rows: the usual sizes capped at ``n``,
    plus one grid step for the whole problem when it is small, each
    rounded up to whole tiles of ``t`` rows."""
    cands = {min(n, b) for b in sizes}
    if n <= single_step_max:
        cands.add(n)
    return sorted({-(-max(b, 1) // t) * t for b in cands})


def _pack_candidates(M: int, unit, dtype, interpret: bool) -> dict:
    impls = {"xla": lambda d, i: jnp.take(d, i, axis=0)}
    if not kernel_dtype_ok(dtype):
        return impls
    for B in _blocks(M, (8, 32, 128, 512), sublane_rows(dtype), 2048):
        if pack_block_ok(B, unit, dtype):
            impls[f"block:{B}"] = (
                lambda d, i, B=B: _pack_blocked(d, i, block_rows=B,
                                                interpret=interpret))
    return impls


def _kernel_default(impls: dict) -> str:
    """Unswept default: the largest kernel block of at most 128 rows (the
    smallest kept block if none is), else the XLA lowering."""
    sizes = sorted(int(k[len("block:"):]) for k in impls
                   if k.startswith("block:"))
    if not sizes:
        return "xla"
    return f"block:{max([b for b in sizes if b <= 128] or sizes[:1])}"


def pack_rows(data, idx, *, interpret=None, key=None):
    """``data[idx]`` row gather through the tuned pack lowering for
    arbitrary unit shapes: rows are ``(*unit)`` dof blocks of any rank and
    the kernels block over the full unit extent — no flattening.  Scalar
    rows (1-D data) ride as the degenerate one-lane unit ``(1,)``.
    Degenerate shapes (no rows, no index, zero-width unit) fall back to
    ``jnp.take``.  Shared by the pallas backend and the DistSF general path.

    ``key`` (e.g. a plan's ``comm_signature()``) scopes the autotune cache
    per communication pattern on top of the shape signature.
    """
    # the sub-µs signature fast path: attribute lookups only, no jnp calls
    dshape = data.shape if hasattr(data, "shape") else np.shape(data)
    idx_shape = idx.shape if hasattr(idx, "shape") else np.shape(idx)
    dts = np.dtype(getattr(data, "dtype", type(data)))
    interpret = resolve_interpret(interpret)
    sig = ("pack", tuple(dshape), tuple(idx_shape), dts, interpret,
           _platform(), key)
    fn = _DISPATCH.get(sig)
    if fn is None:
        fn = _pack_dispatch(sig, tuple(dshape), tuple(idx_shape), dts,
                            interpret)
        _DISPATCH[sig] = fn
    return fn(data, idx)


def _pack_dispatch(sig, dshape, idx_shape, dts, interpret):
    """Build (once per signature) the jitted dispatcher around the winning
    pack lowering — repeat calls cost one jit dispatch."""
    unit = dshape[1:]
    usize = int(np.prod(unit)) if unit else 1
    n_idx = int(np.prod(idx_shape)) if idx_shape else 1
    if usize == 0 or n_idx == 0 or dshape[0] == 0:
        def sf_pack_rows(d, i):
            with _scope("sf.pack"):
                return jnp.take(d, i, axis=0)
        return jax.jit(sf_pack_rows)
    scalar_rows = len(dshape) == 1
    kunit = unit if not scalar_rows else (1,)
    N, M = int(dshape[0]), n_idx
    impls = _pack_candidates(M, kunit, dts, interpret)
    winner = tuning.autotune(
        "pack", (N, M, kunit, dts, interpret, _platform(), sig[-1]), impls,
        lambda: (jnp.zeros((N,) + kunit, dts),
                 jnp.arange(M, dtype=jnp.int32) % N),
        default=_kernel_default(impls), work=M * usize)
    impl = impls[winner]

    def sf_pack_rows(d, i):
        with _scope("sf.pack"):
            out = impl(d[:, None] if scalar_rows else d, i.reshape(-1))
            if scalar_rows:
                out = out[:, 0]
            return out.reshape(idx_shape + unit)

    return jax.jit(sf_pack_rows)


# --------------------------------------------------------------------------
# segment reduce: tuned sorted-buffer reduction
# --------------------------------------------------------------------------
# Longest segment for which the sweep also times the ``gather`` lowering.
# On a TPU v5e, for 2.1M segments of 4-f32 rows, the kernels' one window
# DMA per segment costs ~120 ms whatever ``Lmax``; the gather lowering
# costs 9.3 ms at ``Lmax`` 1, 9.6 ms at 4 and 10.1 ms at 8 (13.7 ms at 32),
# and 9.6 ms on the DMDA star-stencil plan (segments of 1-4 rows) (PERF.md
# §6 has the chip measurements).  8 covers the star (4) and box (8)
# stencils; longer segments, such as an allreduce over more ranks, keep
# the kernels until measured on their own shapes.
SEG_GATHER_MAX_LEN = 8


def _seg_candidates(S: int, Lmax: int, op: str, unit, dtype,
                    interpret: bool, have_ids: bool) -> dict:
    impls = {}
    if have_ids:
        impls["xla"] = lambda v, f, l, ids: ref.unpack_segment_ref(
            v, ids, num_segments=S, op=op)
    if have_ids and Lmax <= SEG_GATHER_MAX_LEN:
        impls["gather"] = lambda v, f, l, ids: segment_reduce_gather(
            v, f, l, ids, Lmax=Lmax, op=op)
    if not kernel_dtype_ok(dtype):
        return impls
    for SB in _blocks(S, (8, 32, 128), sublane_rows(dtype), 1024):
        if seg_block_ok(SB, Lmax, unit, dtype):
            impls[f"block:{SB}"] = (
                lambda v, f, l, ids, SB=SB: segment_reduce_blocked(
                    v, f, l, num_segments=S, Lmax=Lmax, segs_per_block=SB,
                    op=op, interpret=interpret))
    return impls


def segment_reduce_rows(sorted_vals, seg_first, seg_len, *, num_segments,
                        Lmax, op="sum", interpret=None, seg_of_slot=None,
                        key=None):
    """Tuned segment-reduce over a sorted row buffer of arbitrary unit shape
    (the panels block over the full unit extent — no flattening); the kernel
    candidates pad ``Lmax`` rows so the last panel load stays in bounds (the
    pad content is masked out by the per-segment length).  Shared by the
    pallas backend and the DistSF general path.

    ``seg_of_slot`` (per-sorted-slot segment ids, when the caller has them)
    additionally enables the pure-XLA segment-op candidate; ``key`` scopes
    the autotune cache per communication pattern.
    """
    interpret = resolve_interpret(interpret)
    vshape = sorted_vals.shape if hasattr(sorted_vals, "shape") \
        else np.shape(sorted_vals)
    dts = np.dtype(getattr(sorted_vals, "dtype", type(sorted_vals)))
    have_ids = seg_of_slot is not None
    sig = ("segred", tuple(vshape), dts, int(num_segments), int(Lmax), op,
           have_ids, interpret, _platform(), key)
    fn = _DISPATCH.get(sig)
    if fn is None:
        fn = _segred_dispatch(sig, tuple(vshape), dts, int(num_segments),
                              int(Lmax), op, have_ids, interpret)
        _DISPATCH[sig] = fn
    return fn(sorted_vals, seg_first, seg_len, seg_of_slot)


def _segred_dispatch(sig, vshape, dts, S, Lmax, op, have_ids, interpret):
    """Build (once per signature) the jitted dispatcher around the winning
    segment-reduce lowering; each built program counts
    ``sf.combine.<lowering>`` once."""
    scalar_rows = len(vshape) == 1
    kunit = vshape[1:] if not scalar_rows else (1,)
    M = int(vshape[0])
    usize = int(np.prod(kunit)) if kunit else 1
    impls = _seg_candidates(S, Lmax, op, kunit, dts, interpret, have_ids)
    if not impls:
        raise ValueError(f"no segment-reduce lowering serves {dts} rows "
                         "without per-slot segment ids")

    def _synth_args():
        base, rem = divmod(M, max(S, 1))
        lens = np.minimum(np.full(S, base, np.int64)
                          + (np.arange(S) < rem), Lmax)
        first = np.concatenate([[0], np.cumsum(lens)[:-1]])
        ids = np.repeat(np.arange(S), lens)
        ids = np.pad(ids, (0, M - ids.size), constant_values=max(S - 1, 0))
        return (jnp.zeros((M,) + kunit, dts),
                jnp.asarray(first, jnp.int32), jnp.asarray(lens, jnp.int32),
                jnp.asarray(ids, jnp.int32))

    winner = tuning.autotune(
        "segred", (M, S, Lmax, kunit, dts, op, interpret, have_ids,
                   _platform(), sig[-1]),
        impls, _synth_args, default=_kernel_default(impls),
        work=M * usize)
    impl = impls[winner]

    def sf_segment_reduce(v, f, l, ids):
        _sflog().counter(f"sf.combine.{winner}").add()
        with _scope("sf.combine"):
            out = impl(v[:, None] if scalar_rows else v, f, l, ids)
            return out[:, 0] if scalar_rows else out

    return jax.jit(sf_segment_reduce)


# --------------------------------------------------------------------------
# fused local exchange: tuned leaf[gl] = root[gr]
# --------------------------------------------------------------------------
def _local_candidates(rdtype, ldtype, interpret: bool) -> dict:
    def _xla(root, leaf, gr, gl):
        return leaf.at[gl].set(jnp.take(root, gr, axis=0).astype(leaf.dtype),
                               unique_indices=True)

    impls = {"xla": _xla}
    if kernel_dtype_ok(rdtype) and kernel_dtype_ok(ldtype):
        impls["fused"] = lambda root, leaf, gr, gl: _bcast_fused(
            root, leaf, gr, gl, interpret=interpret)
    return impls


def local_bcast_rows(rootdata, leafdata, gr, gl, *, interpret=None,
                     key=None):
    """Local-only bcast ``leaf[gl[e]] = root[gr[e]]`` through the tuned
    fused pack→unpack lowering — self-communication never materializes an
    intermediate packed buffer (paper §5.2 local/remote split).  ``gl`` must
    be duplicate-free (each leaf has exactly one root).  Scalar rows ride as
    the one-lane unit; degenerate shapes fall back to the jnp scatter."""
    rshape = rootdata.shape if hasattr(rootdata, "shape") \
        else np.shape(rootdata)
    lshape = leafdata.shape if hasattr(leafdata, "shape") \
        else np.shape(leafdata)
    E = int(np.size(gr))
    if E == 0:
        return jnp.asarray(leafdata)
    interpret = resolve_interpret(interpret)
    rdts = np.dtype(getattr(rootdata, "dtype", type(rootdata)))
    ldts = np.dtype(getattr(leafdata, "dtype", type(leafdata)))
    sig = ("localbcast", tuple(rshape), tuple(lshape), rdts, ldts, E,
           interpret, _platform(), key)
    fn = _DISPATCH.get(sig)
    if fn is None:
        fn = _local_dispatch(sig, tuple(rshape), tuple(lshape), rdts, ldts,
                             E, interpret)
        _DISPATCH[sig] = fn
    return fn(rootdata, leafdata, gr, gl)


def _local_dispatch(sig, rshape, lshape, rdts, ldts, E, interpret):
    """Build (once per signature) the jitted dispatcher around the winning
    fused local-exchange lowering."""
    unit = lshape[1:]
    usize = int(np.prod(unit)) if unit else 1
    scalar_rows = len(lshape) == 1

    def sf_local_bcast(root, leaf, gr, gl):
        with _scope("sf.unpack"):
            return leaf.at[gl.reshape(-1)].set(
                jnp.take(root, gr.reshape(-1), axis=0).astype(leaf.dtype),
                unique_indices=True)

    if usize == 0 or rshape[0] == 0 or lshape[0] == 0:
        return jax.jit(sf_local_bcast)
    kunit = unit if not scalar_rows else (1,)
    Nr, Nl = int(rshape[0]), int(lshape[0])
    impls = _local_candidates(rdts, ldts, interpret)
    winner = tuning.autotune(
        "localbcast", (Nr, Nl, E, kunit, rdts, ldts, interpret, _platform(),
                       sig[-1]),
        impls,
        lambda: (jnp.zeros((Nr,) + kunit, rdts),
                 jnp.zeros((Nl,) + kunit, ldts),
                 jnp.arange(E, dtype=jnp.int32) % Nr,
                 jnp.arange(E, dtype=jnp.int32) % Nl),
        default="fused" if "fused" in impls else "xla", work=E * usize)
    impl = impls[winner]

    def sf_local_bcast(root, leaf, gr, gl):
        # the fused pack -> scatter writes the destination: an unpack
        with _scope("sf.unpack"):
            if scalar_rows:
                root, leaf = root[:, None], leaf[:, None]
            out = impl(root, leaf, gr.reshape(-1), gl.reshape(-1))
            return out[:, 0] if scalar_rows else out

    return jax.jit(sf_local_bcast)


# --------------------------------------------------------------------------
# direct (untuned) kernel access
# --------------------------------------------------------------------------
def sf_pack(data, idx, *, interpret=None):
    interpret = resolve_interpret(interpret)
    return _pack(data, jnp.asarray(idx), interpret=interpret)


def sf_pack_strided(data, *, start, dims, strides, interpret=None):
    interpret = resolve_interpret(interpret)
    return _pack_strided(data, start=int(start), dims=tuple(int(d) for d in dims),
                         strides=tuple(int(s) for s in strides),
                         interpret=interpret)


def sf_unpack(target, buf_sorted, seg_start, seg_len, seg_dst, *, op="sum",
              interpret=None):
    interpret = resolve_interpret(interpret)
    return unpack_segments(target, buf_sorted, np.asarray(seg_start),
                           np.asarray(seg_len), np.asarray(seg_dst), op=op,
                           interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    interpret = resolve_interpret(interpret)
    return _flash(q, k, v, causal=causal, window=window, scale=scale,
                  block_q=block_q, block_k=block_k, interpret=interpret)


def spmv_ell(data, cols, x, *, block_rows=256, interpret=None):
    interpret = resolve_interpret(interpret)
    return _spmv_ell(data, cols, x, block_rows=block_rows, interpret=interpret)
