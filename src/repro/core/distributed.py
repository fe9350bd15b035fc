"""Distributed SF execution: shard_map lowering to jax.lax collectives.

This is the TPU-native replacement for the paper's MPI / NVSHMEM backends
(DESIGN.md §3).  A ``DistSF`` binds one StarForest template to a mesh axis;
its methods are pure functions designed to be called *inside*
``jax.shard_map`` with per-rank shards:

    root shard: (root_pad, *unit)   leaf shard: (leaf_pad, *unit)

(both padded uniformly across ranks, with one trailing garbage row — see
:mod:`repro.core.plan`).

Lowering selection (the paper's §5.2 pattern optimization as collective
choice):

  local_only  ->  on-device scatter, no collective
  allgather   ->  lax.all_gather (bcast) / lax.psum_scatter (sum-reduce)
  permute     ->  lax.ppermute
  general     ->  pack -> lax.all_to_all -> unpack (sort-segment reduction)

The begin/end split mirrors PetscSFBcastBegin/End: ``*_begin`` issues the
pack+collective, ``*_end`` unpacks.  Compute placed between the two is
independent of the in-flight payload, which is exactly what XLA's
latency-hiding scheduler needs to overlap communication (the NVSHMEM
stream-async insight, transferred).

``sync_mode=True`` reproduces the *blocking-MPI* behaviour of paper Fig 5(R)
for benchmarking: an ``optimization_barrier`` is threaded between the
collective and subsequent compute so no overlap is possible.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .graph import StarForest
from .mpiops import Op, get_op
from .plan import PaddedPlan, build_padded_plan
from .unit import check_plan_unit
from . import patterns as pat
from ..kernels import ops as kops

__all__ = ["DistSF", "DistPending", "pad_ragged", "unpad_ragged"]


def _smap(f, mesh, in_specs, out_specs):
    """shard_map with varying-manual-axes checking off: the Pallas calls
    inside the mapped function declare no ``vma`` on their outputs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------------
# ragged <-> padded-stacked helpers (host side, for tests and drivers)
# --------------------------------------------------------------------------
def pad_ragged(arrays: Sequence[np.ndarray], pad_rows: int) -> np.ndarray:
    """Stack per-rank arrays (n_r, *unit) into (R, pad_rows, *unit)."""
    R = len(arrays)
    unit = arrays[0].shape[1:] if arrays else ()
    out = np.zeros((R, pad_rows) + unit, dtype=np.asarray(arrays[0]).dtype)
    for r, a in enumerate(arrays):
        out[r, : a.shape[0]] = a
    return out


def unpad_ragged(stacked: np.ndarray, sizes: Sequence[int]) -> list:
    return [np.asarray(stacked[r, : n]) for r, n in enumerate(sizes)]


@dataclasses.dataclass
class DistPending:
    kind: str
    buf: jnp.ndarray          # received remote buffer (R, P, *unit) or similar
    self_vals: jnp.ndarray    # local (self-edge) values
    op: Op


def _take_row(const: np.ndarray, me) -> jnp.ndarray:
    """Select this rank's row of a stacked plan constant inside shard_map."""
    return jnp.take(jnp.asarray(const), me, axis=0)


class DistSF:
    """StarForest bound to a mesh axis, exposing shard_map-internal ops."""

    def __init__(self, sf: StarForest, axis_name: str = "sf",
                 plan: Optional[PaddedPlan] = None, lowering: str = "auto",
                 sync_mode: bool = False, use_kernels: Optional[bool] = None,
                 unit=None):
        sf.setup()
        self.sf = sf
        self.axis = axis_name
        if plan is not None:
            check_plan_unit(plan, unit)
            self.plan = plan
        else:
            self.plan = build_padded_plan(sf, unit=unit)
        kind = self.plan.pattern.kind
        if lowering == "auto":
            self.lowering = kind
        else:
            allowed = {pat.GENERAL, kind, pat.LOCAL_ONLY if kind == pat.EMPTY else kind}
            if lowering not in (pat.GENERAL, kind):
                raise ValueError(
                    f"requested lowering {lowering!r} but SF pattern is {kind!r}")
            self.lowering = lowering
        self.sync_mode = sync_mode
        # Pallas pack/unpack kernels on the general path (paper §5.3); they
        # compile to Mosaic on TPU and interpret elsewhere (slower there,
        # but kept on by default so one code path is exercised everywhere —
        # pass use_kernels=False for the plain jnp gather/segment path).
        self.use_kernels = True if use_kernels is None else bool(use_kernels)

    # ------------------------------------------------------------ plumbing
    @property
    def nranks(self) -> int:
        return self.plan.nranks

    @property
    def unit(self):
        """The plan's payload unit spec (paper §3.2 ``MPI_Datatype``)."""
        return self.plan.unit

    def _me(self):
        return lax.axis_index(self.axis)

    def _apply(self, target, idx, vals, op: Op):
        """Padded scatter (garbage row absorbs padding; duplicates only
        there, so plain at[].op is deterministic for the real rows)."""
        return getattr(target.at[idx], op.at_update)(vals.astype(target.dtype))

    def _pack_rows(self, data: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        """Gather ``data[idx]`` rows for the general path via the sf_pack
        Pallas kernel (paper §5.3), or ``jnp.take`` when kernels are off."""
        if not self.use_kernels:
            return jnp.take(data, idx, axis=0)
        return kops.pack_rows(data, idx, key=self.plan.comm_signature())

    def _segment_reduce_kernel(self, sortedv: jnp.ndarray, me,
                               op: Op) -> jnp.ndarray:
        """Segment-reduce the sorted slot buffer with the sf_unpack kernel
        (the CUDA-atomics replacement, DESIGN.md §3.3)."""
        p = self.plan
        return kops.segment_reduce_rows(
            sortedv, _take_row(p.red_seg_first, me),
            _take_row(p.red_seg_len, me), num_segments=p.red_nslots,
            Lmax=p.red_Lmax, op=op.name,
            seg_of_slot=_take_row(p.red_seg_id, me), key=p.comm_signature())

    def _barrier(self, *xs):
        if len(xs) == 1:
            return lax.optimization_barrier(xs[0])
        return lax.optimization_barrier(xs)

    # -------------------------------------------------------------- bcast
    def bcast_begin(self, root_shard: jnp.ndarray, op="replace") -> DistPending:
        op = get_op(op)
        p = self.plan
        p.unit.check(root_shard, "root shard")
        me = self._me()
        self_vals = jnp.take(root_shard, _take_row(p.self_root_idx, me), axis=0)
        if self.lowering == pat.LOCAL_ONLY or self.lowering == pat.EMPTY:
            buf = jnp.zeros((p.nranks, 0) + root_shard.shape[1:],
                            root_shard.dtype)
            return DistPending("bcast", buf, self_vals, op)
        if self.lowering == pat.ALLGATHER:
            buf = lax.all_gather(root_shard, self.axis)  # (R, root_pad, unit)
            return DistPending("bcast_ag", buf, self_vals, op)
        if self.lowering == pat.PERMUTE:
            dsts = self.plan.permute_dst
            perm = [(src, dst) for src, dst in enumerate(dsts) if dst >= 0]
            buf = lax.ppermute(root_shard, self.axis, perm)
            return DistPending("bcast_perm", buf, self_vals, op)
        # general packed all-to-all (pack via the Pallas kernel)
        sidx = _take_row(p.send_root_idx, me)            # (R, P)
        sbuf = self._pack_rows(root_shard, sidx)         # (R, P, unit) pack
        buf = lax.all_to_all(sbuf, self.axis, split_axis=0, concat_axis=0,
                             tiled=True)
        if self.sync_mode:
            buf = self._barrier(buf)
        return DistPending("bcast", buf, self_vals, op)

    def bcast_end(self, pending: DistPending, leaf_shard: jnp.ndarray) -> jnp.ndarray:
        p = self.plan
        me = self._me()
        op = pending.op
        out = leaf_shard
        if pending.kind == "bcast_ag":
            # leaves are the rank-major concatenation of all roots
            flat = pending.buf.reshape((-1,) + pending.buf.shape[2:])
            src = self._allgather_src_map()               # (total,) static
            vals = jnp.take(flat, src, axis=0)
            out = self._apply(out, np.arange(src.shape[0]), vals, op)
            return out
        if pending.kind == "bcast_perm":
            idx = _take_row(self._permute_unpack_idx(), me)
            out = self._apply(out, idx, pending.buf, op)
            return out
        # general / local_only
        if pending.buf.shape[1]:
            lidx = _take_row(p.recv_leaf_idx, me).reshape(-1)
            flat = pending.buf.reshape((-1,) + pending.buf.shape[2:])
            out = self._apply(out, lidx, flat, op)
        out = self._apply(out, _take_row(p.self_leaf_idx, me),
                          pending.self_vals, op)
        return out

    def bcast(self, root_shard, leaf_shard, op="replace"):
        return self.bcast_end(self.bcast_begin(root_shard, op), leaf_shard)

    # -------------------------------------------------------------- reduce
    def reduce_begin(self, leaf_shard: jnp.ndarray, op="sum") -> DistPending:
        op = get_op(op)
        p = self.plan
        p.unit.check(leaf_shard, "leaf shard")
        me = self._me()
        self_vals = jnp.take(leaf_shard, _take_row(p.self_leaf_idx, me), axis=0)
        if self.lowering in (pat.LOCAL_ONLY, pat.EMPTY):
            # keep the full (R, P) slot layout: reduce_end's sort-segment
            # machinery addresses self slots at offset R*P
            buf = jnp.zeros((p.nranks, p.P) + leaf_shard.shape[1:],
                            leaf_shard.dtype)
            return DistPending("reduce", buf, self_vals, op)
        if self.lowering == pat.ALLGATHER and op.name == "sum":
            # reduce over an allgather-SF == reduce_scatter
            blocks = jnp.take(leaf_shard, self._allgather_block_map(), axis=0)
            buf = lax.psum_scatter(blocks, self.axis, scatter_dimension=0,
                                   tiled=False)
            return DistPending("reduce_rs", buf, self_vals, op)
        # general path (also used for permute SFs in reverse and non-sum
        # reductions on allgather SFs); pack via the Pallas kernel
        lidx = _take_row(p.recv_leaf_idx, me)            # (R, P)
        sbuf = self._pack_rows(leaf_shard, lidx)         # (R, P, unit)
        buf = lax.all_to_all(sbuf, self.axis, split_axis=0, concat_axis=0,
                             tiled=True)
        if self.sync_mode:
            buf = self._barrier(buf)
        return DistPending("reduce", buf, self_vals, op)

    def reduce_end(self, pending: DistPending, root_shard: jnp.ndarray) -> jnp.ndarray:
        p = self.plan
        me = self._me()
        op = pending.op
        if pending.kind == "reduce_rs":
            g = np.arange(p.root_pad)
            return self._apply(root_shard, g, pending.buf, op)
        # general: flat slot space = R*P remote ++ self_pad local
        flat = jnp.concatenate(
            [pending.buf.reshape((-1,) + pending.buf.shape[2:]),
             pending.self_vals], axis=0)
        sortedv = self._pack_rows(flat, _take_row(p.red_perm, me))
        if op.name == "replace":
            wsrc = _take_row(p.replace_win_src, me)
            wdst = _take_row(p.replace_win_dst, me)
            return root_shard.at[wdst].set(
                jnp.take(sortedv, wsrc, axis=0).astype(root_shard.dtype))
        if self.use_kernels and op.name in ("sum", "prod", "max", "min") \
                and sortedv.size:
            if p.red_dup_free:
                # every segment is one slot: reduction degenerates to the
                # unpack scatter itself
                return self._apply(root_shard, _take_row(p.red_dst, me),
                                   sortedv, op)
            seg = self._segment_reduce_kernel(sortedv, me, op)
            return self._apply(root_shard, _take_row(p.red_seg_dst, me),
                               seg, op)
        seg_ids = _take_row(p.red_seg_id, me)
        if op.name in ("sum", "prod", "max", "min", "lor", "land"):
            seg = op.segment(sortedv, seg_ids, p.red_nslots)
            seg_dst = _take_row(p.red_seg_dst, me)
            return self._apply(root_shard, seg_dst, seg, op)
        raise NotImplementedError(op.name)

    def reduce(self, leaf_shard, root_shard, op="sum"):
        return self.reduce_end(self.reduce_begin(leaf_shard, op), root_shard)

    # -------------------------------------------------------- fetch-and-op
    def fetch_and_op(self, root_shard: jnp.ndarray, leaf_shard: jnp.ndarray,
                     op="sum") -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Distributed fetch-and-add (paper §3.2).  Returns
        (root_shard', leafupdate_shard)."""
        op = get_op(op)
        if op.name != "sum":
            raise NotImplementedError("fetch_and_op supports op='sum'")
        p = self.plan
        me = self._me()
        # 1) route leaf values to root ranks (same movement as reduce)
        lidx = _take_row(p.recv_leaf_idx, me)
        sbuf = self._pack_rows(leaf_shard, lidx)
        buf = lax.all_to_all(sbuf, self.axis, split_axis=0, concat_axis=0,
                             tiled=True)
        self_vals = jnp.take(leaf_shard, _take_row(p.self_leaf_idx, me), axis=0)
        flat = jnp.concatenate(
            [buf.reshape((-1,) + buf.shape[2:]), self_vals], axis=0)
        perm = _take_row(p.red_perm, me)
        sortedv = self._pack_rows(flat, perm)
        # 2) exclusive in-segment prefix (deterministic order)
        csum = jnp.cumsum(sortedv, axis=0)
        seg_start = _take_row(p.red_seg_start, me)
        head = jnp.take(csum, seg_start, axis=0) - jnp.take(sortedv, seg_start,
                                                            axis=0)
        excl = csum - sortedv - head
        dst = _take_row(p.red_dst, me)
        base = jnp.take(root_shard, dst, axis=0)
        fetched_sorted = base + excl.astype(root_shard.dtype)
        # 3) update roots with segment totals
        seg_ids = _take_row(p.red_seg_id, me)
        seg = op.segment(sortedv, seg_ids, p.red_nslots)
        root_out = self._apply(root_shard, _take_row(p.red_seg_dst, me), seg, op)
        # 4) route fetched values back to leaves (reverse all_to_all)
        flat_fetched = jnp.take(fetched_sorted, _take_row(p.red_inv_perm, me),
                                axis=0)
        remote = flat_fetched[: p.nranks * p.P].reshape(
            (p.nranks, p.P) + flat_fetched.shape[1:])
        back = lax.all_to_all(remote, self.axis, split_axis=0, concat_axis=0,
                              tiled=True)
        # back[q-slot view]: on leaf rank q, back[p] = fetched vals for pair(p,q)
        leafupd = leaf_shard
        sidx = _take_row(p.send_root_idx, me)  # not needed; kept for clarity
        del sidx
        lidx_flat = _take_row(p.recv_leaf_idx, me).reshape(-1)
        leafupd = leafupd.at[lidx_flat].set(
            back.reshape((-1,) + back.shape[2:]).astype(leaf_shard.dtype))
        self_fetched = flat_fetched[p.nranks * p.P:]
        leafupd = leafupd.at[_take_row(p.self_leaf_idx, me)].set(
            self_fetched.astype(leaf_shard.dtype))
        return root_out, leafupd

    # ----------------------------------------------------- static maps
    def _allgather_src_map(self) -> np.ndarray:
        """Static map: global leaf position -> flattened (R*root_pad) index."""
        p = self.plan
        total = int(p.nroots.sum())
        src = np.zeros(total, dtype=np.int64)
        pos = 0
        for r in range(p.nranks):
            n = int(p.nroots[r])
            src[pos: pos + n] = r * p.root_pad + np.arange(n)
            pos += n
        return src

    def _allgather_block_map(self) -> np.ndarray:
        """Static map: (R, root_pad) gather indices into my leaf shard for the
        reduce_scatter path (block p = my leaf values for rank p's roots)."""
        p = self.plan
        ro = np.zeros(p.nranks + 1, dtype=np.int64)
        np.cumsum(p.nroots, out=ro[1:])
        out = np.full((p.nranks, p.root_pad), p.leaf_pad - 1, dtype=np.int64)
        for r in range(p.nranks):
            n = int(p.nroots[r])
            out[r, : n] = ro[r] + np.arange(n)
        return out

    def _permute_unpack_idx(self) -> np.ndarray:
        """Static (R, root_pad) leaf positions: where the received block lands
        on each rank (garbage beyond the true count)."""
        p = self.plan
        out = np.full((p.nranks, p.root_pad), p.leaf_pad - 1, dtype=np.int64)
        for pi in self.sf.pairs:
            if pi.root_rank == pi.leaf_rank:
                continue
            # receiving rank pi.leaf_rank gets root_rank's whole block in order
            out[pi.leaf_rank, : pi.count] = pi.leaf_idx
        return out

    # --------------------------------------------------- jitted global API
    def make_bcast_fn(self, mesh: Mesh, unit_shape=(), dtype=jnp.float32,
                      op="replace"):
        """Build a jitted global-array bcast over ``mesh`` for testing and
        benchmarking: takes stacked (R, root_pad, *unit) and
        (R, leaf_pad, *unit) arrays sharded over ``self.axis``."""
        spec = P(self.axis)
        shard = NamedSharding(mesh, spec)

        def fn(roots, leaves):
            def inner(r, l):
                return self.bcast(r[0], l[0], op=op)[None]
            return _smap(inner, mesh, (spec, spec), spec)(roots, leaves)

        return jax.jit(fn, in_shardings=(shard, shard), out_shardings=shard)

    def make_reduce_fn(self, mesh: Mesh, op="sum"):
        spec = P(self.axis)
        shard = NamedSharding(mesh, spec)

        def fn(leaves, roots):
            def inner(l, r):
                return self.reduce(l[0], r[0], op=op)[None]
            return _smap(inner, mesh, (spec, spec), spec)(leaves, roots)

        return jax.jit(fn, in_shardings=(shard, shard), out_shardings=shard)

    def make_fetch_fn(self, mesh: Mesh, op="sum"):
        spec = P(self.axis)
        shard = NamedSharding(mesh, spec)

        def fn(roots, leaves):
            def inner(r, l):
                ro, lu = self.fetch_and_op(r[0], l[0], op=op)
                return ro[None], lu[None]
            return _smap(inner, mesh, (spec, spec), (spec, spec))(roots, leaves)

        return jax.jit(fn, in_shardings=(shard, shard),
                       out_shardings=(shard, shard))

    # -------------------------------------------------------- data helpers
    def pad_root_stack(self, per_rank: Sequence[np.ndarray]) -> np.ndarray:
        return pad_ragged(per_rank, self.plan.root_pad)

    def pad_leaf_stack(self, per_rank: Sequence[np.ndarray]) -> np.ndarray:
        return pad_ragged(per_rank, self.plan.leaf_pad)

    def unpad_root_stack(self, stacked) -> list:
        return unpad_ragged(np.asarray(stacked), list(self.plan.nroots))

    def unpad_leaf_stack(self, stacked) -> list:
        return unpad_ragged(np.asarray(stacked), list(self.plan.nleafspace))
