"""SF communication operations (paper §3.2) — jnp execution on global arrays.

These are the user-facing, jit-friendly, differentiable implementations used
when the whole SF's data lives in one (possibly sharded-by-GSPMD) array.  The
explicitly rank-decomposed shard_map lowering lives in
:mod:`repro.core.distributed`; both must agree with the numpy oracle in
:mod:`repro.core.simulate`.

All operations come in fused form (``bcast``) and split begin/end form
(``bcast_begin`` / ``bcast_end``), the paper's mechanism for overlapping
communication with independent computation.  Under XLA the begin half issues
the data movement; anything computed between begin and end is independent of
it, so the latency-hiding scheduler overlaps them (DESIGN.md §3.2).
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import StarForest
from .mpiops import Op, get_op
from .plan import GlobalPlan, build_global_plan
from .unit import check_plan_unit
from . import sflog

__all__ = [
    "SFOps", "PendingComm", "UnpackMap", "unpack_map",
]


@dataclasses.dataclass
class PendingComm:
    """In-flight communication token returned by *Begin operations."""
    kind: str
    payload: jnp.ndarray
    op: Op
    owner: "SFOps" = None

    def end(self, data: jnp.ndarray) -> jnp.ndarray:
        """Complete the operation against the destination array."""
        info = sflog.claim_pending(self)
        t0 = time.perf_counter() if info is not None else 0.0
        if self.kind == "bcast":
            out = self.owner.bcast_end(self, data)
        else:
            out = self.owner.reduce_end(self, data)
        if info is not None:
            sflog.pending_end(info, t0, out)
        return out


# Share of the destination rows an index list must cover for the unpack to
# gather over an inverse map instead of scattering.  On a TPU v5e XLA's
# scatter writes unique rows of 2 to 8 f32 elements one after another
# (~90 ns a row) while the gather form costs ~2.4-5.7 ns per destination
# row, so the gather wins above about 5% coverage.  Rows of one element
# scatter at 5-12 ns a row and keep the scatter (PERF.md §6 has the chip
# measurements).
DENSE_UNPACK_SHARE = 0.05

_COMBINE = {"add": jnp.add, "multiply": jnp.multiply, "max": jnp.maximum,
            "min": jnp.minimum}


@dataclasses.dataclass(frozen=True)
class UnpackMap:
    """Setup product for one static unique-destination index list into
    ``nrows`` destination rows, in one of three forms:

    * ``"identity"``: the list is ``arange(nrows)``; the unpack is
      elementwise.
    * ``"gather"``: the list covers at least ``DENSE_UNPACK_SHARE`` of the
      rows; ``src`` (nrows,) is each row's position in the list (0 where
      the row is not written) and ``mask`` marks the written rows.
    * ``"scatter"``: the list is sparse.

    ``idx`` (the list itself, which every form can scatter through),
    ``src`` and ``mask`` are int32/bool device arrays made once."""

    form: str
    nrows: int
    idx: jax.Array
    src: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None


def unpack_map(idx, nrows: int) -> UnpackMap:
    """Choose the unpack form of ``idx`` from its count over ``nrows``."""
    idx = np.asarray(idx, dtype=np.int64).reshape(-1)
    n = idx.size
    # device arrays, not tracers, when set-up runs inside a jax trace
    with jax.ensure_compile_time_eval():
        dev_idx = jnp.asarray(idx.astype(np.int32))
        if n == nrows and np.array_equal(idx, np.arange(n)):
            return UnpackMap("identity", nrows, dev_idx)
        if n and n >= DENSE_UNPACK_SHARE * nrows:
            src = np.zeros(nrows, np.int32)
            src[idx] = np.arange(n, dtype=np.int32)
            mask = np.zeros(nrows, bool)
            mask[idx] = True
            return UnpackMap("gather", nrows, dev_idx, jnp.asarray(src),
                             jnp.asarray(mask))
        return UnpackMap("scatter", nrows, dev_idx)


def _apply_unique(target: jnp.ndarray, umap: UnpackMap, vals: jnp.ndarray,
                  op: Op) -> jnp.ndarray:
    """Write ``vals`` into ``target`` at the unique rows of ``umap`` with
    reduction op (the SF unpack: one named program, device scope
    ``sf.unpack``).  A destination of another length than the map's, and
    rows of one element in the gather form, take the scatter."""
    form = umap.form
    if target.shape[0] != umap.nrows or (
            form == "gather" and math.prod(target.shape[1:]) == 1):
        form = "scatter"
    if form == "scatter":
        return sf_unpack_rows(target, umap.idx, vals, mode=op.at_update)
    return sf_unpack_rows(target, umap.src, vals, umap.mask,
                          mode=op.at_update, form=form)


@partial(jax.jit, static_argnames=("mode", "form"))
def sf_unpack_rows(target: jnp.ndarray, idx, vals: jnp.ndarray, mask=None,
                   *, mode: str, form: str = "scatter") -> jnp.ndarray:
    """``target.at[idx].<mode>(vals)`` for unique ``idx``, computed in the
    :class:`UnpackMap` form ``form`` (in the gather form ``idx`` and
    ``mask`` are the map's ``src`` and ``mask``).  Counts
    ``sf.unpack.<form>`` once per built program."""
    sflog.counter(f"sf.unpack.{form}").add()
    with sflog.scope("sf.unpack"):
        vals = vals.astype(target.dtype)
        if form == "scatter":
            return getattr(target.at[idx], mode)(vals, unique_indices=True,
                                                 indices_are_sorted=False)
        if form == "gather":
            vals = jnp.take(vals, idx, axis=0)
        new = vals if mode == "set" else _COMBINE[mode](target, vals)
        if form == "identity":
            return new
        return jnp.where(mask.reshape(mask.shape + (1,) * (target.ndim - 1)),
                         new, target)


class SFOps:
    """Executable operations bound to one StarForest template.

    The constructor performs the setup-time analysis (``GlobalPlan``); each
    method is a pure function suitable for ``jax.jit`` and ``jax.grad``.
    Payload rows are ``(*unit)`` dof blocks of any rank and dtype (paper
    §3.2's ``MPI_Datatype unit``); passing ``unit=`` pins the plan's unit
    and validates payloads at the SF boundary.
    """

    def __init__(self, sf: StarForest, plan: Optional[GlobalPlan] = None,
                 unit=None):
        sf.setup()
        self.sf = sf
        if plan is not None:
            check_plan_unit(plan, unit)
            self.plan = plan
        else:
            self.plan = build_global_plan(sf, unit=unit)
        p = self.plan
        # setup-time unpack maps (PetscSFSetUp analogue)
        self._unpack_leaf = unpack_map(p.gl, p.nleafspace)
        self._unpack_seg = unpack_map(p.red_seg_root, p.nroots)

    @property
    def unit(self):
        """The plan's payload unit spec (paper §3.2 ``MPI_Datatype``)."""
        return self.plan.unit

    # ------------------------------------------------------------- bcast
    def bcast_begin(self, rootdata: jnp.ndarray, op="replace") -> PendingComm:
        """Roots push values toward leaves; returns the in-flight buffer."""
        op = get_op(op)
        p = self.plan
        rootdata = jnp.asarray(rootdata)
        p.unit.check(rootdata, "rootdata")
        with sflog.scope("sf.pack"):
            vals = jnp.take(rootdata, p.gr, axis=0)   # pack == gather
        return PendingComm("bcast", vals, op, self)

    def bcast_end(self, pending: PendingComm, leafdata: jnp.ndarray) -> jnp.ndarray:
        assert pending.kind == "bcast"
        # each leaf has exactly one root -> unique destinations
        return _apply_unique(jnp.asarray(leafdata), self._unpack_leaf,
                             pending.payload, pending.op)

    def bcast(self, rootdata, leafdata, op="replace"):
        return self.bcast_end(self.bcast_begin(rootdata, op), leafdata)

    # ------------------------------------------------------------- reduce
    def reduce_begin(self, leafdata: jnp.ndarray, op="sum") -> PendingComm:
        """Leaves push values toward roots."""
        op = get_op(op)
        p = self.plan
        leafdata = jnp.asarray(leafdata)
        p.unit.check(leafdata, "leafdata")
        with sflog.scope("sf.pack"):
            vals = jnp.take(leafdata, p.gl, axis=0)
        return PendingComm("reduce", vals, op, self)

    def reduce_end(self, pending: PendingComm, rootdata: jnp.ndarray) -> jnp.ndarray:
        assert pending.kind == "reduce"
        p, op = self.plan, pending.op
        rootdata = jnp.asarray(rootdata)
        vals = pending.payload
        if op.name == "replace":
            # deterministic last-writer wins, precomputed at setup
            win_edges = p.red_perm[p.replace_last]
            with sflog.scope("sf.unpack"):
                return rootdata.at[p.gr[win_edges]].set(
                    jnp.take(vals, win_edges, axis=0).astype(rootdata.dtype),
                    unique_indices=True)
        if op.name in ("sum", "prod", "max", "min"):
            # duplicate roots: the scatter combines as it writes
            with sflog.scope("sf.unpack"):
                return getattr(rootdata.at[p.gr], op.at_update)(
                    vals.astype(rootdata.dtype))
        # logical ops: reduce via segment machinery for exactness
        with sflog.scope("sf.combine"):
            sorted_vals = jnp.take(vals, p.red_perm, axis=0)
            seg = op.segment(sorted_vals, p.red_seg_of_edge,
                             int(p.red_seg_root.shape[0]))
        return _apply_unique(rootdata, self._unpack_seg, seg, op)

    def reduce(self, leafdata, rootdata, op="sum"):
        return self.reduce_end(self.reduce_begin(leafdata, op), rootdata)

    # -------------------------------------------------------- fetch-and-op
    def fetch_and_op(self, rootdata: jnp.ndarray, leafdata: jnp.ndarray,
                     op="sum") -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Paper §3.2 FetchAndOp (op must be ``sum``): every leaf receives the
        root's value as of all earlier edges (deterministic order); roots end
        up fully reduced.  Returns ``(rootdata', leafupdate)``."""
        op = get_op(op)
        if op.name != "sum":
            raise NotImplementedError("fetch_and_op supports op='sum' "
                                      "(fetch-and-add), as used by the paper")
        p = self.plan
        rootdata = jnp.asarray(rootdata)
        leafdata = jnp.asarray(leafdata)
        vals = jnp.take(leafdata, p.gl, axis=0)
        sv = jnp.take(vals, p.red_perm, axis=0)            # sorted by root
        csum = jnp.cumsum(sv, axis=0)
        head = jnp.take(csum, p.red_seg_start, axis=0) - jnp.take(
            sv, p.red_seg_start, axis=0)
        excl = csum - sv - head                            # exclusive in-segment prefix
        base = jnp.take(rootdata, p.gr[p.red_perm], axis=0)
        fetched_sorted = base + excl.astype(rootdata.dtype)
        # un-permute: fetched[perm[i]] = fetched_sorted[i]
        fetched = jnp.take(fetched_sorted, p.red.inv_perm, axis=0)
        leafupdate = leafdata.at[p.gl].set(
            fetched.astype(leafdata.dtype), unique_indices=True)
        root_out = rootdata.at[p.gr].add(vals.astype(rootdata.dtype))
        return root_out, leafupdate

    # ------------------------------------------------------ gather/scatter
    @property
    def nmulti(self) -> int:
        return self.plan.nmulti

    def gather(self, leafdata: jnp.ndarray) -> jnp.ndarray:
        """SFGather: leaf values land in per-edge multi-root slots."""
        p = self.plan
        leafdata = jnp.asarray(leafdata)
        vals = jnp.take(leafdata, p.gl, axis=0)
        out = jnp.zeros((p.nmulti,) + leafdata.shape[1:], dtype=leafdata.dtype)
        return out.at[p.multi_slot].set(vals, unique_indices=True)

    def scatter(self, multirootdata: jnp.ndarray,
                leafdata: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """SFScatter: inverse of gather."""
        p = self.plan
        multirootdata = jnp.asarray(multirootdata)
        vals = jnp.take(multirootdata, p.multi_slot, axis=0)
        if leafdata is None:
            leafdata = jnp.zeros((p.nleafspace,) + multirootdata.shape[1:],
                                 dtype=multirootdata.dtype)
        leafdata = jnp.asarray(leafdata)
        return leafdata.at[p.gl].set(vals.astype(leafdata.dtype),
                                     unique_indices=True)

    # ------------------------------------------------------------- degrees
    def compute_degrees(self) -> jnp.ndarray:
        """Root degrees via SFReduce of ones — the paper's degree routine."""
        ones = jnp.ones((self.plan.nleafspace,), dtype=jnp.int32)
        return self.reduce(ones, jnp.zeros((self.plan.nroots,), jnp.int32))
