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
    "SFOps", "PendingComm",
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


def _apply_unique(target: jnp.ndarray, idx: np.ndarray, vals: jnp.ndarray,
                  op: Op) -> jnp.ndarray:
    """Scatter ``vals`` into ``target`` at unique ``idx`` with reduction op
    (the SF unpack: one named program, device scope ``sf.unpack``)."""
    return sf_unpack_rows(target, idx, vals, mode=op.at_update)


@partial(jax.jit, static_argnames="mode")
def sf_unpack_rows(target: jnp.ndarray, idx, vals: jnp.ndarray, *,
                   mode: str) -> jnp.ndarray:
    """``target.at[idx].<mode>(vals)`` for unique ``idx``."""
    with sflog.scope("sf.unpack"):
        return getattr(target.at[idx], mode)(vals.astype(target.dtype),
                                             unique_indices=True,
                                             indices_are_sorted=False)


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
        p = self.plan
        # each leaf has exactly one root -> unique destinations
        return _apply_unique(jnp.asarray(leafdata), p.gl, pending.payload,
                             pending.op)

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
        return _apply_unique(rootdata, p.red_seg_root, seg, op)

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
