"""Registry-selected SF execution backends (paper §4–§5).

PetscSF's defining design is a small API backed by multiple selectable
implementations — Basic (two-sided MPI), Neighbor, Window, and the CUDA/
NVSHMEM-aware variants — chosen per architecture and communication pattern at
setup time via ``-sf_backend``.  This module is that layer for the JAX port:

  ``"global"``    today's :class:`repro.core.ops.SFOps` — jit/grad-friendly
                  jnp ops on global concatenated arrays (GSPMD decides the
                  actual partitioning), the Basic-backend analogue.
  ``"shardmap"``  today's :class:`repro.core.distributed.DistSF` — explicit
                  rank decomposition lowered to jax.lax collectives inside
                  ``shard_map``, the Neighbor/NVSHMEM analogue.
  ``"pallas"``    the general pack → exchange → unpack path routed through
                  the Pallas device kernels (:mod:`repro.kernels.sf_pack`,
                  :mod:`repro.kernels.sf_unpack`) — the CUDA pack-kernel
                  analogue of §5.3, with the §5.2 ¶3 parametric multi-strided
                  pack engaged whenever the pack index list is a 3D-subdomain
                  enumeration.

``select_backend`` mirrors ``-sf_backend``'s default logic: an explicit hint
wins; a mesh whose size matches the SF's rank count selects ``"shardmap"``;
general-pattern SFs on a real accelerator take the kernel path; everything
else uses ``"global"``.  ``register_backend`` lets downstream code add
implementations (the paper's extensibility argument) without touching this
module.

The user-facing object is :class:`SFComm`: build once per StarForest, then
call ``bcast``/``reduce``/``fetch_and_op``/``gather``/``scatter`` on global
arrays regardless of which backend executes them.  Every backend must agree
with the :mod:`repro.core.simulate` numpy oracle — the per-backend
conformance suite in ``tests/test_backends.py`` enforces this.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .graph import StarForest
from .mpiops import Op, get_op
from .ops import PendingComm, SFOps, _apply_unique, unpack_map
from .plan import GlobalPlan, build_global_plan
from .unit import check_plan_unit, resolve_unit
from .distributed import DistSF
from . import patterns as pat
from . import sflog
from . import priors as priors_mod
from ..kernels import ops as kops
from ..kernels.tuning import resolve_interpret

__all__ = [
    "SFBackend", "SFComm",
    "register_backend", "available_backends", "make_backend",
    "select_backend",
    "GlobalBackend", "ShardmapBackend", "PallasBackend",
]


@runtime_checkable
class SFBackend(Protocol):
    """What every SF execution backend provides (paper §3.2 op set).

    All data arguments are *global concatenated* arrays: ``rootdata`` of
    shape ``(sf.nroots_total, *unit)`` and ``leafdata`` of shape
    ``(sf.nleafspace_total, *unit)`` — the layout of the
    :mod:`repro.core.simulate` oracle.
    """

    name: str

    def bcast_begin(self, rootdata, op="replace"): ...
    def bcast_end(self, pending, leafdata): ...
    def bcast(self, rootdata, leafdata, op="replace"): ...
    def reduce_begin(self, leafdata, op="sum"): ...
    def reduce_end(self, pending, rootdata): ...
    def reduce(self, leafdata, rootdata, op="sum"): ...
    def fetch_and_op(self, rootdata, leafdata, op="sum"): ...
    def gather(self, leafdata): ...
    def scatter(self, multirootdata, leafdata=None): ...


# --------------------------------------------------------------------------
# registry (PetscFunctionList analogue for -sf_backend)
# --------------------------------------------------------------------------
BackendFactory = Callable[..., "SFBackend"]
_REGISTRY: Dict[str, BackendFactory] = {}


def register_backend(name: str, factory: BackendFactory, *,
                     overwrite: bool = False) -> None:
    """Register a backend factory ``factory(sf, mesh=None, **kwargs)``."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"SF backend {name!r} already registered")
    _REGISTRY[name] = factory


def available_backends() -> list:
    return sorted(_REGISTRY)


def make_backend(name: str, sf: StarForest, **kwargs) -> "SFBackend":
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown SF backend {name!r}; registered: "
                         f"{available_backends()}") from None
    return factory(sf, **kwargs)


def estimate_message_bytes(sf: StarForest, unit=None) -> float:
    """Per-exchange payload bytes for ``sf``: edges × unit row bytes
    (scalar float32 rows when the unit is unpinned) — the lookup key into
    the measured priors table."""
    u = resolve_unit(unit)
    row_bytes = u.nbytes if u.nbytes else 4 * max(u.size, 1)
    return float(sf.nedges_total) * row_bytes


def select_backend(sf: StarForest, mesh=None, hint: Optional[str] = None, *,
                   unit=None, priors=None) -> str:
    """Pick a backend name for ``sf`` (the ``-sf_backend`` default logic).

    Order: an explicit ``hint`` wins (validated against the registry); a
    ``mesh`` whose device count matches ``sf.nranks`` selects the explicit
    shard_map decomposition; then the *measured priors table* — shipped
    ``BENCH_*.json`` artifacts parsed by :mod:`repro.core.priors`, trusted
    only when their stamp matches this platform/jax/device-count — picks the
    backend the measurements favor at the SF's message size (paper abstract:
    choose the implementation "based on the characteristics of the
    application or the target architecture").  When no compatible
    measurements exist the static heuristic decides: general-pattern SFs on
    an accelerator take the Pallas kernel path, everything else — including
    the allgather/permute patterns whose §5.2 lowerings live in the
    shard_map/global paths — defaults to ``"global"``.

    ``unit`` sharpens the message-size estimate; ``priors`` substitutes an
    explicit :class:`repro.core.priors.PriorsTable` (tests, fresh
    calibration runs).  ``REPRO_SF_PRIORS=0`` disables the table.
    """
    sf.setup()
    if hint is not None:
        if hint not in _REGISTRY:
            raise ValueError(f"unknown SF backend hint {hint!r}; registered: "
                             f"{available_backends()}")
        return hint
    if mesh is not None and sf.nranks > 1 \
            and int(np.prod(mesh.devices.shape)) == sf.nranks:
        return "shardmap"
    if sf.nedges_total:
        table = priors if priors is not None else priors_mod.default_priors()
        if table is not None:
            cands = [b for b in ("global", "pallas") if b in _REGISTRY]
            choice = table.best_backend(estimate_message_bytes(sf, unit),
                                        candidates=cands)
            if choice is not None:
                return choice
    rep = pat.analyze(sf)
    # kernels only compile (Mosaic) on TPU; everywhere else they interpret,
    # so the jnp global path is the faster default
    if rep.kind == pat.GENERAL and jax.default_backend() == "tpu":
        return "pallas"
    return "global"


# --------------------------------------------------------------------------
# "global" — SFOps on global arrays (the Basic backend analogue)
# --------------------------------------------------------------------------
class GlobalBackend(SFOps):
    """jnp ops on global concatenated arrays (GSPMD-friendly)."""

    name = "global"


# --------------------------------------------------------------------------
# "pallas" — kernel pack/unpack on the general path (paper §5.2–§5.3)
# --------------------------------------------------------------------------
class PallasBackend:
    """Global-array execution with the Pallas pack/unpack kernels on the
    hot path.

    Packs are the scalar-prefetch gather kernel (``sf_pack.pack``), or the
    parametric multi-strided kernel (``sf_pack.pack_strided``) when the pack
    index list enumerates a 3D subdomain (paper §5.2 ¶3 — detected by the
    same machinery that powers :class:`repro.core.patterns.PatternReport`).
    Reductions pack directly in *sorted* slot order, segment-reduce with the
    ``sf_unpack`` kernel (the CUDA-atomics replacement), and finish with one
    duplicate-free scatter.  Kernels interpret on CPU and compile to Mosaic
    on TPU.
    """

    name = "pallas"

    def __init__(self, sf: StarForest, plan: Optional[GlobalPlan] = None,
                 interpret: Optional[bool] = None, unit=None):
        sf.setup()
        self.sf = sf
        if plan is not None:
            check_plan_unit(plan, unit)
            self.plan = plan
        else:
            self.plan = build_global_plan(sf, unit=unit)
        self.interpret = resolve_interpret(interpret)
        # autotune/kernel-cache scope: one signature per (pattern, unit)
        self._tune_key = self.plan.comm_signature()
        p, red = self.plan, self.plan.red
        # setup-time index products (PetscSFSetUp analogue)
        self._gl_sorted = p.gl[red.perm]       # pack list for reduce
        self._gr_sorted = p.gr[red.perm]
        # §5.2 ¶3: engage the parametric strided pack when the index list is
        # exactly a 3D-subdomain enumeration (contiguous is the 1D case)
        self._bcast_strided = pat.detect_strided(p.gr) if p.nedges else None
        self._reduce_strided = pat.detect_strided(self._gl_sorted) \
            if p.nedges else None
        self._unpack_leaf = unpack_map(p.gl, p.nleafspace)
        # one map for both reduce unpacks: a duplicate-free plan's
        # ``dst_sorted`` is its ``seg_dst``
        self._unpack_seg = unpack_map(red.seg_dst, p.nroots)

    @property
    def unit(self):
        return self.plan.unit

    # ------------------------------------------------------------ plumbing
    def _pack(self, data: jnp.ndarray, idx: np.ndarray,
              strided: Optional[pat.Strided3D] = None) -> jnp.ndarray:
        """rows ``data[idx]`` via the pack kernel (strided variant when the
        enumeration is parametric and, compiled, its panels are whole
        tiles).  Both kernels take the full ``(*unit)`` row shape, so
        payloads pass through unreshaped."""
        data = jnp.asarray(data)
        if strided is None or not (self.interpret or kops.strided_ok(
                strided.dims, data.dtype)):
            return kops.pack_rows(data, idx, interpret=self.interpret,
                                  key=self._tune_key)
        unit = data.shape[1:]
        usize = int(np.prod(unit)) if unit else 1
        M = int(np.size(idx))
        with sflog.scope("sf.pack"):
            if M == 0 or usize == 0 or data.shape[0] == 0:
                return jnp.take(data, jnp.asarray(idx), axis=0)
            scalar_rows = data.ndim == 1
            out = kops.sf_pack_strided(
                data[:, None] if scalar_rows else data, start=strided.start,
                dims=strided.dims, strides=strided.strides,
                interpret=self.interpret)
            return out[:, 0] if scalar_rows else out

    def _segment_reduce(self, sorted_vals: jnp.ndarray, opname: str
                        ) -> jnp.ndarray:
        """sf_unpack kernel over the sorted slot buffer -> one row/segment."""
        red = self.plan.red
        return kops.segment_reduce_rows(
            sorted_vals, red.seg_first, red.seg_len, num_segments=red.nseg,
            Lmax=red.max_valid_seg_len, op=opname, interpret=self.interpret,
            seg_of_slot=red.seg_of_slot, key=self._tune_key)

    # ------------------------------------------------------------- bcast
    def bcast_begin(self, rootdata: jnp.ndarray, op="replace") -> PendingComm:
        op = get_op(op)
        rootdata = jnp.asarray(rootdata)
        self.plan.unit.check(rootdata, "rootdata")
        vals = self._pack(rootdata, self.plan.gr, self._bcast_strided)
        return PendingComm("bcast", vals, op, self)

    def bcast_end(self, pending: PendingComm,
                  leafdata: jnp.ndarray) -> jnp.ndarray:
        assert pending.kind == "bcast"
        # each leaf has exactly one root -> unique destinations
        return _apply_unique(jnp.asarray(leafdata), self._unpack_leaf,
                             pending.payload, pending.op)

    def bcast(self, rootdata, leafdata, op="replace"):
        p, opn = self.plan, get_op(op)
        if (opn.name == "replace" and p.nedges
                and p.pattern is not None
                and p.pattern.kind == pat.LOCAL_ONLY):
            # §5.2 local/remote split: self-communication takes the fused
            # pack→unpack kernel — no intermediate packed leaf buffer
            rootdata = jnp.asarray(rootdata)
            leafdata = jnp.asarray(leafdata)
            p.unit.check(rootdata, "rootdata")
            p.unit.check(leafdata, "leafdata")
            return kops.local_bcast_rows(rootdata, leafdata, p.gr, p.gl,
                                         interpret=self.interpret,
                                         key=self._tune_key)
        return self.bcast_end(self.bcast_begin(rootdata, opn), leafdata)

    # ------------------------------------------------------------- reduce
    def reduce_begin(self, leafdata: jnp.ndarray, op="sum") -> PendingComm:
        """Pack leaf values directly in sorted slot order (the pack and the
        determinism sort are one gather)."""
        op = get_op(op)
        leafdata = jnp.asarray(leafdata)
        self.plan.unit.check(leafdata, "leafdata")
        vals = self._pack(leafdata, self._gl_sorted, self._reduce_strided)
        return PendingComm("reduce", vals, op, self)

    def reduce_end(self, pending: PendingComm,
                   rootdata: jnp.ndarray) -> jnp.ndarray:
        assert pending.kind == "reduce"
        p, red, op = self.plan, self.plan.red, pending.op
        rootdata = jnp.asarray(rootdata)
        sv = pending.payload                   # (E, *unit), sorted by root
        if p.nedges == 0:
            return rootdata
        if op.name == "replace":
            # deterministic last-writer wins, precomputed at setup
            return rootdata.at[red.win_dst].set(
                jnp.take(sv, red.win_src, axis=0).astype(rootdata.dtype),
                unique_indices=True)
        usize = int(np.prod(sv.shape[1:])) if sv.shape[1:] else 1
        if op.name in ("sum", "prod", "max", "min") and usize:
            if red.duplicate_free:
                # one slot per root: the unpack scatter is the reduction
                return _apply_unique(rootdata, self._unpack_seg, sv, op)
            seg = self._segment_reduce(sv, op.name)
            return _apply_unique(rootdata, self._unpack_seg, seg, op)
        # logical ops reduce as max/min over the int32 view (as mpiops does)
        seg = op.segment(sv, red.seg_of_slot, red.nseg)
        return _apply_unique(rootdata, self._unpack_seg, seg, op)

    def reduce(self, leafdata, rootdata, op="sum"):
        return self.reduce_end(self.reduce_begin(leafdata, op), rootdata)

    # -------------------------------------------------------- fetch-and-op
    def fetch_and_op(self, rootdata: jnp.ndarray, leafdata: jnp.ndarray,
                     op="sum") -> Tuple[jnp.ndarray, jnp.ndarray]:
        op = get_op(op)
        if op.name != "sum":
            raise NotImplementedError("fetch_and_op supports op='sum' "
                                      "(fetch-and-add), as used by the paper")
        p, red = self.plan, self.plan.red
        rootdata = jnp.asarray(rootdata)
        leafdata = jnp.asarray(leafdata)
        if p.nedges == 0:
            return rootdata, leafdata
        sv = self._pack(leafdata, self._gl_sorted, self._reduce_strided)
        csum = jnp.cumsum(sv, axis=0)
        head = jnp.take(csum, red.seg_start_of_slot, axis=0) - jnp.take(
            sv, red.seg_start_of_slot, axis=0)
        excl = csum - sv - head              # exclusive in-segment prefix
        base = self._pack(rootdata, self._gr_sorted)
        fetched_sorted = base + excl.astype(rootdata.dtype)
        fetched = self._pack(fetched_sorted, red.inv_perm)
        leafupdate = leafdata.at[p.gl].set(
            fetched.astype(leafdata.dtype), unique_indices=True)
        root_out = rootdata.at[self._gr_sorted].add(
            sv.astype(rootdata.dtype))
        return root_out, leafupdate

    # ------------------------------------------------------ gather/scatter
    @property
    def nmulti(self) -> int:
        return self.plan.nmulti

    def gather(self, leafdata: jnp.ndarray) -> jnp.ndarray:
        p = self.plan
        leafdata = jnp.asarray(leafdata)
        out = jnp.zeros((p.nmulti,) + leafdata.shape[1:], dtype=leafdata.dtype)
        if p.nedges == 0:
            return out
        vals = self._pack(leafdata, p.gl)
        return out.at[p.multi_slot].set(vals, unique_indices=True)

    def scatter(self, multirootdata: jnp.ndarray,
                leafdata: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        p = self.plan
        multirootdata = jnp.asarray(multirootdata)
        if leafdata is None:
            leafdata = jnp.zeros((p.nleafspace,) + multirootdata.shape[1:],
                                 dtype=multirootdata.dtype)
        leafdata = jnp.asarray(leafdata)
        if p.nedges == 0:
            return leafdata
        vals = self._pack(multirootdata, p.multi_slot)
        return leafdata.at[p.gl].set(vals.astype(leafdata.dtype),
                                     unique_indices=True)

    def compute_degrees(self) -> jnp.ndarray:
        ones = jnp.ones((self.plan.nleafspace,), dtype=jnp.int32)
        return self.reduce(ones, jnp.zeros((self.plan.nroots,), jnp.int32))


# --------------------------------------------------------------------------
# "shardmap" — DistSF behind the global-array facade
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _DeferredComm:
    """Facade-level pending token for the shardmap backend: the pack +
    collective + unpack run fused inside one compiled shard_map program, so
    the overlap the begin/end split advertises happens in the XLA scheduler
    (DESIGN.md §3.2), not at this Python boundary."""

    kind: str
    owner: "ShardmapBackend"
    data: Any
    op: Any

    def end(self, data):
        info = sflog.claim_pending(self)
        t0 = time.perf_counter() if info is not None else 0.0
        if self.kind == "bcast":
            out = self.owner.bcast(self.data, data, self.op)
        else:
            out = self.owner.reduce(self.data, data, self.op)
        if info is not None:
            sflog.pending_end(info, t0, out)
        return out


def _stack_maps(offsets, pad: int) -> Tuple[np.ndarray, np.ndarray]:
    """Index maps between global rows (rank-concatenated, ``offsets``) and
    the padded ``(R, pad)`` shard stack: the (R, pad) gather (pad slots
    read row ``offsets[-1]``, an appended zero row) and the flat stack
    position of every global row."""
    offsets = np.asarray(offsets, dtype=np.int64)
    R, total = offsets.size - 1, int(offsets[-1])
    gather = np.full((R, pad), total, dtype=np.int32)
    flat = np.empty(total, dtype=np.int32)
    for r in range(R):
        n = int(offsets[r + 1] - offsets[r])
        gather[r, :n] = offsets[r] + np.arange(n)
        flat[offsets[r]: offsets[r + 1]] = r * pad + np.arange(n)
    return gather, flat


class ShardmapBackend:
    """Explicit rank decomposition: gather the global array into padded
    per-rank shards, run the DistSF shard_map lowering over a device mesh,
    gather the result back.  Every step is a jnp gather on device, so the
    backend also runs under ``jax.jit`` (e.g. inside a CG step); the
    stacked shards are placed one rank per mesh device."""

    name = "shardmap"

    def __init__(self, sf: StarForest, mesh=None, axis_name: str = "sf",
                 lowering: str = "auto", sync_mode: bool = False,
                 use_kernels: Optional[bool] = None, plan=None, unit=None):
        sf.setup()
        self.sf = sf
        self.dist = DistSF(sf, axis_name=axis_name, plan=plan,
                           lowering=lowering, sync_mode=sync_mode,
                           use_kernels=use_kernels, unit=unit)
        if mesh is None:
            devs = jax.devices()
            if len(devs) < sf.nranks:
                raise ValueError(
                    f"shardmap backend needs one device per rank "
                    f"({sf.nranks}), have {len(devs)}; pass a mesh or pick "
                    f"another backend")
            mesh = jax.make_mesh((sf.nranks,), (axis_name,),
                                 (jax.sharding.AxisType.Auto,),
                                 devices=devs[: sf.nranks])
        if int(np.prod(mesh.devices.shape)) != sf.nranks:
            raise ValueError(
                f"mesh has {int(np.prod(mesh.devices.shape))} devices but "
                f"the SF has {sf.nranks} ranks")
        self.mesh = mesh
        self._fns: Dict[Tuple[str, str], Callable] = {}
        self._globalops: Optional[GlobalBackend] = None
        p = self.dist.plan
        self._shard = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(axis_name))
        self._root_maps = _stack_maps(sf.root_offsets(), p.root_pad)
        self._leaf_maps = _stack_maps(sf.leaf_offsets(), p.leaf_pad)

    @property
    def unit(self):
        return self.dist.unit

    # ------------------------------------------------------------ plumbing
    def _fn(self, kind: str, opname: str) -> Callable:
        key = (kind, opname)
        if key not in self._fns:
            maker = {"bcast": self.dist.make_bcast_fn,
                     "reduce": self.dist.make_reduce_fn,
                     "fetch": self.dist.make_fetch_fn}[kind]
            self._fns[key] = maker(self.mesh, op=opname)
        return self._fns[key]

    def _stack(self, data, maps):
        """Global rows -> (R, pad, *unit) shards (pad rows read zeros),
        one rank per mesh device."""
        data = jnp.asarray(data)
        gather, _ = maps
        zero = jnp.zeros((1,) + data.shape[1:], data.dtype)
        return jax.device_put(
            jnp.take(jnp.concatenate([data, zero]), gather, axis=0),
            self._shard)

    @staticmethod
    def _unstack(stacked, maps):
        """(R, pad, *unit) shards -> global rows."""
        _, flat = maps
        rows = stacked.reshape((-1,) + stacked.shape[2:])
        return jnp.take(rows, flat, axis=0)

    # ------------------------------------------------------------ ops
    def bcast_begin(self, rootdata, op="replace") -> _DeferredComm:
        return _DeferredComm("bcast", self, rootdata, op)

    def bcast_end(self, pending: _DeferredComm, leafdata):
        return pending.end(leafdata)

    def bcast(self, rootdata, leafdata, op="replace"):
        out = self._fn("bcast", get_op(op).name)(
            self._stack(rootdata, self._root_maps),
            self._stack(leafdata, self._leaf_maps))
        return self._unstack(out, self._leaf_maps)

    def reduce_begin(self, leafdata, op="sum") -> _DeferredComm:
        return _DeferredComm("reduce", self, leafdata, op)

    def reduce_end(self, pending: _DeferredComm, rootdata):
        return pending.end(rootdata)

    def reduce(self, leafdata, rootdata, op="sum"):
        out = self._fn("reduce", get_op(op).name)(
            self._stack(leafdata, self._leaf_maps),
            self._stack(rootdata, self._root_maps))
        return self._unstack(out, self._root_maps)

    def fetch_and_op(self, rootdata, leafdata, op="sum"):
        ro, lu = self._fn("fetch", get_op(op).name)(
            self._stack(rootdata, self._root_maps),
            self._stack(leafdata, self._leaf_maps))
        return (self._unstack(ro, self._root_maps),
                self._unstack(lu, self._leaf_maps))

    # gather/scatter reorganize into the multi-root layout, a host-derived
    # index transform shared with the global backend.
    def _gops(self) -> GlobalBackend:
        if self._globalops is None:
            self._globalops = GlobalBackend(self.sf)
        return self._globalops

    def gather(self, leafdata):
        return self._gops().gather(leafdata)

    def scatter(self, multirootdata, leafdata=None):
        return self._gops().scatter(multirootdata, leafdata)

    def compute_degrees(self):
        ones = jnp.ones((self.sf.nleafspace_total,), dtype=jnp.int32)
        return self.reduce(ones, jnp.zeros((self.sf.nroots_total,),
                                           jnp.int32))


# --------------------------------------------------------------------------
# facade
# --------------------------------------------------------------------------
class SFComm:
    """One StarForest, one backend, the full §3.2 op set on global arrays.

    The PetscSF-object analogue: construct once (setup cost amortizes over
    every operation), then communicate.  The backend is chosen by
    ``select_backend`` unless named explicitly — exactly the paper's
    ``-sf_backend`` override.

    Payload rows are ``(*unit)`` dof blocks (paper §3.2's ``MPI_Datatype
    unit``); pass ``unit=`` to pin and validate the unit shape/dtype.  To
    move *several* same-pattern fields in one exchange (the VecScatter
    fusion), use :meth:`bcast_multi` / :meth:`reduce_multi`, which route
    through a cached :class:`repro.core.fields.FieldBundle`.

    The StarForest handed in may itself be *derived* from other SFs via
    :mod:`repro.core.compose` (paper §2) — composed, inverse-composed and
    embedded graphs communicate exactly like hand-built ones.  The README
    section "Composed SFs: overlap growth, multigrid, and assembly"
    diagrams the three load-bearing consumers
    (:func:`repro.meshdist.plex.grow_overlap`,
    :class:`repro.solvers.multigrid.Transfer`,
    :class:`repro.sparse.parmat.MatAssembler`).

    Backend auto-selection is *measurement-driven* when compatible shipped
    benchmark artifacts exist (see :mod:`repro.core.priors`), and the Pallas
    backend autotunes its kernel block shapes on first use per communication
    signature (see :mod:`repro.kernels.tuning`).  The README section
    "Data-driven backend selection & autotuning" documents the env knobs
    (``REPRO_SF_PRIORS``, ``REPRO_SF_INTERPRET``, ``REPRO_SF_AUTOTUNE``,
    ``REPRO_SF_IMPL_*``, ``REPRO_SF_TUNE_ITERS``) and how to regenerate the
    priors artifacts.

    The split ``reduce_multi_begin``/``reduce_multi_end`` (and bcast twins)
    expose the fused exchange in the paper's begin/end form; the DDP-style
    bucketed gradient exchange in :mod:`repro.training.ddp` drives them with
    byte-budgeted buckets over an allreduce-pattern SF — see the README
    section "Bucketed gradient exchange & elastic training" for the bucket
    diagram and how to choose a byte budget.

    Every operation on this facade reports into the process-wide event
    registry of :mod:`repro.core.sflog` — the ``-log_view`` analogue: counts,
    wall time, comm volume in bytes, and split-phase overlap windows per
    event, plus ``sflog.sf_view(comm)`` for the ``PetscSFView`` structural
    dump.  Enable with ``REPRO_SF_LOG=1`` (or ``fence`` for fenced wall
    times); the README section "Observability: log_view and SFView" shows a
    sample table.  Hooks fire at dispatch time only, so jitted paths keep
    their no-retrace guarantees (``traced`` vs ``count`` in the table).

    When the SF topology is *runtime data* rather than setup-time metadata —
    MoE expert routing, where the router's top-k picks define the edge list
    every step — use :class:`repro.core.dynplan.DynPlan` instead: same
    star-forest semantics and tuned kernels, edge list as a traced argument.
    The README section "MoE routing as a star forest + the serving engine"
    maps that consumer (``models/moe.py``, ``serving/engine.py``,
    ``benchmarks/bench_serving.py``) onto this layer.
    """

    def __init__(self, sf: StarForest, backend: Optional[str] = None, *,
                 mesh=None, unit=None, **backend_kwargs):
        sf.setup()
        self.sf = sf
        name = backend if backend is not None \
            else select_backend(sf, mesh=mesh, unit=unit)
        self.backend = make_backend(name, sf, mesh=mesh, unit=unit,
                                    **backend_kwargs)
        self._bundles: Dict[Any, Any] = {}
        self._lmeta: Optional[Dict[str, Any]] = None   # sflog tag cache

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @property
    def unit(self):
        """The backend plan's payload unit spec."""
        return self.backend.unit

    # sflog plumbing ------------------------------------------------------
    def _logtags(self, op=None) -> Dict[str, Any]:
        """Static tags every event from this comm carries: backend name,
        pattern kind, cached-plan signature (computed once per comm)."""
        m = self._lmeta
        if m is None:
            plan = getattr(self.backend, "plan", None)
            if plan is None:
                plan = getattr(getattr(self.backend, "dist", None),
                               "plan", None)
            m = self._lmeta = {
                "backend": self.backend_name,
                "pattern": getattr(getattr(plan, "pattern", None),
                                   "kind", None),
                "sig": repr(plan.comm_signature())
                if hasattr(plan, "comm_signature") else None,
            }
        if op is None:
            return m
        t = dict(m)
        t["op"] = get_op(op).name
        return t

    def _payload_bytes(self, data) -> float:
        """Comm volume of one exchange: plan edges x unit row bytes of the
        actual payload (trailing dims x itemsize); works on tracers."""
        shape = getattr(data, "shape", None)
        if shape is None:
            data = np.asarray(data)
            shape = data.shape
        row = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        itemsize = np.dtype(getattr(data, "dtype", np.float32)).itemsize
        return float(self.sf.nedges_total) * row * itemsize

    # delegation ----------------------------------------------------------
    def bcast_begin(self, rootdata, op="replace"):
        if not sflog.enabled():
            return self.backend.bcast_begin(rootdata, op)
        t0 = sflog.op_begin()
        pend = self.backend.bcast_begin(rootdata, op)
        nb = self._payload_bytes(rootdata)
        tags = self._logtags(op)
        sflog.op_end("SFBcastBegin", t0, getattr(pend, "payload", None),
                     nbytes=nb, tags=tags)
        sflog.stash_pending(pend, "SFBcastEnd", nb, tags, tracing=t0 < 0)
        return pend

    def bcast_end(self, pending, leafdata):
        info = sflog.claim_pending(pending)
        if info is None:
            return self.backend.bcast_end(pending, leafdata)
        t0 = time.perf_counter()
        out = self.backend.bcast_end(pending, leafdata)
        sflog.pending_end(info, t0, out)
        return out

    def bcast(self, rootdata, leafdata, op="replace"):
        if not sflog.enabled():
            return self.backend.bcast(rootdata, leafdata, op)
        t0 = sflog.op_begin()
        out = self.backend.bcast(rootdata, leafdata, op)
        sflog.op_end("SFBcast", t0, out,
                     nbytes=self._payload_bytes(rootdata),
                     tags=self._logtags(op))
        return out

    def reduce_begin(self, leafdata, op="sum"):
        if not sflog.enabled():
            return self.backend.reduce_begin(leafdata, op)
        t0 = sflog.op_begin()
        pend = self.backend.reduce_begin(leafdata, op)
        nb = self._payload_bytes(leafdata)
        tags = self._logtags(op)
        sflog.op_end("SFReduceBegin", t0, getattr(pend, "payload", None),
                     nbytes=nb, tags=tags)
        sflog.stash_pending(pend, "SFReduceEnd", nb, tags, tracing=t0 < 0)
        return pend

    def reduce_end(self, pending, rootdata):
        info = sflog.claim_pending(pending)
        if info is None:
            return self.backend.reduce_end(pending, rootdata)
        t0 = time.perf_counter()
        out = self.backend.reduce_end(pending, rootdata)
        sflog.pending_end(info, t0, out)
        return out

    def reduce(self, leafdata, rootdata, op="sum"):
        if not sflog.enabled():
            return self.backend.reduce(leafdata, rootdata, op)
        t0 = sflog.op_begin()
        out = self.backend.reduce(leafdata, rootdata, op)
        sflog.op_end("SFReduce", t0, out,
                     nbytes=self._payload_bytes(leafdata),
                     tags=self._logtags(op))
        return out

    def fetch_and_op(self, rootdata, leafdata, op="sum"):
        if not sflog.enabled():
            return self.backend.fetch_and_op(rootdata, leafdata, op)
        t0 = sflog.op_begin()
        out = self.backend.fetch_and_op(rootdata, leafdata, op)
        # fetch-and-op moves payload both ways (fetch + update)
        sflog.op_end("SFFetchAndOp", t0, out,
                     nbytes=2.0 * self._payload_bytes(leafdata),
                     tags=self._logtags(op))
        return out

    # fused multi-field exchange (VecScatter analogue) -------------------
    def _bundle(self, fields):
        from .fields import FieldBundle
        key = tuple((tuple(int(d) for d in f.shape[1:]),
                     np.dtype(f.dtype).str) for f in fields)
        if key not in self._bundles:
            self._bundles[key] = FieldBundle.for_data(self, fields)
        return self._bundles[key]

    def bcast_multi(self, rootfields, leaffields, op="replace"):
        """Broadcast k same-pattern fields through ONE fused exchange per
        byte-compatible group (see :class:`repro.core.fields.FieldBundle`).
        Returns the list of updated leaf fields."""
        return self._bundle(rootfields).bcast_multi(rootfields, leaffields,
                                                    op)

    def reduce_multi(self, leaffields, rootfields, op="sum"):
        """Reduce k same-pattern fields through ONE fused exchange per
        fusable group.  Returns the list of updated root fields."""
        return self._bundle(leaffields).reduce_multi(leaffields, rootfields,
                                                     op)

    # split-phase multi-field exchange: the overlap window the DDP gradient
    # buckets ride (README "Bucketed gradient exchange & elastic training")
    def bcast_multi_begin(self, rootfields, op="replace"):
        """Begin half of :meth:`bcast_multi`; complete with
        :meth:`bcast_multi_end` (or ``pending.end(leaffields)``)."""
        return self._bundle(rootfields).bcast_multi_begin(rootfields, op)

    def bcast_multi_end(self, pending, leaffields):
        return pending.end(leaffields)

    def reduce_multi_begin(self, leaffields, op="sum"):
        """Begin half of :meth:`reduce_multi`: packs every fusable group and
        returns a :class:`repro.core.fields.PendingMulti`.  Compute issued
        between begin and :meth:`reduce_multi_end` is independent of the
        in-flight payloads, so the scheduler overlaps them — this is the
        primitive :mod:`repro.training.ddp` stacks gradient buckets on."""
        return self._bundle(leaffields).reduce_multi_begin(leaffields, op)

    def reduce_multi_end(self, pending, rootfields):
        return pending.end(rootfields)

    def gather(self, leafdata):
        if not sflog.enabled():
            return self.backend.gather(leafdata)
        t0 = sflog.op_begin()
        out = self.backend.gather(leafdata)
        sflog.op_end("SFGather", t0, out,
                     nbytes=self._payload_bytes(leafdata),
                     tags=self._logtags())
        return out

    def scatter(self, multirootdata, leafdata=None):
        if not sflog.enabled():
            return self.backend.scatter(multirootdata, leafdata)
        t0 = sflog.op_begin()
        out = self.backend.scatter(multirootdata, leafdata)
        sflog.op_end("SFScatter", t0, out,
                     nbytes=self._payload_bytes(multirootdata),
                     tags=self._logtags())
        return out

    def compute_degrees(self):
        return self.backend.compute_degrees()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"SFComm({self.sf!r}, backend={self.backend_name!r})"


# --------------------------------------------------------------------------
# built-in registrations
# --------------------------------------------------------------------------
def _global_factory(sf, mesh=None, plan=None, unit=None):
    return GlobalBackend(sf, plan=plan, unit=unit)


def _shardmap_factory(sf, mesh=None, **kw):
    return ShardmapBackend(sf, mesh=mesh, **kw)


def _pallas_factory(sf, mesh=None, plan=None, interpret=None, unit=None):
    return PallasBackend(sf, plan=plan, interpret=interpret, unit=unit)


register_backend("global", _global_factory)
register_backend("shardmap", _shardmap_factory)
register_backend("pallas", _pallas_factory)
