"""Distributed sparse matrices on star forests (paper §4.1, §6.4).

A ``ParCSR`` is PETSc's MPIAIJ layout (paper Fig 3): rows are block-
distributed; on each rank the local rows split into the *diagonal* block A
(columns owned by this rank) and the *off-diagonal* block B whose columns are
compacted through ``garray`` (the global ids of the nonzero off-diagonal
columns).  The ghost vector ``lvec`` holds the remote x entries B needs, and
a star forest — roots: owned x entries, leaves: lvec entries (contiguous!) —
provides all communication:

  SpMV     y = A x_local (+overlap) then  y += B lvec   after SFBcast
  SpMV^T   lvec = B^T x ; y = A^T x ; SFReduce(lvec -> y, SUM)

The contiguity of lvec's leaves means the SF's pattern analysis elides the
leaf-side unpack entirely — the paper's flagship §5.2 optimization.

Also here: SF-driven submatrix extraction (paper §4.1), SpMM (AP, PtAP —
paper §6.4) with ghost-row fetching through a section-derived dof-SF, and
COO assembly with fetch-and-add slot allocation (the SF formulation of
PETSc's MatStash used in step 3 of §6.4).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import SFComm, StarForest, compose_inverse, ragged_offsets
from ..kernels import ops as kops
from ..meshdist.section import Section, apply_section
from .csr import LocalCSR, csr_from_coo, csr_transpose, spgemm

__all__ = ["ParCSR", "Sparsity", "MatAssembler", "assemble_coo"]


def _owner_of(offsets: np.ndarray, ids: np.ndarray) -> np.ndarray:
    return np.searchsorted(offsets, ids, side="right") - 1


@dataclasses.dataclass
class _EllBlock:
    data: jnp.ndarray   # (m, K)
    cols: jnp.ndarray   # (m, K) padded -> n (trailing zero of x)
    n: int

    def apply(self, x: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
        """y = block @ x.  ``x`` may carry trailing RHS-column dims
        ``(n, *unit)``; the contraction broadcasts over them (the Pallas ELL
        kernel is single-vector, so multi-RHS takes the einsum path)."""
        xz = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
        if use_kernel and x.ndim == 1:
            return kops.spmv_ell(self.data, self.cols, xz)
        return jnp.einsum("nk,nk...->n...", self.data,
                          jnp.take(xz, self.cols, axis=0))


class ParCSR:
    """Row-distributed sparse matrix with SF-based ghost communication."""

    def __init__(self, nranks: int, row_offsets: np.ndarray,
                 col_offsets: np.ndarray, diag: List[LocalCSR],
                 offd: List[LocalCSR], garray: List[np.ndarray],
                 dtype=np.float32, backend=None):
        self.nranks = nranks
        self.row_offsets = np.asarray(row_offsets, dtype=np.int64)
        self.col_offsets = np.asarray(col_offsets, dtype=np.int64)
        self.diag = diag
        self.offd = offd
        self.garray = garray
        self.dtype = dtype

        # ---- the SpMV star forest (paper §4.1): roots = owned x entries,
        # leaves = lvec entries, contiguous on each rank.
        sf = StarForest(nranks)
        for r in range(nranks):
            ncols_local = int(self.col_offsets[r + 1] - self.col_offsets[r])
            g = self.garray[r]
            owner = _owner_of(self.col_offsets, g)
            remote = np.stack([owner, g - self.col_offsets[owner]], axis=1) \
                if g.size else np.zeros((0, 2), np.int64)
            sf.set_graph(r, ncols_local, None, remote,
                         nleafspace=max(int(g.size), 1))
        self.sf = sf.setup()
        # backend=None -> measurement-driven auto-selection (priors table
        # + tuned Pallas kernels; see repro.core.backend.select_backend)
        self.comm = SFComm(self.sf, backend=backend)
        self.lvec_offsets = ragged_offsets(
            [self.sf.graph(r).nleafspace for r in range(nranks)])

        self._diag_ell = [self._ell(c) for c in self.diag]
        self._offd_ell = [self._ell(c) for c in self.offd]
        self._diag_t_ell = [self._ell(csr_transpose(c)) for c in self.diag]
        self._offd_t_ell = [self._ell(csr_transpose(c)) for c in self.offd]

    def _ell(self, c: LocalCSR) -> _EllBlock:
        data, cols, _ = c.to_ell(dtype=self.dtype)
        return _EllBlock(jnp.asarray(data), jnp.asarray(cols), c.shape[1])

    # ------------------------------------------------------------ factory
    @staticmethod
    def from_global_coo(nranks: int, m: int, n: int, rows: np.ndarray,
                        cols: np.ndarray, vals: np.ndarray,
                        row_offsets: Optional[np.ndarray] = None,
                        col_offsets: Optional[np.ndarray] = None,
                        dtype=np.float32, backend=None) -> "ParCSR":
        if row_offsets is None:
            row_offsets = np.linspace(0, m, nranks + 1).astype(np.int64)
        if col_offsets is None:
            col_offsets = np.linspace(0, n, nranks + 1).astype(np.int64)
        diag, offd, garray = [], [], []
        rows = np.asarray(rows); cols = np.asarray(cols); vals = np.asarray(vals)
        for r in range(nranks):
            r0, r1 = row_offsets[r], row_offsets[r + 1]
            c0, c1 = col_offsets[r], col_offsets[r + 1]
            sel = (rows >= r0) & (rows < r1)
            rr, cc, vv = rows[sel] - r0, cols[sel], vals[sel]
            on = (cc >= c0) & (cc < c1)
            diag.append(csr_from_coo(int(r1 - r0), int(c1 - c0),
                                     rr[on], cc[on] - c0, vv[on]))
            goff = np.unique(cc[~on])
            cmap = {int(g): i for i, g in enumerate(goff)}
            offd.append(csr_from_coo(int(r1 - r0), max(goff.size, 1),
                                     rr[~on],
                                     np.asarray([cmap[int(c)] for c in cc[~on]],
                                                dtype=np.int64),
                                     vv[~on]))
            garray.append(goff.astype(np.int64))
        return ParCSR(nranks, row_offsets, col_offsets, diag, offd, garray,
                      dtype=dtype, backend=backend)

    @staticmethod
    def from_dmda_stencil(da, coeffs: Optional[Sequence[float]] = None,
                          dtype=np.float32, backend=None) -> "ParCSR":
        """Stencil operator on a :class:`repro.meshdist.dmda.DMDA` grid.

        One matrix row per grid cell (DMDA *global* ordering, so the row/col
        distribution is exactly the DMDA's owned decomposition and the SpMV
        ghost SF reproduces the DMDA halo).  ``coeffs`` aligns with
        ``da.stencil_offsets()`` (center first); default is the
        row-sum-zero Laplacian: +deg at the center, -1 per neighbor.
        Off-domain neighbors of non-periodic boundaries are dropped
        (homogeneous Dirichlet).  ``backend`` names the SpMV ghost SF's
        backend (auto-selected when ``None``).
        """
        offs = da.stencil_offsets()
        if coeffs is None:
            coeffs = np.concatenate([[float(offs.shape[0] - 1)],
                                     -np.ones(offs.shape[0] - 1)])
        coeffs = np.asarray(coeffs, dtype=np.float64)
        if coeffs.shape[0] != offs.shape[0]:
            raise ValueError(f"{coeffs.shape[0]} coeffs for "
                             f"{offs.shape[0]} stencil offsets")
        rows_l, cols_l, vals_l = [], [], []
        for r in range(da.nranks):
            nat = da.box_coords(da.owned_box(r))
            row = da.owned_offsets[r] + np.arange(nat.shape[0])
            for o, c in zip(offs, coeffs):
                nb, valid = da.wrap_coords(nat + o)
                if not valid.any():
                    continue
                rows_l.append(row[valid])
                cols_l.append(da.natural_to_global(nb[valid]))
                vals_l.append(np.full(int(valid.sum()), float(c)))
        n = da.nglobal
        return ParCSR.from_global_coo(
            da.nranks, n, n,
            np.concatenate(rows_l), np.concatenate(cols_l),
            np.concatenate(vals_l),
            row_offsets=da.owned_offsets, col_offsets=da.owned_offsets,
            dtype=dtype, backend=backend)

    @property
    def shape(self) -> Tuple[int, int]:
        return int(self.row_offsets[-1]), int(self.col_offsets[-1])

    def diagonal(self) -> np.ndarray:
        """Main-diagonal entries (MatGetDiagonal) — purely local: entry
        (i, i) always lives in the owner's diagonal block when row and
        column distributions agree (square MPIAIJ layout)."""
        m, n = self.shape
        out = np.zeros(m, dtype=np.float64)
        for r in range(self.nranks):
            r0 = int(self.row_offsets[r]); c0 = int(self.col_offsets[r])
            A = self.diag[r]
            for i in range(A.shape[0]):
                lc = r0 + i - c0
                if not (0 <= lc < A.shape[1]):
                    continue
                s, e = int(A.indptr[i]), int(A.indptr[i + 1])
                hit = np.flatnonzero(A.indices[s:e] == lc)
                if hit.size:
                    out[r0 + i] = float(A.data[s:e][hit].sum())
        return out

    def toarray(self) -> np.ndarray:
        m, n = self.shape
        out = np.zeros((m, n))
        for r in range(self.nranks):
            r0 = int(self.row_offsets[r]); c0 = int(self.col_offsets[r])
            out[r0: int(self.row_offsets[r + 1]),
                c0: int(self.col_offsets[r + 1])] += self.diag[r].toarray()
            B = self.offd[r].toarray()
            for j, g in enumerate(self.garray[r]):
                out[r0: int(self.row_offsets[r + 1]), int(g)] += B[:, j]
        return out

    # ------------------------------------------------------------- SpMV
    def spmv(self, x: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
        """y = M x with communication/compute overlap — the paper's listing:

            PetscSFBcastBegin(sf, x, lvec, MPI_REPLACE);
            y = A*x;                       // local, overlapped
            PetscSFBcastEnd(sf, x, lvec, MPI_REPLACE);
            y += B*lvec;

        ``x`` may be ``(n,)`` or multi-RHS ``(n, k)``: the k ghost columns
        travel as ONE bcast of unit ``(k,)`` instead of k exchanges (the
        fused multi-field insight of :mod:`repro.core.fields`).
        """
        x = jnp.asarray(x)
        pend = self.comm.bcast_begin(x, "replace")
        y_parts = []
        for r in range(self.nranks):
            c0, c1 = int(self.col_offsets[r]), int(self.col_offsets[r + 1])
            y_parts.append(self._diag_ell[r].apply(x[c0:c1], use_kernel))
        y = jnp.concatenate(y_parts)
        lvec = pend.end(jnp.zeros((self.sf.nleafspace_total,) + x.shape[1:],
                                  x.dtype))
        y2 = []
        for r in range(self.nranks):
            l0, l1 = int(self.lvec_offsets[r]), int(self.lvec_offsets[r + 1])
            y2.append(self._offd_ell[r].apply(lvec[l0:l1], use_kernel))
        return y + jnp.concatenate(y2)

    def spmv_multi(self, X: jnp.ndarray, use_kernel: bool = False
                   ) -> jnp.ndarray:
        """Multi-RHS SpMV ``Y = M X`` for ``X`` of shape ``(n, k)``: all k
        columns' halos move through one fused ghost exchange."""
        X = jnp.asarray(X)
        if X.ndim != 2:
            raise ValueError(f"spmv_multi expects (n, k), got {X.shape}")
        return self.spmv(X, use_kernel)

    def spmv_transpose(self, x: jnp.ndarray, use_kernel: bool = False
                       ) -> jnp.ndarray:
        """y = M^T x:  y = A^T x ; lvec = B^T x ; SFReduce(lvec -> y, SUM)."""
        y_parts, l_parts = [], []
        for r in range(self.nranks):
            r0, r1 = int(self.row_offsets[r]), int(self.row_offsets[r + 1])
            y_parts.append(self._diag_t_ell[r].apply(x[r0:r1], use_kernel))
            l_parts.append(self._offd_t_ell[r].apply(x[r0:r1], use_kernel))
        y = jnp.concatenate(y_parts)
        lvec_parts = []
        for r in range(self.nranks):
            nls = self.sf.graph(r).nleafspace
            lp = l_parts[r]
            if lp.shape[0] < nls:   # offd block may be the 1-col placeholder
                lp = jnp.zeros((nls,), y.dtype).at[: lp.shape[0]].set(lp)
            lvec_parts.append(lp[:nls])
        lvec = jnp.concatenate(lvec_parts)
        return self.comm.reduce(lvec, y, "sum")

    # ------------------------------------------------- ghost-row fetching
    def _row_sf(self, wanted: List[np.ndarray],
                row_offsets: Optional[np.ndarray] = None) -> StarForest:
        """SF whose roots are matrix rows and leaves the requested rows."""
        ro = self.row_offsets if row_offsets is None else row_offsets
        sf = StarForest(self.nranks)
        for r in range(self.nranks):
            w = np.asarray(wanted[r], dtype=np.int64)
            owner = _owner_of(ro, w)
            remote = np.stack([owner, w - ro[owner]], axis=1) if w.size \
                else np.zeros((0, 2), np.int64)
            nroots = int(ro[r + 1] - ro[r])
            sf.set_graph(r, nroots, None, remote, nleafspace=max(w.size, 1))
        return sf.setup()

    def fetch_rows(self, wanted: List[np.ndarray]
                   ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Fetch full rows (global columns) of self for each rank's ``wanted``
        global row list.  Rows are communicated through a dof-SF derived by
        applying the nnz-per-row Section to the row SF (paper §4.2 style).
        Returns per rank (indptr, cols, vals) of the fetched rows."""
        R = self.nranks
        row_sf = self._row_sf(wanted)
        # per-rank merged local rows in global column space
        merged: List[LocalCSR] = []
        for r in range(R):
            A, B, g = self.diag[r], self.offd[r], self.garray[r]
            c0 = int(self.col_offsets[r])
            m = A.shape[0]
            rows = np.concatenate([np.repeat(np.arange(m), np.diff(A.indptr)),
                                   np.repeat(np.arange(m), np.diff(B.indptr))])
            cols = np.concatenate([A.indices + c0,
                                   g[B.indices] if B.nnz else np.zeros(0, np.int64)])
            vals = np.concatenate([A.data, B.data])
            merged.append(csr_from_coo(m, self.shape[1], rows, cols, vals))
        sections = [Section.from_sizes(np.diff(merged[r].indptr)) for r in range(R)]
        dof_sf = apply_section(row_sf, sections)
        dops = SFComm(dof_sf)
        root_cols = np.concatenate([m.indices for m in merged]) \
            if sum(m.nnz for m in merged) else np.zeros(0, np.int64)
        root_vals = np.concatenate([m.data for m in merged]) \
            if sum(m.nnz for m in merged) else np.zeros(0, np.float64)
        nls = dof_sf.nleafspace_total
        leaf_cols = np.asarray(dops.bcast(root_cols, np.zeros(nls, np.int64),
                                          "replace"))
        leaf_vals = np.asarray(dops.bcast(
            jnp.asarray(root_vals.astype(np.float32)),
            jnp.zeros(nls, jnp.float32), "replace"))
        # also bcast row sizes over the row SF to rebuild indptrs
        pops = SFComm(row_sf)
        root_sizes = np.concatenate([s.sizes for s in sections])
        lsizes = np.asarray(pops.bcast(root_sizes,
                                       np.zeros(row_sf.nleafspace_total, np.int64),
                                       "replace"))
        out = []
        lo = row_sf.leaf_offsets()
        dlo = dof_sf.leaf_offsets()
        for r in range(R):
            sz = lsizes[lo[r]: lo[r] + len(np.asarray(wanted[r]))]
            indptr = np.zeros(sz.shape[0] + 1, dtype=np.int64)
            np.cumsum(sz, out=indptr[1:])
            c = leaf_cols[dlo[r]: dlo[r + 1]][: indptr[-1]]
            v = leaf_vals[dlo[r]: dlo[r + 1]][: indptr[-1]]
            out.append((indptr, c, v))
        return out

    # ------------------------------------------------------------- SpMM
    def spmm(self, P: "ParCSR") -> "ParCSR":
        """AP = self @ P (paper §6.4): fetch ghost rows of P named by garray,
        then purely local products — step 3 assembly is row-local for AP."""
        R = self.nranks
        fetched = P.fetch_rows(self.garray)   # step 1: ghost rows of P
        rows_l, cols_l, vals_l = [], [], []
        for r in range(R):
            c0 = int(self.col_offsets[r])
            # local block of P (rows owned by r), global columns
            indptr, cols, vals = fetched[r]
            Pf = csr_from_coo(
                len(self.garray[r]), P.shape[1],
                np.repeat(np.arange(len(self.garray[r])), np.diff(indptr)),
                cols, vals)
            m = self.diag[r].shape[0]
            Pl_ip, Pl_c, Pl_v = self._local_rows_global_cols(P, r)
            Pl = csr_from_coo(self.diag[r].shape[1], P.shape[1],
                              np.repeat(np.arange(self.diag[r].shape[1]),
                                        np.diff(Pl_ip)), Pl_c, Pl_v)
            APr = spgemm(self.diag[r], Pl)
            if self.offd[r].nnz:
                AP2 = spgemm(self.offd[r], Pf)
                APr = _csr_add(APr, AP2)
            r0 = int(self.row_offsets[r])
            rows_l.append(np.repeat(np.arange(m), np.diff(APr.indptr)) + r0)
            cols_l.append(APr.indices)
            vals_l.append(APr.data)
        rows = np.concatenate(rows_l); cols = np.concatenate(cols_l)
        vals = np.concatenate(vals_l)
        return ParCSR.from_global_coo(R, self.shape[0], P.shape[1], rows, cols,
                                      vals, row_offsets=self.row_offsets,
                                      col_offsets=P.col_offsets,
                                      dtype=self.dtype)

    def _local_rows_global_cols(self, M: "ParCSR", r: int):
        A, B, g = M.diag[r], M.offd[r], M.garray[r]
        c0 = int(M.col_offsets[r])
        m = A.shape[0]
        rows = np.concatenate([np.repeat(np.arange(m), np.diff(A.indptr)),
                               np.repeat(np.arange(m), np.diff(B.indptr))])
        cols = np.concatenate([A.indices + c0,
                               g[B.indices] if B.nnz else np.zeros(0, np.int64)])
        vals = np.concatenate([A.data, B.data])
        csr = csr_from_coo(m, M.shape[1], rows, cols, vals)
        return csr.indptr, csr.indices, csr.data

    def ptap(self, P: "ParCSR") -> "ParCSR":
        """Galerkin product P^T (self) P (paper §6.4, Fig 12 right).

        Local P_r^T @ (AP)_r yields contributions to rows owned by *other*
        ranks (P's columns); they are routed with the COO assembly SF below
        — fetch-and-add slot allocation + reduce, PETSc's MatStash on SF."""
        AP = self.spmm(P)
        R = self.nranks
        trips: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        for r in range(R):
            ip, c, v = self._local_rows_global_cols(AP, r)
            APl = csr_from_coo(AP.diag[r].shape[0], AP.shape[1],
                               np.repeat(np.arange(AP.diag[r].shape[0]),
                                         np.diff(ip)), c, v)
            ipP, cP, vP = self._local_rows_global_cols(P, r)
            Pl = csr_from_coo(P.diag[r].shape[0], P.shape[1],
                              np.repeat(np.arange(P.diag[r].shape[0]),
                                        np.diff(ipP)), cP, vP)
            Pt = csr_transpose(Pl)   # (P global cols) x (local rows)
            prod = spgemm(Pt, APl)   # rows: global P cols; cols: global
            rows = np.repeat(np.arange(prod.shape[0]), np.diff(prod.indptr))
            trips.append((rows, prod.indices, prod.data))
        return assemble_coo(R, P.shape[1], AP.shape[1], trips,
                            row_offsets=P.col_offsets,
                            col_offsets=P.col_offsets
                            if P.shape[1] == AP.shape[1] else None,
                            dtype=self.dtype)


def _csr_add(a: LocalCSR, b: LocalCSR) -> LocalCSR:
    m, n = a.shape
    rows = np.concatenate([np.repeat(np.arange(m), np.diff(a.indptr)),
                           np.repeat(np.arange(m), np.diff(b.indptr))])
    cols = np.concatenate([a.indices, b.indices])
    vals = np.concatenate([a.data, b.data])
    return csr_from_coo(m, n, rows, cols, vals)


def _value_bits(vals: np.ndarray) -> np.ndarray:
    """Bit-pattern view of a float array, used as a tie-break sort key so
    duplicate-entry sums run in a value-canonical (insert-order-free)
    sequence."""
    vals = np.ascontiguousarray(vals)
    return vals.view({2: np.uint16, 4: np.uint32,
                      8: np.uint64}[vals.dtype.itemsize])


def _canonical_sum(keys: np.ndarray, vals: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Sum ``vals`` grouped by integer ``keys`` in a canonical order:
    entries are sorted by (key, value bits) and summed left-to-right per
    group (``np.add.reduceat``), so the result is bitwise independent of
    the caller's insertion order — the sorted-segment reduction invariant
    of ``core/redplan.py`` applied on the host."""
    if keys.size == 0:
        return keys.copy(), vals.copy()
    order = np.lexsort((_value_bits(vals), keys))
    ks, vs = keys[order], vals[order]
    starts = np.concatenate([[0], np.flatnonzero(np.diff(ks)) + 1])
    return ks[starts], np.add.reduceat(vs, starts)


class Sparsity:
    """Preallocated distributed sparsity pattern (MatPreallocator / pyop2
    ``Sparsity``).

    The global set of (row, col) positions is dedup'd once; each owner
    rank stores its entries in canonical (local row, global col) order —
    the *slot* numbering all inserts resolve against.  Row blocks are
    contiguous in slot space, which is exactly what lets the stash flush
    ride a Section-derived dof-SF (nnz-per-row sizes) in
    :class:`MatAssembler`.
    """

    def __init__(self, nranks: int, m: int, n: int,
                 rows: np.ndarray, cols: np.ndarray,
                 row_offsets: Optional[np.ndarray] = None,
                 col_offsets: Optional[np.ndarray] = None,
                 dtype=np.float32):
        self.nranks = int(nranks)
        self.m, self.n = int(m), int(n)
        if row_offsets is None:
            row_offsets = np.linspace(0, m, nranks + 1).astype(np.int64)
        self.row_offsets = np.asarray(row_offsets, dtype=np.int64)
        self.col_offsets = col_offsets
        self.dtype = np.dtype(dtype)
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        if rows.size and (rows.min() < 0 or rows.max() >= m):
            raise ValueError("row index out of range")
        if cols.size and (cols.min() < 0 or cols.max() >= n):
            raise ValueError("col index out of range")
        keys = np.unique(rows * n + cols)        # sorted (row, col) pairs
        urows = keys // n
        owner = _owner_of(self.row_offsets, urows)
        # per-owner canonical slot arrays (key-sorted => row-major blocks)
        self.keys: List[np.ndarray] = []
        self.rows_of: List[np.ndarray] = []
        self.cols_of: List[np.ndarray] = []
        self.row_nnz: List[np.ndarray] = []
        self.row_slot_start: List[np.ndarray] = []
        for p in range(self.nranks):
            k = keys[owner == p]
            self.keys.append(k)
            self.rows_of.append(k // n)
            self.cols_of.append(k % n)
            nrows = int(self.row_offsets[p + 1] - self.row_offsets[p])
            lr = self.rows_of[p] - self.row_offsets[p]
            cnt = np.bincount(lr, minlength=nrows).astype(np.int64) \
                if nrows else np.zeros(0, np.int64)
            self.row_nnz.append(cnt)
            self.row_slot_start.append(ragged_offsets(cnt.tolist())[:-1])
        self.nnz = np.asarray([k.size for k in self.keys], dtype=np.int64)
        self.slot_offsets = ragged_offsets(self.nnz.tolist())

    @property
    def nnz_total(self) -> int:
        return int(self.slot_offsets[-1])

    def owner_of_rows(self, rows: np.ndarray) -> np.ndarray:
        return _owner_of(self.row_offsets, np.asarray(rows, dtype=np.int64))

    def lookup(self, rows: np.ndarray, cols: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
        """(owner rank, owner-local slot) of each (row, col); raises
        ``KeyError`` for positions not preallocated."""
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        owner = self.owner_of_rows(rows)
        key = rows * self.n + cols
        slot = np.empty(rows.shape[0], dtype=np.int64)
        for p in np.unique(owner):
            sel = owner == p
            idx = np.searchsorted(self.keys[p], key[sel])
            idx = np.minimum(idx, max(self.keys[p].size - 1, 0))
            ok = self.keys[p].size and \
                (self.keys[p][idx] == key[sel]).all()
            if not ok:
                bad = np.flatnonzero(self.keys[p][idx] != key[sel]) \
                    if self.keys[p].size else np.arange(sel.sum())
                r0, c0 = rows[sel][bad[0]], cols[sel][bad[0]]
                raise KeyError(f"entry ({int(r0)}, {int(c0)}) not in the "
                               "preallocated sparsity")
            slot[sel] = idx
        return owner, slot

    def to_parcsr(self, slot_values: np.ndarray,
                  backend: Optional[str] = None) -> ParCSR:
        """Materialize a ParCSR from the concatenated per-owner slot-value
        array (length ``nnz_total``)."""
        vals = np.asarray(slot_values)
        rows = np.concatenate(self.rows_of) if self.nnz_total else \
            np.zeros(0, np.int64)
        cols = np.concatenate(self.cols_of) if self.nnz_total else \
            np.zeros(0, np.int64)
        return ParCSR.from_global_coo(
            self.nranks, self.m, self.n, rows, cols,
            vals.astype(np.float64), row_offsets=self.row_offsets,
            col_offsets=self.col_offsets, dtype=self.dtype, backend=backend)


class MatAssembler:
    """Stash-based parallel assembly (PETSc MatStash / pyop2 ``Mat``).

    ``add_values(rank, ...)`` resolves owned-row contributions to slots
    immediately (pure local writes); off-process triplets accumulate in a
    per-rank *stash*.  ``assemble()`` flushes every stash with **one** SF
    reduce whose graph is built by :func:`repro.core.compose.compose_inverse`
    over the row-ownership dof-SF — replacing the counting-SF + staging-SF
    all-to-all of the legacy ``assemble_coo`` path:

      row SF (roots = owned matrix rows, leaves = ranks' stashed rows)
        --apply_section(nnz per row)-->  dof SF (roots = owner nnz slots)
        --compose_inverse(dof SF, stash entry SF)-->  flush SF
            (roots = owner slots, leaves = stash entries)

    Duplicate inserts are pre-summed per rank in a value-canonical order
    (:func:`_canonical_sum`), and the SF reduce itself runs in the
    deterministic (leaf rank, edge index) order of ``core/redplan.py`` —
    the assembled matrix is bitwise independent of insertion order.
    """

    def __init__(self, sparsity: Sparsity, backend: Optional[str] = None):
        self.sparsity = sparsity
        self.backend = backend
        R = sparsity.nranks
        self._local: List[List[Tuple[np.ndarray, np.ndarray]]] = \
            [[] for _ in range(R)]
        self._stash: List[List[Tuple[np.ndarray, np.ndarray, np.ndarray]]] = \
            [[] for _ in range(R)]
        self._flush_cache: Optional[Tuple[tuple, StarForest, List[int]]] = None
        self.stats = {"local_inserts": 0, "stashed_inserts": 0, "flushes": 0}

    def add_values(self, rank: int, rows: np.ndarray, cols: np.ndarray,
                   vals: np.ndarray) -> None:
        """Insert COO contributions from ``rank`` (ADD_VALUES semantics)."""
        sp = self.sparsity
        rows = np.asarray(rows, dtype=np.int64).reshape(-1)
        cols = np.asarray(cols, dtype=np.int64).reshape(-1)
        vals = np.asarray(vals, dtype=sp.dtype).reshape(-1)
        if not (rows.size == cols.size == vals.size):
            raise ValueError("rows/cols/vals length mismatch")
        owner = sp.owner_of_rows(rows)
        mine = owner == rank
        if mine.any():
            _, slot = sp.lookup(rows[mine], cols[mine])
            self._local[rank].append((slot, vals[mine]))
            self.stats["local_inserts"] += int(mine.sum())
        rest = ~mine
        if rest.any():
            sp.lookup(rows[rest], cols[rest])   # fail fast on bad pattern
            self._stash[rank].append((rows[rest], cols[rest], vals[rest]))
            self.stats["stashed_inserts"] += int(rest.sum())

    # ------------------------------------------------------------- flush
    def _stash_partials(self) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """Per-rank (sorted distinct stash keys, canonical partial sums)."""
        sp = self.sparsity
        keys_q, vals_q = [], []
        for q in range(sp.nranks):
            if self._stash[q]:
                r = np.concatenate([s[0] for s in self._stash[q]])
                c = np.concatenate([s[1] for s in self._stash[q]])
                v = np.concatenate([s[2] for s in self._stash[q]])
                k, pv = _canonical_sum(r * sp.n + c, v)
            else:
                k = np.zeros(0, np.int64)
                pv = np.zeros(0, sp.dtype)
            keys_q.append(k)
            vals_q.append(pv)
        return keys_q, vals_q

    def _flush_sf(self, keys_q: List[np.ndarray]) -> StarForest:
        """The stash-flush SF, built by compose_inverse and cached on the
        stash pattern (time-stepping re-assemblies reuse it)."""
        sig = tuple(k.tobytes() for k in keys_q)
        if self._flush_cache is not None and self._flush_cache[0] == sig:
            return self._flush_cache[1]
        sp = self.sparsity
        R = sp.nranks
        # row-ownership SF over each rank's distinct stashed rows
        row_sf = StarForest(R)
        urows_q = [np.unique(k // sp.n) for k in keys_q]
        for q in range(R):
            w = urows_q[q]
            owner = sp.owner_of_rows(w)
            remote = np.stack([owner, w - sp.row_offsets[owner]], axis=1) \
                if w.size else np.zeros((0, 2), np.int64)
            row_sf.set_graph(q, int(sp.row_offsets[q + 1]
                                    - sp.row_offsets[q]),
                             None, remote, nleafspace=max(w.size, 1))
        row_sf.setup()
        # nnz-per-row Section -> dof SF whose roots ARE the owner slots
        sections = [Section(sp.row_nnz[p],
                            np.concatenate([sp.row_slot_start[p],
                                            [sp.nnz[p]]]))
                    for p in range(R)]
        dof_sf = apply_section(row_sf, sections)
        # stash-entry SF: every stash entry is a root whose single leaf
        # sits at its (row block, col position) in the dof-SF leaf space
        owner_all = [sp.owner_of_rows(u) for u in urows_q]
        B = StarForest(R)
        for q in range(R):
            k = keys_q[q]
            if k.size:
                rows = k // sp.n
                cols = k % sp.n
                own, slot = sp.lookup(rows, cols)
                rowpos = np.searchsorted(urows_q[q], rows)
                nnz_of = np.asarray(
                    [sp.row_nnz[int(p)][int(r - sp.row_offsets[p])]
                     for p, r in zip(owner_all[q], urows_q[q])],
                    dtype=np.int64)
                block_start = ragged_offsets(nnz_of.tolist())[:-1]
                colpos = slot - np.asarray(
                    [sp.row_slot_start[int(p)][int(r - sp.row_offsets[p])]
                     for p, r in zip(own, rows)], dtype=np.int64)
                local = block_start[rowpos] + colpos
                remote = np.stack([np.full(k.size, q, np.int64),
                                   np.arange(k.size, dtype=np.int64)],
                                  axis=1)
            else:
                local = np.zeros(0, np.int64)
                remote = np.zeros((0, 2), np.int64)
            B.set_graph(q, int(k.size), local, remote,
                        nleafspace=dof_sf.graph(q).nleafspace)
        flush_sf = compose_inverse(dof_sf, B)
        self._flush_cache = (sig, flush_sf, [int(k.size) for k in keys_q])
        return flush_sf

    def assemble(self, backend: Optional[str] = None) -> ParCSR:
        """Drain all buffered inserts into a :class:`ParCSR`.

        Local contributions are segment-summed into the owner slot arrays
        on the host; the off-process stash moves with exactly ONE
        ``SFComm.reduce`` over the compose_inverse flush SF.
        """
        sp = self.sparsity
        R = sp.nranks
        # 1) local canonical partials -> slot arrays
        root = np.zeros(sp.nnz_total, dtype=sp.dtype)
        for p in range(R):
            if not self._local[p]:
                continue
            slots = np.concatenate([s for s, _ in self._local[p]])
            vals = np.concatenate([v for _, v in self._local[p]])
            us, sums = _canonical_sum(slots, vals)
            root[sp.slot_offsets[p] + us] += sums
        # 2) per-rank stash partials + 3) the ONE flush reduce
        keys_q, vals_q = self._stash_partials()
        flush_sf = self._flush_sf(keys_q)
        lo = flush_sf.leaf_offsets()
        leaf = np.zeros(max(flush_sf.nleafspace_total, 1), dtype=sp.dtype)
        for q in range(R):
            leaf[lo[q]: lo[q] + vals_q[q].size] = vals_q[q]
        comm = SFComm(flush_sf, backend=backend or self.backend)
        out = np.asarray(comm.reduce(
            jnp.asarray(leaf[:flush_sf.nleafspace_total]),
            jnp.asarray(root), "sum"))
        self.stats["flushes"] += 1
        # drain buffers; the sparsity and cached flush SF stay reusable
        self._local = [[] for _ in range(R)]
        self._stash = [[] for _ in range(R)]
        return sp.to_parcsr(out, backend=backend or self.backend)


def assemble_coo(nranks: int, m: int, n: int,
                 triplets: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                 row_offsets: Optional[np.ndarray] = None,
                 col_offsets: Optional[np.ndarray] = None,
                 dtype=np.float32, method: str = "stash") -> ParCSR:
    """Distributed COO assembly via star forests (paper §6.4 step 3).

    ``method="stash"`` (default): derive a :class:`Sparsity` from the
    union pattern and flush through :class:`MatAssembler` — all
    off-process values move in ONE compose_inverse-built SF reduce.

    ``method="fetch"`` keeps the legacy 3-step path:

    1. A *counting SF* (one counter root per rank) + FetchAndOp(SUM) assigns
       every triplet a staging slot on its owner rank — the paper's
       fetch-and-add offset allocation.
    2. A *staging SF* (roots = allocated slots) routes (row, col, val) with
       three REPLACE reduces.
    3. Owners build their local CSR from the staged COO.
    """
    if method not in ("stash", "fetch"):
        raise ValueError(f"unknown assembly method {method!r}")
    if method == "stash":
        rows_all = np.concatenate([np.asarray(t[0], dtype=np.int64)
                                   for t in triplets]) \
            if triplets else np.zeros(0, np.int64)
        cols_all = np.concatenate([np.asarray(t[1], dtype=np.int64)
                                   for t in triplets]) \
            if triplets else np.zeros(0, np.int64)
        sp = Sparsity(nranks, m, n, rows_all, cols_all,
                      row_offsets=row_offsets, col_offsets=col_offsets,
                      dtype=dtype)
        asm = MatAssembler(sp)
        for q, t in enumerate(triplets):
            asm.add_values(q, t[0], t[1], t[2])
        return asm.assemble()
    if row_offsets is None:
        row_offsets = np.linspace(0, m, nranks + 1).astype(np.int64)
    row_offsets = np.asarray(row_offsets, dtype=np.int64)

    owners = [np.searchsorted(row_offsets, np.asarray(t[0]), side="right") - 1
              for t in triplets]
    # --- 1) counting SF: rank p owns one counter (root); each triplet is a
    # leaf connected to its owner's counter.
    csf = StarForest(nranks)
    for q in range(nranks):
        t = owners[q]
        remote = np.stack([t, np.zeros_like(t)], axis=1) if t.size \
            else np.zeros((0, 2), np.int64)
        csf.set_graph(q, 1, None, remote, nleafspace=max(t.size, 1))
    csf.setup()
    cops = SFComm(csf)
    root0 = jnp.zeros((nranks,), jnp.int32)
    ones = jnp.ones((csf.nleafspace_total,), jnp.int32)
    totals, slots = cops.fetch_and_op(root0, ones, "sum")
    totals = np.asarray(totals)
    slots = np.asarray(slots)
    lo = csf.leaf_offsets()

    # --- 2) staging SF: roots = totals[r] slots on rank r
    ssf = StarForest(nranks)
    for q in range(nranks):
        t = owners[q]
        s = slots[lo[q]: lo[q] + t.size]
        remote = np.stack([t, s], axis=1) if t.size else np.zeros((0, 2), np.int64)
        ssf.set_graph(q, int(totals[q]), None, remote,
                      nleafspace=max(t.size, 1))
    ssf.setup()
    sops = SFComm(ssf)
    nstage = ssf.nroots_total

    def route(vals, dt):
        leaf = np.zeros(ssf.nleafspace_total, dtype=dt)
        for q in range(nranks):
            v = np.asarray(vals[q], dtype=dt)
            leaf[lo[q]: lo[q] + v.size] = v
        return np.asarray(sops.reduce(jnp.asarray(leaf),
                                      jnp.zeros(nstage, dt), "replace"))

    rows_g = route([t[0] for t in triplets], np.int64)
    cols_g = route([t[1] for t in triplets], np.int64)
    vals_g = route([t[2] for t in triplets], np.float64)

    # --- 3) local CSR per rank from staged COO
    so = ragged_offsets(totals.tolist())
    rows_all, cols_all, vals_all = [], [], []
    for r in range(nranks):
        rows_all.append(rows_g[so[r]: so[r + 1]])
        cols_all.append(cols_g[so[r]: so[r + 1]])
        vals_all.append(vals_g[so[r]: so[r + 1]])
    rows = np.concatenate(rows_all) if rows_all else np.zeros(0, np.int64)
    cols = np.concatenate(cols_all) if cols_all else np.zeros(0, np.int64)
    vals = np.concatenate(vals_all) if vals_all else np.zeros(0, np.float64)
    return ParCSR.from_global_coo(nranks, m, n, rows, cols, vals,
                                  row_offsets=row_offsets,
                                  col_offsets=col_offsets, dtype=dtype)
