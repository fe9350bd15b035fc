"""Plain reference for the DMDA cells, from the grid's geometry alone.

Imports nothing of the program.  The grid, its split over ranks and the
orderings follow PETSc's DMDA: ranks own balanced boxes of a row-major
process grid; *global* order concatenates the owned boxes in rank order
(row-major inside each box); each rank's *local* array is its owned box
widened by the stencil width (clipped at a non-periodic boundary), in
row-major order, ranks concatenated.  With a star stencil a local position
outside the owned box in two or more dimensions is a hole that no exchange
touches (it stays 0).

* ``global_to_local`` (replace): each connected local position takes the
  value of the cell it shows.
* ``local_to_global`` (sum): each cell gets the sum of every connected
  local position that shows it.
* The operator: ``center`` on the diagonal and ``neighbor`` for each face
  neighbour inside the domain (homogeneous Dirichlet), applied matrix-free
  on the natural grid; CG is the textbook unpreconditioned iteration.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Geometry"]


def _splits(extent: int, parts: int) -> np.ndarray:
    base, rem = divmod(extent, parts)
    sizes = np.full(parts, base, dtype=np.int64)
    sizes[:rem] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


class Geometry:
    def __init__(self, grid: Sequence[int], proc_grid: Sequence[int], *,
                 width: int = 1, stencil: str = "star",
                 periodic: bool = False):
        if stencil != "star" or periodic:
            raise ValueError("the reference covers non-periodic star "
                             "stencils only")
        self.grid = tuple(int(e) for e in grid)
        self.procs = tuple(int(p) for p in proc_grid)
        self.width = int(width)
        nd = len(self.grid)
        self.splits = [_splits(e, p) for e, p in zip(self.grid, self.procs)]
        self.n = int(np.prod(self.grid))
        # global id of every natural cell
        coords = np.indices(self.grid).reshape(nd, -1)
        rc = [np.searchsorted(s, c, side="right") - 1
              for s, c in zip(self.splits, coords)]
        ext = [np.diff(s)[r] for s, r in zip(self.splits, rc)]
        off = [c - s[r] for s, c, r in zip(self.splits, coords, rc)]
        rank = np.ravel_multi_index(tuple(rc), self.procs)
        counts = [int(np.prod([np.diff(s)[c] for s, c in
                               zip(self.splits, np.unravel_index(r, self.procs))]))
                  for r in range(int(np.prod(self.procs)))]
        owned0 = np.concatenate([[0], np.cumsum(counts)])
        inbox = np.zeros(self.n, dtype=np.int64)
        for d in range(nd):
            inbox = inbox * ext[d] + off[d]
        self.glob_of_nat = (owned0[rank] + inbox).astype(np.int64)
        # local arrays: source cell of every position (-1 for a hole)
        src = []
        for r in range(len(counts)):
            rcoord = np.unravel_index(r, self.procs)
            lo = [int(s[c]) for s, c in zip(self.splits, rcoord)]
            hi = [int(s[c + 1]) for s, c in zip(self.splits, rcoord)]
            glo = [max(a - self.width, 0) for a in lo]
            ghi = [min(b + self.width, e) for b, e in zip(hi, self.grid)]
            pos = np.indices([b - a for a, b in zip(glo, ghi)]).reshape(nd, -1)
            nat = [p + a for p, a in zip(pos, glo)]
            outside = sum(((c < a) | (c >= b)).astype(np.int64)
                          for c, a, b in zip(nat, lo, hi))
            flat = np.ravel_multi_index(tuple(nat), self.grid)
            src.append(np.where(outside <= 1, self.glob_of_nat[flat], -1))
        self.src = np.concatenate(src)
        self.n_local = int(self.src.size)
        self.multiplicity = np.bincount(self.src[self.src >= 0],
                                        minlength=self.n)
        self._src_d = jnp.asarray(self.src, jnp.int32)
        conn = np.flatnonzero(self.src >= 0)
        self._conn_d = jnp.asarray(conn, jnp.int32)
        self._conn_src_d = jnp.asarray(self.src[conn], jnp.int32)
        self._g_of_n_d = jnp.asarray(self.glob_of_nat, jnp.int32)
        self._n_of_g_d = jnp.asarray(np.argsort(self.glob_of_nat), jnp.int32)

    @property
    def n_connected(self) -> int:
        return int(self._conn_d.shape[0])

    # ------------------------------------------------------------ exchange
    def global_to_local(self, g):
        return _g2l(g, self._src_d)

    def local_to_global(self, lvec):
        return _l2g(lvec, self._conn_d, self._conn_src_d, self.n)

    # ------------------------------------------------------------ operator
    def apply(self, x, center: float, neighbor: float):
        """y = A x for a global-ordered vector ``x``."""
        return _apply(x, self._g_of_n_d, self._n_of_g_d, self.grid,
                      center, neighbor)

    def cg(self, b, iters: int, center: float, neighbor: float,
           dtype: str = "float32"):
        """``iters`` unpreconditioned CG iterations from x0 = 0, every
        vector and scalar held in ``dtype``."""
        return _cg(b, self._g_of_n_d, self._n_of_g_d, grid=self.grid,
                   iters=iters, center=center, neighbor=neighbor,
                   dtype=dtype)


@jax.jit
def _g2l(g, src):
    vals = jnp.take(g, jnp.maximum(src, 0), axis=0)
    keep = (src >= 0).reshape((-1,) + (1,) * (g.ndim - 1))
    return jnp.where(keep, vals, jnp.zeros((), g.dtype))


@partial(jax.jit, static_argnames="n")
def _l2g(lvec, conn, conn_src, n):
    return jax.ops.segment_sum(jnp.take(lvec, conn, axis=0), conn_src,
                               num_segments=n)


def _apply(x, g_of_n, n_of_g, grid, center, neighbor):
    u = jnp.take(x, g_of_n).reshape(grid)
    y = center * u
    for d in range(len(grid)):
        lo = [(0, 0)] * len(grid)
        hi = [(0, 0)] * len(grid)
        lo[d], hi[d] = (1, 0), (0, 1)
        below = [slice(None)] * len(grid)
        above = [slice(None)] * len(grid)
        below[d], above[d] = slice(0, -1), slice(1, None)
        y = y + neighbor * jnp.pad(u[tuple(below)], lo) \
            + neighbor * jnp.pad(u[tuple(above)], hi)
    return jnp.take(y.reshape(-1), n_of_g)


@partial(jax.jit, static_argnames=("grid", "iters", "center", "neighbor",
                                   "dtype"))
def _cg(b, g_of_n, n_of_g, *, grid, iters, center, neighbor, dtype):
    dt = jnp.dtype(dtype)
    b = b.astype(dt)
    A = lambda v: _apply(v, g_of_n, n_of_g, grid, center, neighbor
                         ).astype(dt)

    def body(_, st):
        x, r, p, rz = st
        Ap = A(p)
        alpha = rz / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rz_new = jnp.vdot(r, r)
        p = r + (rz_new / rz) * p
        return x, r, p, rz_new

    st = (jnp.zeros_like(b), b, b, jnp.vdot(b, b))
    return jax.lax.fori_loop(0, iters, body, st)[0]
