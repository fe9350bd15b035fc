"""Conjugate gradient on SF-based SpMV: blocking CG vs. async CG (paper §6.2).

The paper contrasts two executions of the same Krylov iteration:

* **CG** — each iteration launches device kernels, then *synchronizes* for
  scalar reductions (VecDot copies the partial dot to the host, MPI_Allreduce
  runs on the host, convergence is checked on the host).  Every iteration
  blocks the kernel-launch pipeline (paper Fig 5(R), Fig 10 top).

* **CGAsync** — dots are reduced on-device (NVSHMEM), scalar arithmetic runs
  in tiny device kernels, convergence is *not* checked on the host; the host
  can run ahead and enqueue many iterations (paper Fig 10 bottom).

JAX/TPU adaptation (DESIGN.md §3.2): ``cg`` below steps one jitted iteration
per Python-loop turn and pulls the residual norm to the host every iteration
— the exact blocking structure of the paper's CG.  ``cg_async`` fuses the
whole loop into one compiled ``lax.while_loop``: scalars live on device,
convergence is evaluated on device (optionally every k-th iteration, the
paper's suggested improvement), and the host is out of the loop entirely —
the end state NVSHMEM approximates.  ``benchmarks/bench_cg.py`` reproduces
the §6.2 comparison on these two.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sflog

__all__ = ["CGResult", "cg", "cg_async", "as_matvec", "step_program"]


def as_matvec(op) -> Callable:
    """Accept either a raw matvec callable or an SF-backed operator (e.g.
    :class:`repro.sparse.parmat.ParCSR`) whose ``spmv`` routes its ghost
    exchange through the :class:`repro.core.SFComm` backend layer."""
    if hasattr(op, "spmv"):
        return op.spmv
    if callable(op):
        return op
    raise TypeError(f"need a callable or an object with .spmv, got {op!r}")


@dataclasses.dataclass
class CGResult:
    x: jnp.ndarray
    iters: int
    rnorm: float
    converged: bool


def _step(matvec, x, r, p, rz, M=None):
    """One (preconditioned) CG iteration.  With ``M=None`` this is exactly
    the paper's unpreconditioned loop (z = r); with a preconditioner the
    step returns both rz = <r, z> (for beta) and <r, r> (for the residual
    convergence check)."""
    Ap = matvec(p)
    with sflog.scope("cg.vec"):
        alpha = rz / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
    z = r if M is None else M(r)
    with sflog.scope("cg.vec"):
        rz_new = jnp.vdot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        rr = rz_new if M is None else jnp.vdot(r, r)
    return x, r, p, rz_new, rr


def step_program(matvec: Callable, M: Optional[Callable] = None) -> Callable:
    """One CG iteration over ``matvec`` as the jitted program ``cg_step``
    (a new program: it traces on its first call)."""
    def cg_step(x, r, p, rz):
        return _step(matvec, x, r, p, rz, M)
    return jax.jit(cg_step)


def cg(matvec: Callable, b: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
       *, tol: float = 1e-8, maxiter: int = 500,
       M: Optional[Callable] = None) -> CGResult:
    """Host-stepped CG: one jitted iteration per host turn + host-side
    convergence check (the paper's blocking baseline).  ``matvec`` may be a
    callable or an SF-backed operator accepted by :func:`as_matvec`.

    ``M`` is an optional (left, SPD) preconditioner applied as ``z = M(r)``
    — e.g. ``cg(A, b, M=mg.vcycle)`` for the V-cycle of
    :class:`repro.solvers.multigrid.Multigrid`.  Convergence is still
    judged on the true residual norm ||r||."""
    matvec = as_matvec(matvec)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - matvec(x)
    z = r if M is None else M(r)
    p = z
    rz = jnp.vdot(r, z)
    rr = rz if M is None else jnp.vdot(r, r)
    with sflog.span("cg.readback"):
        bnorm = float(jnp.sqrt(jnp.vdot(b, b)))
    step = step_program(matvec, M)
    it = 0
    with sflog.span("cg.readback"):
        rnorm = float(jnp.sqrt(rr))
    while it < maxiter:
        # host reads the residual -> device/host sync every iteration,
        # mirroring VecDot + host convergence check in the paper's CG
        if rnorm <= tol * max(bnorm, 1e-30):
            return CGResult(x, it, rnorm, True)
        with sflog.span("cg.iter", k=it):
            if it == 0:
                with sflog.span("cg.jit"):    # traces the new step program
                    x, r, p, rz, rr = step(x, r, p, rz)
            else:
                x, r, p, rz, rr = step(x, r, p, rz)
            with sflog.span("cg.readback"):
                rnorm = float(jnp.sqrt(rr))   # blocking host readback
        it += 1
    return CGResult(x, it, rnorm, rnorm <= tol * max(bnorm, 1e-30))


def cg_async(matvec: Callable, b: jnp.ndarray,
             x0: Optional[jnp.ndarray] = None, *, tol: float = 1e-8,
             maxiter: int = 500, check_every: int = 1,
             M: Optional[Callable] = None) -> CGResult:
    """Fully fused CG: the entire loop is one ``lax.while_loop`` on device.

    Convergence is checked on device every ``check_every`` iterations (the
    paper's CGAsync checks never and runs to maxiter; pass
    ``check_every=0`` for that exact behaviour).  ``M`` is the optional
    preconditioner of :func:`cg`; it is traced into the fused loop."""
    matvec = as_matvec(matvec)
    x = jnp.zeros_like(b) if x0 is None else x0
    # One eager application before tracing: an SF-backed matvec autotunes
    # its pack/unpack lowerings on first execution (repro.kernels.tuning),
    # and running the sweep here keeps setup work out of the fused
    # while_loop trace — every in-loop exchange dispatches straight to the
    # memoized winner.
    jax.block_until_ready(matvec(x))

    def run(x, b):
        r = b - matvec(x)
        z = r if M is None else M(r)
        p = z
        rz = jnp.vdot(r, z)
        rr = rz if M is None else jnp.vdot(r, r)
        b2 = jnp.vdot(b, b)
        tol2 = jnp.asarray(tol, rz.dtype) ** 2 * jnp.maximum(b2, 1e-30)

        def cond(state):
            x, r, p, rz, rr, it = state
            not_done = rr > tol2
            if check_every == 0:
                not_done = jnp.asarray(True)
            elif check_every > 1:
                # only observe convergence at multiples of check_every
                not_done = jnp.logical_or(not_done,
                                          (it % check_every) != 0)
            return jnp.logical_and(it < maxiter, not_done)

        def body(state):
            x, r, p, rz, rr, it = state
            x, r, p, rz, rr = _step(matvec, x, r, p, rz, M)
            return (x, r, p, rz, rr, it + 1)

        state = (x, r, p, rz, rr, jnp.asarray(0, jnp.int32))
        x, r, p, rz, rr, it = jax.lax.while_loop(cond, body, state)
        return x, jnp.sqrt(rr), it

    run_j = jax.jit(run)
    x, rnorm, it = run_j(x, b)
    rnorm = float(rnorm)
    bnorm = float(jnp.sqrt(jnp.vdot(b, b)))
    return CGResult(x, int(it), rnorm,
                    rnorm <= tol * max(bnorm, 1e-30))
