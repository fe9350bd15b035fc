"""DMDA cells: the halo exchange and host-stepped CG on a structured grid.

``build`` returns the problem the traffic's ``driver`` names:

* ``halo_pairs``: a closed loop with one caller.  Each pair is one
  ``DMDA.global_to_local`` (replace) and one ``DMDA.local_to_global`` (sum)
  of a ``dof``-unit f32 field, ending in ``block_until_ready`` as a
  time-stepper that needs its ghosts before it computes.  The pair's output,
  divided cell by cell by how many local arrays show that cell (so values
  neither grow nor shrink), is the next pair's input.
* ``cg_sets``: HPCG-style sets of ``cg(A, b, tol, maxiter)`` back to back,
  each from x0 = 0 with its own b drawn from the seed; the set in progress
  when the window closes is finished and counted.

The program is driven only through its public entry points; the
comparison (``readings``) uses the geometric reference in ``dmda_ref``.
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench import seeds, work
from bench.systems.dmda_ref import Geometry

__all__ = ["build"]

TA = jax.profiler.TraceAnnotation


def build(config: Dict, traffic: Dict, seed: int, log):
    drivers = {"halo_pairs": HaloPairs, "cg_sets": CGSets}
    return drivers[traffic["driver"]](config, traffic, seed, log)


def _dmda(config: Dict):
    from repro.meshdist.dmda import DMDA
    return DMDA(tuple(config["grid"]), config["ranks"],
                proc_grid=tuple(config["proc_grid"]),
                stencil=config["stencil"], width=config["width"],
                periodic=config["periodic"], interior=config["interior"])


def _geometry(config: Dict) -> Geometry:
    return Geometry(config["grid"], config["proc_grid"],
                    width=config["width"], stencil=config["stencil"],
                    periodic=config["periodic"])


def _normal(key, shape):
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


class HaloPairs:
    def __init__(self, config: Dict, traffic: Dict, seed: int, log):
        t = time.perf_counter()
        self.da = _dmda(config)
        self.geo = _geometry(config)
        log(f"DMDA {self.da.shape} on {self.da.nranks} ranks, proc grid "
            f"{self.da.proc_grid}: {self.da.nglobal} global and "
            f"{self.da.nlocal_total} local rows; reference geometry too; "
            f"{time.perf_counter() - t:.6f} s")
        self.dof = int(traffic["dof"])
        self.keep = int(traffic["checked_pairs"])
        self.reseed(seed)
        inv = jnp.asarray(1.0 / self.geo.multiplicity, jnp.float32)[:, None]
        self.rescale = jax.jit(lambda out: out * inv)
        self.pair_work = work.halo_pair(self.da.nglobal, self.geo.n_connected,
                                        self.dof)
        t = time.perf_counter()
        g = self.g0
        for _ in range(2):      # first call sweeps the kernels and compiles
            g = self._pair(g)[2]
        log(f"halo backend {self.da.comm().backend_name!r}; warm-up "
            f"{time.perf_counter() - t:.6f} s")

    def reseed(self, seed: int) -> None:
        """New inputs from ``seed``: the field and the check's sample."""
        self.rng = seeds.rng(seed, 5)
        self.g0 = _normal(seeds.jax_key(seed, 1), (self.da.nglobal, self.dof))

    def _pair(self, g):
        with TA("halo.g2l"):
            lvec = self.da.global_to_local(g)
        with TA("halo.l2g"):
            out = self.da.local_to_global(lvec, op="sum")
        nxt = jax.block_until_ready(self.rescale(out))
        return lvec, out, nxt

    def window(self, seconds: float) -> Dict:
        kept: List = []
        g, n = self.g0, 0
        t0 = time.perf_counter()
        while True:
            lvec, out, nxt = self._pair(g)
            # the first pair, and a reservoir sample of the others drawn
            # from the seed, are checked once the window has closed
            if n < self.keep:
                kept.append((n, g, lvec, out))
            else:
                j = int(self.rng.integers(1, n + 1))
                if j < self.keep:
                    kept[j] = (n, g, lvec, out)
            g, n = nxt, n + 1
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        self.kept = kept
        return {"t0": t0, "t1": t1, "attempted": n, "pairs": n,
                "pair_work": self.pair_work,
                "counts": {"pairs": n, "bytes_per_pair": self.pair_work.bytes,
                           "flops_per_pair": self.pair_work.flops}}

    def readings(self, with_control: bool) -> Dict:
        geo, out = self.geo, {"program": {}, "control": None}

        def numbers(pairs):
            mism, rel = 0, 0.0
            for g, lvec, res in pairs:
                want_l = geo.global_to_local(g)
                want = geo.local_to_global(want_l)
                mism += int(jnp.sum(lvec != want_l))
                rel = max(rel, float(jnp.max(jnp.abs(res - want))
                                     / jnp.max(jnp.abs(want))))
            return {"g2l_mismatched": float(mism), "l2g_max_rel_err": rel}

        out["program"] = numbers([(g, lv, o) for _, g, lv, o in self.kept])
        if with_control:
            bf = lambda a: a.astype(jnp.bfloat16)
            ctl = []
            for _, g, _, _ in self.kept:
                lv = geo.global_to_local(bf(g))
                ctl.append((g, lv.astype(jnp.float32),
                            geo.local_to_global(lv).astype(jnp.float32)))
            out["control"] = numbers(ctl)
        return out


class CGSets:
    def __init__(self, config: Dict, traffic: Dict, seed: int, log):
        from repro.sparse.parmat import ParCSR
        t = time.perf_counter()
        self.da = _dmda(config)
        self.A = ParCSR.from_dmda_stencil(self.da)
        op = config["operator"]
        self.center, self.neighbor = float(op["center"]), float(op["neighbor"])
        log(f"DMDA {self.da.shape} on {self.da.nranks} ranks: ParCSR "
            f"operator, {self.A.sf.nedges_total} ghost edges, backend "
            f"{self.A.comm.backend_name!r}; {time.perf_counter() - t:.6f} s")
        self.config = config
        self.maxiter = int(traffic["maxiter"])
        self.tol = float(traffic["tol"])
        self.reseed(seed)
        n = self.da.nglobal
        self.make_b = jax.jit(lambda k, i: jax.random.normal(
            jax.random.fold_in(k, i), (n,), jnp.float32))
        self.iter_work = work.cg_iteration(work.stencil_nnz(self.da.shape), n)
        t = time.perf_counter()
        self._solve(self.make_b(self.key, -1), maxiter=2)   # compiles, sweeps
        log(f"cg warm-up {time.perf_counter() - t:.6f} s")

    def reseed(self, seed: int) -> None:
        """New inputs from ``seed``: the sets' right-hand sides."""
        self.key = seeds.jax_key(seed, 2)

    def _solve(self, b, maxiter: int):
        from repro.solvers.cg import cg
        res = cg(self.A, b, tol=self.tol, maxiter=maxiter)
        jax.block_until_ready(res.x)
        return res

    def window(self, seconds: float) -> Dict:
        sets, iters = [], 0
        t0 = time.perf_counter()
        while True:
            k = len(sets)
            b = self.make_b(self.key, k)
            with TA("cg.set"):
                res = self._solve(b, self.maxiter)
            sets.append((k, b, res.x, res.iters))
            iters += res.iters
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        self.sets = sets
        return {"t0": t0, "t1": t1, "attempted": len(sets), "sets": len(sets),
                "cg_iters": iters, "iter_work": self.iter_work,
                "counts": {"sets": len(sets), "iterations": iters,
                           "bytes_per_iteration": self.iter_work.bytes,
                           "flops_per_iteration": self.iter_work.flops}}

    def readings(self, with_control: bool) -> Dict:
        geo = _geometry(self.config)
        out = {"program": {}, "control": None}

        def numbers(results):
            off, rel = 0, 0.0
            for b, x, its in results:
                want = geo.cg(b, self.maxiter, self.center, self.neighbor)
                off = max(off, abs(its - self.maxiter))
                rel = max(rel, float(jnp.linalg.norm(x - want)
                                     / jnp.linalg.norm(want)))
            return {"cg_iters_off": float(off), "cg_x_rel_err": rel}

        out["program"] = numbers([(b, x, its) for _, b, x, its in self.sets])
        if with_control:
            out["control"] = numbers([
                (b, geo.cg(b, self.maxiter, self.center, self.neighbor,
                           "bfloat16").astype(jnp.float32), self.maxiter)
                for _, b, _, _ in self.sets])
        return out
