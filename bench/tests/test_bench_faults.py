"""A run with the timed path broken underneath comes out not correct.

The whole run past the look for a chip (``tiny.run``), on tiny copies of
the DMDA cells, once for each fault such a cell can have: a step that
returns its state unchanged, the exchange between ranks left out, and an
answer altered where it is produced."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny


def test_sound_runs_are_correct():
    for name in ("poisson128-halo4", "poisson128-cg50"):
        assert tiny.run(name)["correct"] is True


# ------------------------------------------------------------------ halo
def _ghost_free(da, lvec):
    """``lvec`` with every ghost position zeroed: what a rank holds when
    nothing travelled between ranks."""
    from bench.systems.dmda_ref import Geometry
    src = Geometry(da.shape, da.proc_grid).src
    pos = np.arange(src.size)
    rank = np.searchsorted(da.local_offsets, pos, side="right") - 1
    own = (src >= da.owned_offsets[rank]) & (src < da.owned_offsets[rank + 1])
    return jnp.where(jnp.asarray(own)[:, None], lvec, 0)


def _halo_fault(kind):
    from repro.meshdist.dmda import DMDA
    g2l, l2g = DMDA.global_to_local, DMDA.local_to_global

    if kind == "unchanged":
        return "global_to_local", lambda self, g, lvec=None, backend=None: \
            jnp.zeros((self.nlocal_total,) + g.shape[1:], g.dtype)
    if kind == "no_exchange":
        return "global_to_local", lambda self, g, lvec=None, backend=None: \
            _ghost_free(self, g2l(self, g, lvec, backend))
    return "local_to_global", lambda self, lvec, gvec=None, op="sum", \
        backend=None: l2g(self, lvec, gvec, op, backend).at[3, 1].add(1.0)


@pytest.mark.parametrize("kind", ["unchanged", "no_exchange", "altered"])
def test_halo_fault_is_caught(monkeypatch, kind):
    from repro.meshdist.dmda import DMDA
    attr, broken = _halo_fault(kind)
    monkeypatch.setattr(DMDA, attr, broken)
    assert tiny.run("poisson128-halo4")["correct"] is False


# -------------------------------------------------------------------- cg
@pytest.mark.parametrize("kind", ["unchanged", "no_exchange", "altered"])
def test_cg_fault_is_caught(monkeypatch, kind):
    from repro.sparse.parmat import ParCSR
    cgmod = importlib.import_module("repro.solvers.cg")

    if kind == "unchanged":
        monkeypatch.setattr(cgmod, "_step", lambda mv, x, r, p, rz, M=None:
                            (x, r, p, rz, rz))
    elif kind == "no_exchange":
        def diag_only(self, x, use_kernel=False):
            return jnp.concatenate([
                self._diag_ell[r].apply(
                    x[int(self.col_offsets[r]):int(self.col_offsets[r + 1])])
                for r in range(self.nranks)])
        monkeypatch.setattr(ParCSR, "spmv", diag_only)
    else:
        cg = cgmod.cg

        def altered(*a, **kw):
            res = cg(*a, **kw)
            res.x = res.x.at[5].multiply(2.0)
            return res
        monkeypatch.setattr(cgmod, "cg", altered)
    assert tiny.run("poisson128-cg50")["correct"] is False
