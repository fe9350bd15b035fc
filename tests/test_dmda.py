"""DMDA-lite: structured-grid halo exchange compiled to a StarForest.

Checks the SF against the edge-by-edge oracle, the ghost values against
direct numpy grid indexing (periodic wrap, star/box stencils, widths), the
interior connect/skip equivalence, backend interchangeability, and the
stencil-matrix + multi-RHS SpMV wiring into sparse/parmat.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SFComm, simulate
from repro.core.mpiops import get_op
from repro.meshdist.dmda import DMDA, default_proc_grid
from repro.sparse.parmat import ParCSR


def _local_gid(da):
    """Numpy ground truth: the global row each local row shows, found by
    natural-coordinate indexing of every rank's ghosted box (periodic wrap;
    -1 where no owner, or a corner ghost of a star stencil)."""
    gid = np.full(da.nlocal_total, -1, np.int64)
    for r in range(da.nranks):
        gbox = da.ghosted_box(r)
        grids = np.meshgrid(*[np.arange(a, b) for a, b in gbox],
                            indexing="ij")
        nat = np.stack([gr.reshape(-1) for gr in grids], axis=1)
        valid = np.ones(nat.shape[0], bool)
        w = nat.copy()
        for d in range(da.ndim):
            if da.periodic[d]:
                w[:, d] %= da.shape[d]
            else:
                valid &= (nat[:, d] >= 0) & (nat[:, d] < da.shape[d])
        obox = da.owned_box(r)
        outside = np.zeros(nat.shape[0], dtype=int)
        for d, (a, b) in enumerate(obox):
            outside += (nat[:, d] < a) | (nat[:, d] >= b)
        if da.stencil == "star":
            valid &= outside <= 1
        pos = np.flatnonzero(valid)
        gid[da.local_offsets[r] + pos] = da.natural_to_global(w[pos])
    return gid


def _expected_local(da, g):
    """The ghosted local arrays filled from the global vector (0 where no
    owner), and the mask of filled rows."""
    gid = _local_gid(da)
    mask = gid >= 0
    out = np.zeros((da.nlocal_total,) + g.shape[1:], g.dtype)
    out[mask] = g[gid[mask]]
    return out, mask


@pytest.mark.parametrize("stencil,width", [("star", 1), ("star", 2),
                                           ("box", 1), ("box", 2)])
@pytest.mark.parametrize("periodic", [True, False, (True, False)])
def test_global_to_local_matches_grid(stencil, width, periodic, rng):
    da = DMDA((9, 7), 4, stencil=stencil, width=width, periodic=periodic)
    g = rng.standard_normal((da.nglobal,)).astype(np.float32)
    got = np.asarray(da.global_to_local(g, backend="global"))
    want, mask = _expected_local(da, g)
    np.testing.assert_allclose(got[mask], want[mask])
    # and the SF itself agrees with the edge-by-edge oracle
    oracle = simulate.bcast_ref(da.sf, g, np.zeros_like(got), "replace")
    np.testing.assert_allclose(got, oracle)


def test_three_d_and_vector_unit(rng):
    """3-D grid with a dof-block unit (n, 3) — the unit rides the same SF."""
    da = DMDA((4, 5, 6), 6, stencil="star", width=1, periodic=True)
    g = rng.standard_normal((da.nglobal, 3)).astype(np.float32)
    got = np.asarray(da.global_to_local(g, backend="global"))
    want, mask = _expected_local(da, g)
    np.testing.assert_allclose(got[mask], want[mask])


def test_local_to_global_is_assembly(rng):
    da = DMDA((8, 8), 4, stencil="box", width=1, periodic=True)
    lv = rng.standard_normal((da.nlocal_total,)).astype(np.float32)
    got = np.asarray(da.local_to_global(lv, op="sum", backend="global"))
    want = simulate.reduce_ref(da.sf, lv,
                               np.zeros(da.nglobal, np.float32), "sum")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_interior_skip_equals_connect(rng):
    """interior='skip' (pure-halo SF + direct owned copy) produces the same
    local vectors as the fully-connected DMGlobalToLocal."""
    kw = dict(stencil="star", width=1, periodic=True)
    full = DMDA((8, 6), 4, interior="connect", **kw)
    halo = DMDA((8, 6), 4, interior="skip", **kw)
    assert halo.sf.nedges_total < full.sf.nedges_total
    g = rng.standard_normal((full.nglobal,)).astype(np.float32)
    lv_full = np.asarray(full.global_to_local(g, backend="global"))
    lv_halo = np.asarray(halo.global_to_local(g, backend="global"))
    np.testing.assert_allclose(lv_halo, lv_full)
    # and back: assembly agrees too
    lv = rng.standard_normal((full.nlocal_total,)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(halo.local_to_global(lv, op="sum", backend="global")),
        np.asarray(full.local_to_global(lv, op="sum", backend="global")),
        rtol=1e-5, atol=1e-5)


def test_backends_interchangeable(rng):
    da = DMDA((10, 6), 4, stencil="star", width=1, periodic=True)
    g = rng.standard_normal((da.nglobal, 2)).astype(np.float32)
    ref = np.asarray(da.global_to_local(g, backend="global"))
    got = np.asarray(da.global_to_local(g, backend="pallas"))
    np.testing.assert_allclose(got, ref)
    assert da.comm("pallas").backend_name == "pallas"


def test_proc_grid_and_errors():
    assert default_proc_grid((64, 64), 4) == (2, 2)
    assert default_proc_grid((128, 8), 4) == (4, 1)
    assert np.prod(default_proc_grid((16, 16, 16), 6)) == 6
    with pytest.raises(ValueError, match="cannot place"):
        DMDA((2, 2), 8)
    with pytest.raises(ValueError, match="stencil"):
        DMDA((8, 8), 2, stencil="diamond")
    with pytest.raises(ValueError, match="width"):
        DMDA((8, 8), 2, width=0)
    with pytest.raises(ValueError, match="proc_grid"):
        DMDA((8, 8), 4, proc_grid=(3, 1))
    with pytest.raises(ValueError, match="one bool per dim"):
        DMDA((8, 8), 2, periodic=(True, False, True))


def test_star_skips_corner_ghosts():
    da = DMDA((6, 6), 4, stencil="star", width=1, periodic=True)
    db = DMDA((6, 6), 4, stencil="box", width=1, periodic=True)
    # box connects the corner ghosts star leaves as holes
    assert db.sf.nedges_total > da.sf.nedges_total


# ------------------------------------------------- stencil matrix + SpMV
def test_stencil_matrix_dense_reference(rng):
    da = DMDA((6, 5), 4, stencil="star", width=1, periodic=True)
    A = ParCSR.from_dmda_stencil(da)
    dense = A.toarray()
    # periodic Laplacian: rows sum to zero, 4 on the diagonal
    np.testing.assert_allclose(dense.sum(1), 0, atol=1e-6)
    assert (np.diag(dense) == 4).all()
    x = rng.standard_normal(da.nglobal).astype(np.float32)
    np.testing.assert_allclose(np.asarray(A.spmv(jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)


def test_stencil_matrix_dirichlet_and_coeffs(rng):
    da = DMDA((5, 4), 2, stencil="star", width=1, periodic=False)
    A = ParCSR.from_dmda_stencil(da, coeffs=[6.0, -1.0, -1.0, -2.0, -2.0])
    dense = A.toarray()
    assert (np.diag(dense) == 6).all()
    x = rng.standard_normal(da.nglobal).astype(np.float32)
    np.testing.assert_allclose(np.asarray(A.spmv(jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="coeffs"):
        ParCSR.from_dmda_stencil(da, coeffs=[1.0, 2.0])


def test_spmv_multi_one_fused_exchange(rng, monkeypatch):
    """Multi-RHS SpMV batches k x-columns through ONE ghost bcast."""
    da = DMDA((8, 6), 4, stencil="star", width=1, periodic=True)
    A = ParCSR.from_dmda_stencil(da)
    dense = A.toarray()
    k = 4
    X = rng.standard_normal((da.nglobal, k)).astype(np.float32)
    counts = {"begin": 0}
    real_begin = A.comm.bcast_begin

    def counting_begin(rootdata, op="replace"):
        counts["begin"] += 1
        return real_begin(rootdata, op)

    monkeypatch.setattr(A.comm, "bcast_begin", counting_begin)
    Y = np.asarray(A.spmv_multi(jnp.asarray(X)))
    assert counts["begin"] == 1                # one exchange for all k RHS
    np.testing.assert_allclose(Y, dense @ X, rtol=1e-3, atol=1e-3)
    # column-by-column agreement with the single-RHS path
    for j in range(k):
        np.testing.assert_allclose(
            Y[:, j], np.asarray(A.spmv(jnp.asarray(X[:, j]))),
            rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="expects"):
        A.spmv_multi(X[:, 0])


@pytest.mark.parametrize("backend", ["global", "pallas"])
@pytest.mark.parametrize("interior", ["connect", "skip"])
def test_halo_unpack_bitwise_equals_numpy_scatter(interior, backend, rng):
    """A 16^3 grid on 8 ranks with a 4-dof unit: DMGlobalToLocal and
    DMLocalToGlobal (sum, max) equal a plain numpy scatter bit for bit.
    Values are small integers, so a sum is exact in any order and the check
    is on where each value lands."""
    da = DMDA((16, 16, 16), 8, stencil="star", width=1, periodic=False,
              interior=interior)
    gid = _local_gid(da)
    pos = np.flatnonzero(gid >= 0)
    g = rng.integers(-8, 8, (da.nglobal, 4)).astype(np.float32)
    want_l = np.zeros((da.nlocal_total, 4), np.float32)
    want_l[pos] = g[gid[pos]]
    got_l = np.asarray(da.global_to_local(g, backend=backend))
    assert got_l.tobytes() == want_l.tobytes()
    lv = rng.integers(-8, 8, (da.nlocal_total, 4)).astype(np.float32)
    for op, ufunc in (("sum", np.add), ("max", np.maximum)):
        want = np.full((da.nglobal, 4), get_op(op).identity_of(np.float32))
        ufunc.at(want, gid[pos], lv[pos])
        got = np.asarray(da.local_to_global(lv, op=op, backend=backend))
        assert got.tobytes() == want.tobytes(), op


@pytest.mark.parametrize("stencil,Lmax", [("star", 4), ("box", 8)])
def test_local_to_global_gather_combine(stencil, Lmax, rng, monkeypatch):
    """DMLocalToGlobal (sum) on the pallas backend with the segment reduce
    pinned to the ``gather`` lowering equals the swept lowering and a numpy
    scatter bit for bit (small integers: exact in any order); the built
    combine program counts ``sf.combine.gather`` once."""
    from repro.core import sflog
    from repro.kernels import tuning
    da = DMDA((8, 8, 8), 8, stencil=stencil, width=1, periodic=False)
    gid = _local_gid(da)
    pos = np.flatnonzero(gid >= 0)
    assert np.bincount(gid[pos]).max() == Lmax     # longest segment
    lv = rng.integers(-8, 8, (da.nlocal_total, 4)).astype(np.float32)
    want = np.zeros((da.nglobal, 4), np.float32)
    np.add.at(want, gid[pos], lv[pos])

    def l2g():
        tuning.clear_cache()
        out = np.asarray(DMDA((8, 8, 8), 8, stencil=stencil, width=1,
                              periodic=False).local_to_global(
                                  lv, op="sum", backend="pallas"))
        tuning.clear_cache()
        return out

    swept = l2g()
    monkeypatch.setenv("REPRO_SF_IMPL_SEGRED", "gather")
    before = sflog.counters().get("sf.combine.gather", 0)
    pinned = l2g()
    assert sflog.counters()["sf.combine.gather"] - before == 1
    assert pinned.tobytes() == want.tobytes()
    assert swept.tobytes() == want.tobytes()
