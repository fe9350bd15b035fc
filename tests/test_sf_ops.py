"""SF operation semantics: plan-based jnp implementation vs numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import random_star_forest
from repro.core import SFOps, StarForest, simulate


@pytest.fixture(params=range(6))
def sf(request):
    return random_star_forest(seed=request.param)


@pytest.mark.parametrize("op", ["replace", "sum", "max", "min", "prod"])
def test_bcast_matches_oracle(sf, op, rng):
    ops = SFOps(sf)
    root = rng.standard_normal((sf.nroots_total, 3)).astype(np.float32)
    leaf = rng.standard_normal((sf.nleafspace_total, 3)).astype(np.float32)
    got = np.asarray(ops.bcast(jnp.asarray(root), jnp.asarray(leaf), op))
    want = simulate.bcast_ref(sf, root, leaf, op)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("op", ["replace", "sum", "max", "min", "prod"])
def test_reduce_matches_oracle(sf, op, rng):
    ops = SFOps(sf)
    root = rng.standard_normal((sf.nroots_total, 2)).astype(np.float32)
    leaf = rng.standard_normal((sf.nleafspace_total, 2)).astype(np.float32)
    got = np.asarray(ops.reduce(jnp.asarray(leaf), jnp.asarray(root), op))
    want = simulate.reduce_ref(sf, leaf, root, op)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fetch_and_op_exact_int(sf, rng):
    ops = SFOps(sf)
    ri = rng.integers(0, 100, (sf.nroots_total,)).astype(np.int32)
    li = rng.integers(0, 100, (sf.nleafspace_total,)).astype(np.int32)
    wr, wl = simulate.fetch_and_op_ref(sf, ri, li, "sum")
    gr, gl = ops.fetch_and_op(jnp.asarray(ri), jnp.asarray(li), "sum")
    np.testing.assert_array_equal(np.asarray(gr), wr)
    np.testing.assert_array_equal(np.asarray(gl), wl)


def test_gather_scatter_roundtrip(sf, rng):
    ops = SFOps(sf)
    leaf = rng.standard_normal((sf.nleafspace_total, 2)).astype(np.float32)
    multi = ops.gather(jnp.asarray(leaf))
    assert multi.shape[0] == ops.nmulti
    np.testing.assert_allclose(np.asarray(multi),
                               simulate.gather_ref(sf, leaf))
    back = ops.scatter(multi, jnp.asarray(leaf))
    np.testing.assert_allclose(np.asarray(back),
                               simulate.scatter_ref(sf, np.asarray(multi),
                                                    leaf))
    # scatter(gather(x)) restores x on connected leaves
    gl = sf.edges_global()[:, 1]
    np.testing.assert_allclose(np.asarray(back)[gl], leaf[gl])


def test_degrees_match_reduce_of_ones(sf):
    ops = SFOps(sf)
    deg = np.asarray(ops.compute_degrees())
    want = np.concatenate([sf.degrees(r) for r in range(sf.nranks)])
    np.testing.assert_array_equal(deg, want)


def test_begin_end_equals_fused(sf, rng):
    ops = SFOps(sf)
    root = rng.standard_normal((sf.nroots_total,)).astype(np.float32)
    leaf = rng.standard_normal((sf.nleafspace_total,)).astype(np.float32)
    pend = ops.bcast_begin(jnp.asarray(root), "replace")
    # unrelated compute between begin and end (paper's overlap idiom)
    _ = jnp.sum(jnp.asarray(leaf) ** 2)
    out = pend.end(jnp.asarray(leaf))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ops.bcast(root, leaf, "replace")))


def test_bcast_differentiable(sf, rng):
    import jax
    ops = SFOps(sf)
    root = jnp.asarray(rng.standard_normal((sf.nroots_total,))
                       .astype(np.float32))
    leaf = jnp.zeros((sf.nleafspace_total,), jnp.float32)

    def f(r):
        return jnp.sum(ops.bcast(r, leaf, "replace") ** 2)

    g = jax.grad(f)(root)
    # each root's grad = 2 * value * degree
    deg = np.concatenate([sf.degrees(r) for r in range(sf.nranks)])
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(root) * deg,
                               rtol=1e-5)


def test_errors():
    sf = StarForest(2)
    with pytest.raises(ValueError):
        sf.set_graph(0, 2, [0, 0], [(0, 0), (0, 1)])  # dup leaf position
    sf2 = StarForest(2)
    sf2.set_graph(0, 1, None, [(1, 5)])
    sf2.set_graph(1, 1, None, [])
    with pytest.raises(ValueError):
        sf2.setup()  # root offset beyond owner nroots


# ------------------------------------------------------------ unpack forms
_ROWS = 64


def _index_list(kind, rng):
    """A unique-destination index list into ``_ROWS`` rows: every row in
    order, all but three rows shuffled, or too few rows for a gather."""
    from repro.core.ops import DENSE_UNPACK_SHARE
    if kind == "identity":
        return np.arange(_ROWS)
    if kind == "dense":
        return rng.permutation(np.setdiff1d(np.arange(_ROWS), [0, 9, 63]))
    need = int(np.ceil(DENSE_UNPACK_SHARE * _ROWS))
    size = need if kind == "threshold" else need - 1
    return rng.choice(_ROWS, size=size, replace=False)


def _rows(rng, shape, dtype):
    if dtype == "int32":
        return jnp.asarray(rng.integers(-50, 50, shape), jnp.int32)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("unit", [(), (4,), (2, 3)])
@pytest.mark.parametrize("op", ["replace", "sum", "max", "min", "prod"])
@pytest.mark.parametrize("kind", ["identity", "dense", "sparse"])
def test_unpack_bitwise_equals_scatter(kind, op, unit, dtype, rng):
    """Each unpack form writes exactly what the unique-index scatter
    writes, bit for bit."""
    from repro.core.mpiops import get_op
    from repro.core.ops import _apply_unique, unpack_map
    idx = _index_list(kind, rng)
    target = _rows(rng, (_ROWS,) + unit, dtype)
    vals = _rows(rng, (idx.size,) + unit, dtype)
    o = get_op(op)
    want = getattr(target.at[idx], o.at_update)(vals, unique_indices=True)
    got = _apply_unique(target, unpack_map(idx, _ROWS), vals, o)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


_MAP_FORM = {"identity": "identity", "dense": "gather", "threshold": "gather",
             "sparse": "scatter"}


@pytest.mark.parametrize("kind,rows,unit,form", [
    ("identity", _ROWS, (2,), "identity"), ("dense", _ROWS, (2,), "gather"),
    ("threshold", _ROWS, (2,), "gather"), ("sparse", _ROWS, (2,), "scatter"),
    ("dense", _ROWS + 5, (2,), "scatter"), ("dense", _ROWS, (), "scatter"),
    ("identity", _ROWS, (), "identity")])
def test_unpack_form_follows_density(kind, rows, unit, form, rng):
    """The map's form follows the index count over the destination rows,
    and each built unpack program counts the form it ran once.  A
    destination of another length than the map's, and one-element rows in
    the gather form, take the scatter."""
    from repro.core import sflog
    from repro.core.mpiops import get_op
    from repro.core.ops import _apply_unique, sf_unpack_rows, unpack_map
    idx = _index_list(kind, rng)
    umap = unpack_map(idx, _ROWS)
    assert umap.form == _MAP_FORM[kind]
    sf_unpack_rows.clear_cache()
    before = sflog.counters()
    target = jnp.zeros((rows,) + unit, jnp.float32)
    vals = jnp.ones((idx.size,) + unit, jnp.float32)
    want = target.at[idx].add(vals)
    for _ in range(2):
        got = _apply_unique(target, umap, vals, get_op("sum"))
    moved = {k: v - before.get(k, 0) for k, v in sflog.counters().items()
             if k.startswith("sf.unpack.") and v != before.get(k, 0)}
    assert moved == {f"sf.unpack.{form}": 1}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
