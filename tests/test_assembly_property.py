"""Property suite for stash-based parallel assembly (paper §6.4).

Three properties of :class:`repro.sparse.parmat.MatAssembler`, over random
partitions / patterns / insert orders (hypothesis, ``repro-ci`` profile):

1. **Serial equivalence** — with f32-exact values (dyadic fractions: any
   summation order is exact) the distributed assembly is BITWISE equal to
   a single-rank dense ``np.add.at`` reference.
2. **Insert-order determinism** — for *arbitrary* float values and a fixed
   contribution->source-rank map, shuffling the insert order and call
   chunking does not change a single output bit (canonical value-sorted
   partials + the deterministic (leaf rank, edge index) SF reduce order).
3. **ONE reduce** — each ``assemble()`` performs exactly one
   ``SFComm.reduce`` (the compose_inverse-built stash flush); no hidden
   exchanges, counted with the same monkeypatch tracing as
   ``test_fields.py``.

hypothesis is a CI-only dependency — skipped cleanly where absent.
"""

import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import SFComm
from repro.sparse.parmat import MatAssembler, Sparsity


# ------------------------------------------------------------- strategies
@st.composite
def assembly_cases(draw, exact_values):
    """(nranks, m, n, rows, cols, vals, src_rank) with every contribution
    assigned a source rank.  ``exact_values`` restricts values to dyadic
    multiples of 1/8 in [-16, 16] so float32 sums are order-exact."""
    nranks = draw(st.integers(2, 4))
    m = draw(st.integers(nranks, 12))
    n = draw(st.integers(1, 10))
    nins = draw(st.integers(0, 60))
    rows = np.asarray(draw(st.lists(st.integers(0, m - 1), min_size=nins,
                                    max_size=nins)), dtype=np.int64)
    cols = np.asarray(draw(st.lists(st.integers(0, n - 1), min_size=nins,
                                    max_size=nins)), dtype=np.int64)
    if exact_values:
        vals = np.asarray(draw(st.lists(st.integers(-128, 128),
                                        min_size=nins, max_size=nins)),
                          dtype=np.float32) / 8.0
    else:
        vals = np.asarray(draw(st.lists(
            st.floats(-1e3, 1e3, allow_nan=False, width=32),
            min_size=nins, max_size=nins)), dtype=np.float32)
    src = np.asarray(draw(st.lists(st.integers(0, nranks - 1),
                                   min_size=nins, max_size=nins)),
                     dtype=np.int64)
    return nranks, m, n, rows, cols, vals, src


def _assemble(nranks, m, n, rows, cols, vals, src, order=None, chunks=1):
    """Drive a MatAssembler with the given insert order / call chunking and
    return the dense float32 result."""
    sp = Sparsity(nranks, m, n, rows, cols)
    asm = MatAssembler(sp)
    order = np.arange(rows.size) if order is None else order
    for q in range(nranks):
        idx = order[src[order] == q]
        for chunk in np.array_split(idx, max(chunks, 1)):
            asm.add_values(q, rows[chunk], cols[chunk], vals[chunk])
    return asm.assemble().toarray().astype(np.float32)


# -------------------------------------------------------------- properties
@given(assembly_cases(exact_values=True))
@settings(max_examples=25)
def test_stash_assembly_bitwise_equals_serial(case):
    nranks, m, n, rows, cols, vals, src = case
    got = _assemble(nranks, m, n, rows, cols, vals, src)
    want = np.zeros((m, n), np.float32)
    np.add.at(want, (rows, cols), vals)
    np.testing.assert_array_equal(got, want)


@given(assembly_cases(exact_values=False), st.randoms(use_true_random=False))
@settings(max_examples=25)
def test_stash_assembly_insert_order_invariant(case, rnd):
    nranks, m, n, rows, cols, vals, src = case
    base = _assemble(nranks, m, n, rows, cols, vals, src)
    order = np.arange(rows.size)
    for chunks in (1, 3):
        perm = order.copy()
        rnd.shuffle(perm)
        shuffled = _assemble(nranks, m, n, rows, cols, vals, src,
                             order=perm, chunks=chunks)
        np.testing.assert_array_equal(shuffled, base)


@given(assembly_cases(exact_values=True))
@settings(max_examples=10)
def test_assemble_performs_exactly_one_reduce(case):
    nranks, m, n, rows, cols, vals, src = case
    sp = Sparsity(nranks, m, n, rows, cols)
    asm = MatAssembler(sp)
    for q in range(nranks):
        sel = src == q
        asm.add_values(q, rows[sel], cols[sel], vals[sel])
    calls = {"reduce": 0}
    orig = SFComm.reduce
    def counting(self, *a, **kw):
        calls["reduce"] += 1
        return orig(self, *a, **kw)
    try:
        SFComm.reduce = counting
        asm.assemble()
    finally:
        SFComm.reduce = orig
    assert calls["reduce"] == 1


# ----------------------------------------------------- non-property extras
def test_sparsity_rejects_unplanned_entry():
    sp = Sparsity(2, 4, 4, np.array([0, 3]), np.array([0, 3]))
    asm = MatAssembler(sp)
    with pytest.raises(KeyError):
        asm.add_values(0, [0], [1], [1.0])


def test_reassembly_reuses_cached_flush_sf():
    """Time-stepping: same stash pattern -> the compose_inverse flush SF is
    built once and reused."""
    rows = np.array([0, 5, 5, 2]); cols = np.array([1, 0, 3, 2])
    sp = Sparsity(2, 6, 4, rows, cols)
    asm = MatAssembler(sp)
    for _ in range(2):
        asm.add_values(0, rows, cols, np.ones(4, np.float32))
        asm.assemble()
    assert asm.stats["flushes"] == 2
    first = asm._flush_cache[1]
    asm.add_values(0, rows, cols, np.ones(4, np.float32))
    asm.assemble()
    assert asm._flush_cache[1] is first
