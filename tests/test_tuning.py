"""Kernel autotuning / interpret-policy tests (repro.kernels.tuning) and
conformance sweeps for the lowerings the autotuner picks between
(sf_pack.pack_blocked, sf_unpack.segment_reduce_blocked,
sf_unpack.segment_reduce_gather, sf_pack.bcast_fused)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mpiops import get_op
from repro.kernels import ops as K
from repro.kernels import ref, tuning
from repro.kernels.sf_pack import bcast_fused, pack_blocked
from repro.kernels.sf_unpack import (segment_reduce_blocked,
                                     segment_reduce_gather)


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Each test sees an empty winner cache and leaves none behind."""
    tuning.clear_cache()
    yield
    tuning.clear_cache()


# ------------------------------------------------------- interpret policy
def test_resolve_interpret_explicit_arg_wins(monkeypatch):
    monkeypatch.setenv("REPRO_SF_INTERPRET", "1")
    assert tuning.resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_SF_INTERPRET", "0")
    assert tuning.resolve_interpret(True) is True


def test_resolve_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SF_INTERPRET", "0")
    assert tuning.resolve_interpret() is False
    monkeypatch.setenv("REPRO_SF_INTERPRET", "1")
    assert tuning.resolve_interpret() is True
    monkeypatch.delenv("REPRO_SF_INTERPRET")
    assert tuning.resolve_interpret() is (not tuning.compiled_supported())


# ------------------------------------------------------------- autotune
def _counting_candidates(counts):
    return {
        "a": lambda x: (counts.__setitem__("a", counts["a"] + 1),
                        x + 1)[1],
        "b": lambda x: (counts.__setitem__("b", counts["b"] + 1),
                        x + 1)[1],
    }


def test_autotune_sweeps_once_then_hits(monkeypatch):
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "1")
    counts = {"a": 0, "b": 0}
    cands = _counting_candidates(counts)
    args = lambda: (jnp.zeros((8,)),)
    w1 = tuning.autotune("k", ("sig",), cands, args, default="a", work=1)
    assert w1 in cands
    assert counts["a"] > 0 and counts["b"] > 0      # both were timed
    swept = dict(counts)
    w2 = tuning.autotune("k", ("sig",), cands, args, default="a", work=1)
    assert w2 == w1
    assert counts == swept                          # cache hit: no re-sweep
    st = tuning.stats()
    assert st["sweeps"] == 1 and st["hits"] == 1


def test_autotune_sweep_ns_sums_sweep_wall_time(monkeypatch):
    """``sweep_ns`` adds each sweep's wall time; a hit, a default and a pin
    add nothing."""
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "1")
    cands = _counting_candidates({"a": 0, "b": 0})
    args = lambda: (jnp.zeros((8,)),)
    assert tuning.stats()["sweep_ns"] == 0
    tuning.autotune("k", ("one",), cands, args, default="a", work=1)
    one = tuning.stats()["sweep_ns"]
    assert one > 0
    tuning.autotune("k", ("one",), cands, args, default="a", work=1)
    assert tuning.stats()["sweep_ns"] == one        # hit: no sweep
    tuning.autotune("k", ("two",), cands, args, default="a", work=1)
    assert tuning.stats()["sweep_ns"] > one
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "0")
    before = tuning.stats()["sweep_ns"]
    tuning.autotune("k", ("three",), cands, args, default="a", work=1)
    assert tuning.stats()["sweep_ns"] == before     # default: no sweep
    tuning.clear_cache()
    assert tuning.stats()["sweep_ns"] == 0


def test_autotune_small_work_takes_default(monkeypatch):
    monkeypatch.delenv("REPRO_SF_AUTOTUNE", raising=False)
    counts = {"a": 0, "b": 0}
    w = tuning.autotune("k", ("tiny",), _counting_candidates(counts),
                        lambda: (jnp.zeros((2,)),), default="b", work=4)
    assert w == "b"
    assert counts == {"a": 0, "b": 0}               # nothing was timed
    assert tuning.stats()["defaults"] == 1


def test_autotune_disabled_env(monkeypatch):
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "0")
    counts = {"a": 0, "b": 0}
    w = tuning.autotune("k", ("big",), _counting_candidates(counts),
                        lambda: (jnp.zeros((8,)),), default="a",
                        work=10**9)
    assert w == "a" and counts == {"a": 0, "b": 0}


def test_autotune_env_pin(monkeypatch):
    monkeypatch.setenv("REPRO_SF_IMPL_K", "b")
    counts = {"a": 0, "b": 0}
    w = tuning.autotune("k", ("pinme",), _counting_candidates(counts),
                        lambda: (jnp.zeros((8,)),), default="a",
                        work=10**9)
    assert w == "b" and tuning.stats()["pinned"] == 1
    monkeypatch.setenv("REPRO_SF_IMPL_K", "nope")
    with pytest.raises(ValueError, match="REPRO_SF_IMPL_K"):
        tuning.autotune("k", ("pinme2",), _counting_candidates(counts),
                        lambda: (jnp.zeros((8,)),), default="a", work=1)


def test_autotune_disqualifies_raising_candidate(monkeypatch):
    """A raising candidate is a bug in the candidate set: the sweep counts
    it and re-raises instead of letting another lowering win unnoticed."""
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "1")

    def boom(x):
        raise RuntimeError("unsupported lowering")

    with pytest.raises(RuntimeError, match="lowering 'bad' failed") as exc:
        tuning.autotune("k", ("boom",),
                        {"bad": boom, "good": lambda x: x + 1},
                        lambda: (jnp.zeros((4,)),), default="bad", work=1)
    assert "unsupported lowering" in str(exc.value.__cause__)
    assert tuning.stats()["candidate_errors"] == 1
    assert tuning.lookup(("k", "boom")) is None     # nothing memoized


def test_autotune_all_fail_falls_back_to_default(monkeypatch):
    """No fallback: a sweep whose every candidate fails raises."""
    monkeypatch.setenv("REPRO_SF_AUTOTUNE", "1")

    def boom(x):
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError, match="lowering 'bad' failed"):
        tuning.autotune("k", ("allboom",), {"bad": boom},
                        lambda: (jnp.zeros((4,)),), default="bad", work=1)
    assert tuning.lookup(("k", "allboom")) is None


# ------------------------------------------- tuned entry points: caching
def test_pack_rows_sweeps_once_and_caches_dispatch(rng):
    data = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 512, 128).astype(np.int32))
    ndisp = len(K._DISPATCH)
    for _ in range(5):
        out = K.pack_rows(data, idx, key=("t",))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(data)[np.asarray(idx)])
    # work = 128*64 = 8192 >= the tune gate -> exactly one sweep, then the
    # memoized winner behind ONE cached jitted dispatcher (no re-tracing)
    assert tuning.stats()["sweeps"] == 1
    assert len(K._DISPATCH) == ndisp + 1


def test_pack_rows_distinct_keys_tune_separately(rng):
    data = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 512, 128).astype(np.int32))
    K.pack_rows(data, idx, key=("plan_a",))
    K.pack_rows(data, idx, key=("plan_b",))
    assert tuning.stats()["sweeps"] == 2            # per-plan cache scope


def test_segment_reduce_rows_sweeps_once(rng):
    M, S, L = 256, 64, 4
    vals = jnp.asarray(rng.standard_normal((M, 32)).astype(np.float32))
    first = np.arange(0, M, L, dtype=np.int64)
    lens = np.full(S, L, np.int64)
    ids = np.repeat(np.arange(S), L)
    for _ in range(3):
        out = K.segment_reduce_rows(vals, first, lens, num_segments=S,
                                    Lmax=L, op="sum", seg_of_slot=ids,
                                    key=("t",))
    want = np.add.reduceat(np.asarray(vals), first, axis=0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert tuning.stats()["sweeps"] == 1


# ------------------------------------------- blocked kernel conformance
@pytest.mark.parametrize("unit", [(), (1,), (5,), (3, 2)])
@pytest.mark.parametrize("dt", [np.float32, np.int32])
@pytest.mark.parametrize("N,M,B", [(37, 11, 4), (64, 64, 64), (100, 130, 32),
                                   (16, 1, 8)])
def test_pack_blocked_conformance(N, M, B, unit, dt, rng):
    data = rng.standard_normal((N,) + unit).astype(dt) \
        if dt is np.float32 else rng.integers(0, 99, (N,) + unit).astype(dt)
    idx = rng.integers(0, N, M).astype(np.int32)
    d = jnp.asarray(data if unit else data[:, None])
    got = pack_blocked(d, jnp.asarray(idx), block_rows=B, interpret=True)
    if not unit:
        got = got[:, 0]
    np.testing.assert_array_equal(np.asarray(got), data[idx])


@pytest.mark.parametrize("op", ["sum", "max", "min", "prod"])
@pytest.mark.parametrize("SB", [1, 3, 8, 32])
def test_segment_reduce_blocked_conformance(op, SB, rng):
    # ragged segments including a zero-length one (identity row expected)
    lens = np.array([3, 0, 5, 1, 2, 4, 0, 7], np.int64)
    S, L = lens.size, int(lens.max())
    first = np.concatenate([[0], np.cumsum(lens)[:-1]])
    M = int(lens.sum())
    vals = rng.standard_normal((M, 3)).astype(np.float32) + 1.5
    buf = jnp.asarray(np.concatenate(
        [vals, np.zeros((L, 3), np.float32)]))    # Lmax pad rows
    got = segment_reduce_blocked(buf, first, lens, num_segments=S, Lmax=L,
                                 segs_per_block=SB, op=op, interpret=True)
    ufunc = {"sum": np.add, "max": np.maximum, "min": np.minimum,
             "prod": np.multiply}[op]
    ident = {"sum": 0.0, "max": -np.inf, "min": np.inf, "prod": 1.0}[op]
    want = np.full((S, 3), ident, np.float32)
    for s in range(S):
        for j in range(int(lens[s])):
            want[s] = ufunc(want[s], vals[int(first[s]) + j])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


_UFUNC = {"sum": np.add, "max": np.maximum, "min": np.minimum,
          "prod": np.multiply}


@pytest.mark.parametrize("op", ["sum", "max", "min", "prod"])
@pytest.mark.parametrize("unit", [(), (4,), (3, 2)])
@pytest.mark.parametrize("dt", [np.float32, np.int32, jnp.bfloat16])
@pytest.mark.parametrize("L", [1, 4, 8])
def test_segment_reduce_gather_conformance(op, unit, dt, L, rng):
    """The ``gather`` lowering equals a numpy loop that combines each
    segment's rows in sorted order in 32 bits, and the XLA segment op, on
    ragged segments with zero-length ones (the op's identity) and segments
    of ``L`` rows at the buffer's end."""
    lens = np.minimum(np.array([3, 0, 5, L, 1, 2, 0, 7, 4, L]), L)
    S = lens.size
    first = np.concatenate([[0], np.cumsum(lens)[:-1]])
    M = int(lens.sum())
    vals = rng.integers(1, 4, (M,) + unit) if dt is np.int32 else \
        rng.standard_normal((M,) + unit) + 1.5
    vals = np.asarray(jnp.asarray(vals.astype(np.float32)).astype(dt))
    acc = np.int32 if dt is np.int32 else np.float32
    want = np.full((S,) + unit, get_op(op).identity_of(acc), acc)
    for s in range(S):
        for j in range(int(lens[s])):
            want[s] = _UFUNC[op](want[s], vals[first[s] + j].astype(acc))
    want = np.asarray(jnp.asarray(want).astype(dt))
    ids = np.repeat(np.arange(S), lens)
    got = np.asarray(segment_reduce_gather(jnp.asarray(vals), first, lens,
                                           ids, Lmax=L, op=op))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    xla = ref.unpack_segment_ref(jnp.asarray(vals), jnp.asarray(ids),
                                 num_segments=S, op=op)
    tol = 1e-2 if dt is jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(got.astype(np.float32),
                               np.asarray(xla).astype(np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("L,offered", [(1, True), (K.SEG_GATHER_MAX_LEN, True),
                                       (K.SEG_GATHER_MAX_LEN + 1, False)])
def test_segment_reduce_gather_offered_up_to_bound(L, offered):
    """The sweep times the ``gather`` lowering for segments of at most
    ``SEG_GATHER_MAX_LEN`` rows, for kernel and non-kernel dtypes alike,
    where the caller gives each sorted row's segment, and never past the
    bound."""
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        impls = K._seg_candidates(64, L, "sum", (4,), dt, True, True)
        assert ("gather" in impls) is offered
        impls = K._seg_candidates(64, L, "sum", (4,), dt, True, False)
        assert "gather" not in impls


def test_bcast_fused_conformance(rng):
    Nr, Nl, E = 50, 40, 30
    root = rng.standard_normal((Nr, 4)).astype(np.float32)
    leaf = rng.standard_normal((Nl, 4)).astype(np.float32)
    gr = rng.integers(0, Nr, E).astype(np.int64)
    gl = rng.permutation(Nl)[:E].astype(np.int64)   # duplicate-free dests
    got = bcast_fused(jnp.asarray(root), jnp.asarray(leaf),
                      jnp.asarray(gr), jnp.asarray(gl), interpret=True)
    want = leaf.copy()
    want[gl] = root[gr]
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("scalar", [False, True])
def test_local_bcast_rows_conformance(scalar, rng):
    Nr, Nl, E = 33, 29, 20
    shape_r = (Nr,) if scalar else (Nr, 3)
    shape_l = (Nl,) if scalar else (Nl, 3)
    root = rng.standard_normal(shape_r).astype(np.float64)  # dtype cast path
    leaf = rng.standard_normal(shape_l).astype(np.float32)
    gr = rng.integers(0, Nr, E).astype(np.int64)
    gl = rng.permutation(Nl)[:E].astype(np.int64)
    got = K.local_bcast_rows(jnp.asarray(root), jnp.asarray(leaf), gr, gl,
                             key=("t",))
    want = leaf.copy()
    want[gl] = root[gr].astype(np.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
