"""The trace reduction: interval arithmetic, and a small trace recorded on
a TPU v5e by ``run.py --trace 1`` (``data/``)."""

import glob
import gzip
import os
import shutil

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    iv = np.array([[0, 2], [1, 3], [5, 6], [5.5, 5.7]], dtype=float)
    assert trace.union_length(iv) == 4.0
    assert trace.gaps(iv, -1, 8).tolist() == [[-1, 0], [3, 5], [6, 8]]
    assert trace.gaps(iv, 1, 2.5).tolist() == []
    assert trace.union_length(np.zeros((0, 2))) == 0.0


def test_innermost_span():
    idx = trace._SpanIndex([(0, 10, "serve.step"), (2, 3, "serve.submit"),
                            (11, 12, "serve.submit")])
    assert idx.at(2.5) == "serve.submit"
    assert idx.at(5) == "serve.step"
    assert idx.at(10.5) == "outside any span"
    assert idx.at(-1) == "outside any span"


def test_self_time_of_nested_ops():
    evs = [("while", 0, 10), ("a", 1, 3), ("b", 4, 9), ("b.inner", 5, 6),
           ("c", 12, 13)]
    got = {n: t for n, _, _, t in trace._self_times(evs)}
    assert got == {"while": 3, "a": 2, "b": 4, "b.inner": 1, "c": 1}


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))


def test_a_trace_is_recorded():
    assert RECORDED


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path, tmp_path):
    pb = tmp_path / "t.xplane.pb"
    with gzip.open(path) as src, open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = trace.reduce_trace(str(pb))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < len(r["device_ops"]) <= trace.TOP
    assert 0 < len(r["idle_gaps"]) <= trace.TOP
    busy = sum(v for _, v in r["device_ops"])
    assert busy <= r["window_s"] * 1.000001
    idle = sum(v for _, v in r["idle_gaps"])
    # idle names cover the window's idle time, the top 10 at most all of it
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert all(len(n) <= trace.NAME_CHARS for n, _ in r["device_ops"])
    names = {n for n, _ in r["idle_gaps"]}
    assert names & {"halo.g2l", "halo.l2g", "serve.step", "serve.submit",
                    "cg.set", "outside any span"}
