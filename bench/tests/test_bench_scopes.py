"""The scope reader: device time by program scope and idle time by program
span, on a synthetic two-device trace and on the trace recorded on a TPU
v5e (``data/``), checked against ``trace.py``'s reduction."""

import gzip
import os
import shutil

import pytest

from bench import harness, scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "halo-v5e.xplane.pb.gz")

READERS = ["halo_sf_unpack_pct", "halo_sf_combine_pct", "cg_spmv_pct",
           "cg_sf_pct", "cg_readback_idle_pct", "serve_attn_pct",
           "serve_sf_pct", "serve_prefill_pct", "serve_sample_idle_pct"]
SAMPLES = {"pairs": 1, "cg_iters": 1, "serve_units": [1]}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    pb = tmp_path_factory.mktemp("rec") / "t.xplane.pb"
    with gzip.open(RECORDED) as src, open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(pb)


# ----------------------------------------------------------- synthetic
def _space(path):
    """Two devices; host spans bench.window [0, 100) > cg.iter [15, 90) >
    cg.readback [60, 80) (ns).  Each device runs a while op (its own time
    2 ns) around an op under sf.pack inside mat.offdiag, then an unscoped
    op; device 1's ops run twice as long."""
    space = scopes._xspace_class()()

    def plane(name, events, tf_ops=None):
        p = space.planes.add(name=name)
        p.stat_metadata[1].name = scopes.TF_OP
        line = p.lines.add(name=trace.OP_LINE if tf_ops else "python",
                           timestamp_ns=0)
        for i, (ename, s, e) in enumerate(events, start=1):
            p.event_metadata[i].name = ename
            if tf_ops and tf_ops[i - 1]:
                st = p.event_metadata[i].stats.add(metadata_id=1)
                st.str_value = tf_ops[i - 1]
            line.events.add(metadata_id=i, offset_ps=s * 1000,
                            duration_ps=(e - s) * 1000)

    plane("/host:CPU", [("bench.window", 0, 100), ("cg.iter", 15, 90),
                        ("cg.readback", 60, 80), ("PjitFunction(f)", 12, 14)])
    ops = ["jit(cg_step)/while", "jit(cg_step)/mat.offdiag/sf.pack/gather",
           "jit(f)/add"]
    for dev, k in (("/device:TPU:0", 1), ("/device:TPU:1", 2)):
        plane(dev, [("while", 20, 20 + 12 * k),
                    ("gather", 21, 21 + 10 * k), ("add", 50, 50 + 4 * k)],
              tf_ops=ops)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_synthetic_trace(tmp_path):
    path = str(tmp_path / "s.xplane.pb")
    _space(path)
    s = scopes.reduce_scopes(path)
    ns = 1e-9
    assert s["devices"] == 2 and s["window_s"] == pytest.approx(100 * ns)
    assert s["busy_s"] == pytest.approx((16 + 32) / 2 * ns)
    comp = s["components"]
    # inclusive: sf.pack's gather counts under mat.offdiag and jit(cg_step)
    assert comp["sf.pack"] == pytest.approx((10 + 20) / 2 * ns)
    assert comp["mat.offdiag"] == comp["sf.pack"]
    assert comp["jit(cg_step)"] == pytest.approx((12 + 24) / 2 * ns)
    assert s["unscoped_s"] == pytest.approx((2 + 4 + 4 + 8) / 2 * ns)
    assert scopes.scope_s(s, ("sf.pack", "mat.offdiag")) == \
        pytest.approx(comp["sf.pack"])                  # each op once
    assert scopes.scope_s(s, lambda n: n.startswith("sf.")) == \
        comp["sf.pack"]
    assert scopes.scope_s(s, "moe.dispatch") is None
    # idle, each gap named at its middle: [0, 20) outside any span; then
    # [32, 50) and [44, 50) in cg.iter; [54, 100) and [58, 100) in
    # cg.readback
    idle = s["idle"]
    assert idle["outside any span"] == pytest.approx(20 * ns)
    assert idle["cg.iter"] == pytest.approx((18 + 6) / 2 * ns)
    assert idle["cg.readback"] == pytest.approx((46 + 42) / 2 * ns)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert scopes.idle_pct(s, "cg.readback") == pytest.approx(44.0)
    assert scopes.idle_pct(s, "serve.sample") is None
    assert s["spans"] == ["cg.iter", "cg.readback"]


# ------------------------------------------------------------ recorded
def test_recorded_busy_window_and_idle_equal_trace_py(recorded):
    s = scopes.reduce_scopes(recorded)
    r = trace.reduce_trace(recorded, top=10 ** 6)
    assert s["devices"] == r["devices"] == 1
    assert s["busy_s"] == pytest.approx(r["busy_s"], rel=1e-12)
    assert s["window_s"] == pytest.approx(r["window_s"], rel=1e-12)
    # every op's self time is credited once, scoped or not
    assert sum(s["scope_paths"].values()) == pytest.approx(
        sum(v for _, v in r["device_ops"]), rel=1e-9)
    # the same spans name the same gaps when the program opened none
    assert s["idle"] == pytest.approx(dict(r["idle_gaps"]), rel=1e-9)


def test_recorded_segment_reduce_time_equals_its_ops(recorded):
    """Time under ``jit(segment_reduce_blocked)`` is the sum, in
    ``trace.py``'s device ops, of the ops whose name path holds it."""
    s = scopes.reduce_scopes(recorded)
    r = dict(trace.reduce_trace(recorded, top=10 ** 6)["device_ops"])
    dev = next(p for p in scopes.read_xspace(recorded).planes
               if p.name.startswith(trace.DEVICE_PREFIX))
    paths = scopes._paths(dev)
    names = {dev.event_metadata[mid].name[:trace.NAME_CHARS]
             for mid, (comps, _) in paths.items()
             if "jit(segment_reduce_blocked)" in comps}
    want = sum(r[n] for n in names if n in r)
    assert want > 0
    assert s["components"]["jit(segment_reduce_blocked)"] == \
        pytest.approx(want, rel=1e-9)


def test_recorded_trace_has_no_program_scope(recorded):
    """Recorded before the program named its work: everything is the
    unscoped remainder, so a scope metric has nothing to read."""
    s = scopes.reduce_scopes(recorded)
    assert s["unscoped_s"] == pytest.approx(s["busy_s"], rel=1e-6)
    assert scopes.scope_pct(s, "sf.unpack") is None
    assert s["spans"] == ["halo.g2l", "halo.l2g"]


# -------------------------------------------------------------- readers
@pytest.mark.parametrize("name", READERS + ["setup_autotune_s"])
def test_reader_without_a_trace(name):
    ctx = {"samples": dict(SAMPLES), "window_s": 1.0, "setup_s": 1.0,
           "peaks": None, "trace": None}
    assert harness.metric_reader(name)(ctx) is None


def test_setup_autotune_s_reads_the_sweep_counter():
    from repro.kernels import tuning
    ctx = {"samples": {}, "trace": {"busy_s": 1.0, "window_s": 1.0}}
    got = harness.metric_reader("setup_autotune_s")(ctx)
    assert got == tuning.stats()["sweep_ns"] / 1e9


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_trace_without_program_scopes(name, recorded,
                                                  tmp_path, monkeypatch):
    """A program that names nothing (the recorded trace) gives no reading
    rather than a zero, and nothing raises."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "t.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    ctx = {"samples": dict(SAMPLES), "window_s": 1.0, "setup_s": 1.0,
           "peaks": None, "trace": {"busy_s": 1.0, "window_s": 1.0}}
    assert harness.metric_reader(name)(ctx) is None


def test_for_run_parses_once(recorded, tmp_path, monkeypatch, capsys):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "t.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    ctx = {"trace": {}}
    first = scopes.for_run(ctx)
    assert scopes.for_run(ctx) is first
    err = capsys.readouterr().err
    assert err.count("trace reduced in") == 1
    assert "scopes: unscoped" in err and "scopes: idle in halo.g2l" in err
