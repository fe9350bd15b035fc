"""``-log_view`` for star forests (core/sflog.py): registry unit behaviour,
exact per-event exchange counts and byte volumes over the paper's consumer
paths (CG SpMV, DMDA halo, MoE decode dispatch, bucketed DDP), zero-added-
retrace proofs on the fused ``cg_async`` / decode-step / jitted-DDP paths,
identical event streams across backends on the shared ``sf_fixtures``
matrix, host spans in a profiler trace and device scopes in the lowered
programs, and the <2%-of-one-exchange disabled-overhead bound."""

import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sf_fixtures import FIXTURES
from repro.core import SFComm, StarForest, sflog
from repro.core.dynplan import DynPlan
from repro.sparse.parmat import ParCSR

INPROCESS_BACKENDS = ["global", "pallas"]
F32 = 4  # itemsize every byte formula below is built on


@pytest.fixture
def logged():
    """Event logging on, registry clean, prior mode restored afterwards."""
    old = sflog.set_mode("on")
    sflog.reset()
    yield
    sflog.reset()
    sflog.set_mode(old)


def fig2_sf() -> StarForest:
    """The paper's Fig 2 graph (quickstart): 3 ranks, 5 roots, 7 leaves."""
    sf = StarForest(3)
    sf.set_graph(0, 2, [0, 1, 2], [(0, 0), (0, 1), (1, 0)])
    sf.set_graph(1, 2, [0, 2], [(0, 1), (2, 0)], nleafspace=4)
    sf.set_graph(2, 1, [0, 1], [(2, 0), (1, 1)])
    return sf.setup()


@pytest.fixture
def tridiag():
    """4-rank tridiagonal SPD ParCSR (the CG operator of test_solvers)."""
    n = 64
    rows, cols, vals = [], [], []
    for i in range(n):
        rows += [i]; cols += [i]; vals += [2.5]
        if i > 0:
            rows += [i]; cols += [i - 1]; vals += [-1.0]
        if i < n - 1:
            rows += [i]; cols += [i + 1]; vals += [-1.0]
    return ParCSR.from_global_coo(4, n, n, np.array(rows), np.array(cols),
                                  np.array(vals))


# --------------------------------------------------------------------------
# registry unit behaviour
# --------------------------------------------------------------------------
def test_mode_parse_and_set_mode_roundtrip():
    old = sflog.set_mode("off")
    try:
        assert not sflog.enabled() and sflog.mode() == "off"
        assert sflog.set_mode("fence") == "off"
        assert sflog.mode() == "fence" and sflog.enabled()
        assert sflog.set_mode("1") == "fence"
        assert sflog.mode() == "on"
        with pytest.raises(ValueError):
            sflog.set_mode("loud")
        assert sflog.mode() == "on"   # failed parse leaves mode untouched
    finally:
        sflog.set_mode(old)


def test_counter_unique_mints_fresh_instances():
    a = sflog.counter("t_sflog.u", unique=True)
    b = sflog.counter("t_sflog.u", unique=True)
    assert a is not b and a.name != b.name
    a.add(3); b.add()
    snap = sflog.counters()
    assert snap[a.name] == 3 and snap[b.name] == 1
    # non-unique access aliases to one shared instance
    assert sflog.counter("t_sflog.shared") is sflog.counter("t_sflog.shared")


def test_tag_values_bounded_with_overflow_bucket(logged):
    ev = sflog.event("TagCap")
    for i in range(20):
        ev.tag("rid", f"r{i}")
    vals = ev.tags["rid"]
    assert len(vals) == 9 and vals["..."] == 12  # 8 distinct + overflow


def test_stash_claim_is_exactly_once(logged):
    class Tok:
        pass
    tok = Tok()
    sflog.stash_pending(tok, "PairEnd", 128.0, {"k": "v"})
    info = sflog.claim_pending(tok)
    assert info is not None and info[0] == "PairEnd" and info[2] == 128.0
    assert sflog.claim_pending(tok) is None    # second claimant gets nothing

    class Slotted:                              # frozen token: stash no-ops
        __slots__ = ()
    s = Slotted()
    sflog.stash_pending(s, "PairEnd", 1.0)
    assert sflog.claim_pending(s) is None


def test_events_delta_and_exchange_totals(logged):
    sflog.op_end("SFThing", sflog.op_begin(), nbytes=100.0)
    before = sflog.events_snapshot()
    sflog.op_end("SFThing", sflog.op_begin(), nbytes=100.0)
    sflog.op_end("SFOther", sflog.op_begin(), nbytes=8.0)
    sflog.op_end("NotComm", sflog.op_begin(), nbytes=1e9)
    d = sflog.events_delta(before)
    assert d["SFThing"] == {"count": 1, "traced": 0, "bytes": 100.0}
    assert d["SFOther"]["count"] == 1
    # totals only see SF* events; NotComm's gigabyte is invisible
    assert sflog.exchange_totals(d) == {"exchanges": 2.0, "bytes": 108.0}
    # traced executions count as exchanges (structure inside jit is real)
    sflog.event("SFThing").traced += 5
    assert sflog.exchange_totals()["exchanges"] == 8.0


def test_overlap_efficiency_from_aggregates(logged):
    a, b = sflog.event("HaloSync"), sflog.event("HaloSplit")
    a.count, a.time = 4, 0.8
    b.count, b.time = 8, 0.8
    assert sflog.overlap_efficiency("HaloSync", "HaloSplit") == \
        pytest.approx(2.0)
    assert sflog.overlap_efficiency("Missing", "HaloSplit") is None
    b.time = 0.0
    assert sflog.overlap_efficiency("HaloSync", "HaloSplit") is None


def test_timed_and_context_tagging(logged):
    """A span is one execution of its event, its meta the event's tags;
    entered while jit traces, it counts ``traced`` only."""
    with sflog.span("Scoped", rid="r7", step=3):
        pass
    ev = sflog.event("Scoped")
    assert ev.count == 1 and ev.traced == 0 and ev.bytes == 0.0
    assert ev.tags["rid"] == {"r7": 1} and ev.tags["step"] == {"3": 1}

    @jax.jit
    def f(x):
        with sflog.span("Scoped", rid="r8"):
            return x + 1

    for _ in range(3):
        jax.block_until_ready(f(jnp.ones(2)))
    assert ev.count == 1 and ev.traced == 1
    assert ev.tags["rid"] == {"r7": 1}


def test_log_view_and_dump_json_render(logged):
    sflog.op_end("SFDemo", sflog.op_begin(), nbytes=2048.0)
    sflog.counter("t_sflog.render").add(2)
    view = sflog.log_view()
    assert view.startswith("SF log_view  (mode=on)")
    assert "Event" in view and "MBytes" in view
    assert any(line.startswith("SFDemo") and " 1 " in line
               for line in view.splitlines())
    assert "t_sflog.render = 2" in view
    d = json.loads(sflog.dumps_json())
    assert d["mode"] == "on"
    assert d["events"]["SFDemo"]["count"] == 1
    assert d["events"]["SFDemo"]["bytes"] == 2048.0
    assert d["counters"]["t_sflog.render"] >= 2


def test_sf_view_three_shapes():
    sf = fig2_sf()
    v = sflog.sf_view(sf)
    assert v["type"] == "StarForest" and v["nranks"] == 3
    assert v["nroots"] == 5 and v["nleaves"] == 7
    assert v["edges"]["total"] == v["edges"]["local"] + v["edges"]["remote"]
    assert sum(d * c for d, c in v["root_degree_histogram"].items()) == 7

    comm = SFComm(sf, backend="global")
    vc = sflog.sf_view(comm)
    assert vc["backend"] == "global" and "plan_signature" in vc
    text = sflog.format_sf_view(comm)
    assert text.startswith("SFView: StarForest (3 ranks): 5 roots, 7 leaves")
    assert "backend: global" in text

    plan = DynPlan(4, 6, unit=(3,), label="t_sflog")
    vp = sflog.sf_view(plan)
    assert vp["type"] == "DynPlan" and vp["nroots"] == 4
    assert "DynPlan" in sflog.format_sf_view(plan)


# --------------------------------------------------------------------------
# exact counts + bytes on the paper's consumer paths
# --------------------------------------------------------------------------
def test_cg_spmv_exact_counts_and_bytes(tridiag, logged, rng):
    """Eager SpMV is one split-phase pair: count, bytes (halo edges x 4B
    f32 row) and a strictly positive overlap window, exactly per call."""
    b = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    jax.block_until_ready(tridiag.spmv(b))     # autotune outside the window
    sflog.reset()
    for _ in range(4):
        jax.block_until_ready(tridiag.spmv(b))
    nb = float(tridiag.sf.nedges_total * F32)
    d = sflog.events_snapshot()
    assert d["SFBcastBegin"] == {"count": 4, "traced": 0, "bytes": 4 * nb}
    assert d["SFBcastEnd"] == {"count": 4, "traced": 0, "bytes": 4 * nb}
    assert sflog.event("SFBcastEnd").overlap > 0.0
    assert "Split-phase overlap windows" in sflog.log_view()


def test_cg_blocking_traces_once_executes_eagerly_once(tridiag, logged, rng):
    """cg(): the initial residual SpMV runs eagerly (1 count), the jitted
    step traces its SpMV exactly once — iterations add nothing."""
    from repro.solvers.cg import cg
    b = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    jax.block_until_ready(tridiag.spmv(b))
    sflog.reset()
    res = cg(tridiag.spmv, b, tol=1e-6, maxiter=300)
    assert res.converged and res.iters > 5
    nb = float(tridiag.sf.nedges_total * F32)
    d = sflog.events_snapshot()
    assert d["SFBcastBegin"] == {"count": 1, "traced": 1, "bytes": nb}
    assert d["SFBcastEnd"] == {"count": 1, "traced": 1, "bytes": nb}


def test_dmda_halo_exact_counts_and_bytes(logged, rng):
    """DMGlobalToLocal is one SFBcast (halo edges x row bytes), exactly
    counted per call; DMLocalToGlobal is one SFReduce."""
    from repro.meshdist.dmda import DMDA
    da = DMDA((9, 7), 4, stencil="star", width=1)
    g = jnp.asarray(rng.standard_normal(da.nglobal).astype(np.float32))
    lv = da.global_to_local(g, backend="global")  # warm the cached comm
    sflog.reset()
    for _ in range(3):
        lv = da.global_to_local(g, backend="global")
    da.local_to_global(lv, backend="global")
    nb = float(da.sf.nedges_total * F32)
    d = sflog.events_snapshot()
    assert d["SFBcast"] == {"count": 3, "traced": 0, "bytes": 3 * nb}
    assert d["SFReduce"] == {"count": 1, "traced": 0, "bytes": nb}


def test_moe_decode_exact_event_stream(logged):
    """One eager decode-shape MoE layer = one fused two-field reduce
    (slots x (d_model+1) f32: payload + gate column, surfaced as both the
    DynPlan event and the FieldBundle event underneath) + one combine
    bcast (slots x d_model f32).  slots = B*S*topk = 4*1*2 = 8."""
    from repro.configs import get_config
    from repro.models import moe
    cfg = get_config("phi3.5-moe-42b-a6.6b").smoke_config().scaled(
        dtype="float32")
    p = jax.tree.map(lambda a: a[0],
                     moe.init_moe(jax.random.PRNGKey(0), cfg, 1))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, cfg.d_model)) * 0.3
    moe.plan_cache().clear()
    moe.moe_layer(x, p, cfg, dispatch="sf")      # plan build + autotune
    sflog.reset()
    for _ in range(2):
        moe.moe_layer(x, p, cfg, dispatch="sf")
    slots = 4 * 1 * 2
    nb_red = float(slots * (cfg.d_model + 1) * F32)
    nb_bc = float(slots * cfg.d_model * F32)
    d = sflog.events_snapshot()
    assert d["SFDynReduce"] == {"count": 2, "traced": 0, "bytes": 2 * nb_red}
    assert d["SFReduceMulti"] == {"count": 2, "traced": 0,
                                  "bytes": 2 * nb_red}
    assert d["SFDynBcast"] == {"count": 2, "traced": 0, "bytes": 2 * nb_bc}
    # and the migrated PlanCache counters saw 1 miss + repeat hits
    st = moe.plan_cache().stats()
    assert st["misses"] == 1 and st["hits"] == 2


def test_ddp_bucketed_exact_counts_and_bytes(logged, rng):
    """One eager bucketed allreduce: one DDP begin/end pair carrying
    grains x plan.total_bytes, one fused SFReduceMulti pair per bucket
    whose byte totals sum to exactly the same volume (fusion changes the
    exchange count, never the bytes)."""
    from repro.training.ddp import (BucketPlan, DDPGradReducer,
                                    reset_ddp_plan_cache)
    tree = {"w": rng.standard_normal((8, 4)).astype(np.float32),
            "b": rng.standard_normal((4,)).astype(np.float32),
            "head": rng.standard_normal((4, 6)).astype(np.float32)}
    plan = BucketPlan.for_tree(tree, 64)
    assert plan.nbuckets > 1
    reset_ddp_plan_cache()
    grains = 4
    red = DDPGradReducer(plan, world=2, grains=grains, backend="global")
    gg = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal((grains,) + a.shape)
                              .astype(a.dtype)), tree)
    jax.block_until_ready(jax.tree_util.tree_leaves(red.allreduce(gg))[0])
    sflog.reset()
    out = red.allreduce(gg)
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
    vol = float(grains * plan.total_bytes)
    d = sflog.events_snapshot()
    assert d["DDPBucketReduceBegin"] == {"count": 1, "traced": 0,
                                         "bytes": vol}
    assert d["DDPBucketReduceEnd"]["count"] == 1
    assert d["SFReduceMultiBegin"] == {"count": plan.nbuckets, "traced": 0,
                                       "bytes": vol}
    assert d["SFReduceMultiEnd"]["count"] == plan.nbuckets
    assert d["SFReduceMultiEnd"]["bytes"] == vol


# --------------------------------------------------------------------------
# zero added retraces
# --------------------------------------------------------------------------
def test_jitted_spmv_no_growth_across_cached_calls(tridiag, logged, rng):
    """Hooks fire at dispatch only: once a jitted SpMV is compiled, repeat
    calls add neither eager counts nor traced counts to any event."""
    b = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    f = jax.jit(tridiag.spmv)
    jax.block_until_ready(f(b))                # compile: traced bumps here
    assert sflog.event("SFBcastEnd").traced >= 1
    before = sflog.events_snapshot()
    for _ in range(3):
        jax.block_until_ready(f(b))
    assert sflog.events_delta(before) == {}


def test_cg_async_fused_loop_zero_added_retraces(tridiag, logged, rng):
    """cg_async with logging on performs the identical matvec invocations
    (Python-level = eager + trace) as with logging off, and the recorded
    split: 1 eager warmup pair + 2 traced hooks (residual + while_loop
    body), with bytes counted for the eager execution only."""
    b = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    from repro.solvers.cg import cg_async
    calls = []

    def probe(v):
        calls.append(1)
        return tridiag.spmv(v)

    sflog.set_mode("off")
    cg_async(probe, b, maxiter=8, check_every=0)
    n_off = len(calls)
    calls.clear()
    sflog.set_mode("on")
    sflog.reset()
    cg_async(probe, b, maxiter=8, check_every=0)
    assert len(calls) == n_off                 # logging added zero retraces
    nb = float(tridiag.sf.nedges_total * F32)
    d = sflog.events_snapshot()
    assert d["SFBcastBegin"] == {"count": 1, "traced": 2, "bytes": nb}
    assert d["SFBcastEnd"] == {"count": 1, "traced": 2, "bytes": nb}


def test_serving_decode_steps_counted_without_retrace(logged):
    """Decode-step path: every engine step is one ``serve.decode`` event,
    every admission one ``serve.prefill`` (and one ``serve.cache_insert``),
    and a second batch of requests compiles zero new programs (the decode
    program cache miss count stays flat)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving.engine import Request, ServeEngine
    cfg = get_config("qwen3-4b").smoke_config().scaled(dtype="float32",
                                                       remat="none")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, batch=2, s_max=64)
    done = eng.run([Request(i, [1 + i, 2, 3], max_new=4) for i in range(4)])
    assert len(done) == 4
    assert sflog.event("serve.decode").count == eng.steps
    assert sflog.event("serve.prefill").count == 4
    assert sflog.event("serve.cache_insert").count == 4
    assert sflog.event("serve.sample").count == eng.steps + 4
    misses = eng.programs.stats()["misses"]
    done2 = eng.run([Request(10 + i, [5 + i, 2, 3], max_new=4)
                     for i in range(4)])
    assert len(done2) == 4
    assert eng.programs.stats()["misses"] == misses
    assert sflog.event("serve.decode").count == eng.steps
    assert sflog.event("serve.prefill").count == 8


def test_ddp_jitted_train_path_zero_added_retraces(logged, rng):
    """The bucketed allreduce traced into jit: hooks mark traced once at
    compile, then cached executions add nothing to any event."""
    from repro.training.ddp import (BucketPlan, DDPGradReducer,
                                    reset_ddp_plan_cache)
    tree = {"w": rng.standard_normal((8, 4)).astype(np.float32),
            "b": rng.standard_normal((4,)).astype(np.float32)}
    plan = BucketPlan.for_tree(tree, None)
    reset_ddp_plan_cache()
    red = DDPGradReducer(plan, world=2, grains=2, backend="global")
    gg = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal((2,) + a.shape)
                              .astype(a.dtype)), tree)
    f = jax.jit(red.allreduce)
    jax.block_until_ready(jax.tree_util.tree_leaves(f(gg))[0])
    assert sflog.event("SFReduceMultiEnd").traced >= 1
    before = sflog.events_snapshot()
    for _ in range(3):
        jax.block_until_ready(jax.tree_util.tree_leaves(f(gg))[0])
    assert sflog.events_delta(before) == {}


# --------------------------------------------------------------------------
# backend conformance: identical event streams
# --------------------------------------------------------------------------
def _event_stream(sf, backend):
    """counts+bytes the facade records for a fixed op sequence (time and
    overlap are machine-dependent and excluded)."""
    sflog.reset()
    comm = SFComm(sf, backend=backend)
    roots = jnp.reshape(
        jnp.arange(2.0 * sf.nroots_total, dtype=jnp.float32),
        (sf.nroots_total, 2))
    leaves = jnp.zeros((sf.nleafspace_total, 2), jnp.float32)
    comm.bcast(roots, leaves, "replace")
    comm.reduce(jnp.ones_like(leaves), jnp.zeros_like(roots), "sum")
    pend = comm.bcast_begin(roots, "replace")
    jax.block_until_ready(pend.end(leaves))
    return sflog.events_snapshot()


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_backend_event_stream_conformance(name, logged):
    """Every in-process backend emits the identical event stream (names,
    counts, traced, bytes) for the same SF and op sequence, and the byte
    volumes are exactly edges x 8B (2-wide f32 rows)."""
    sf = FIXTURES[name]()
    streams = {b: _event_stream(sf, b) for b in INPROCESS_BACKENDS}
    ref = streams["global"]
    nb = float(sf.nedges_total * 2 * F32)
    assert ref["SFBcast"] == {"count": 1, "traced": 0, "bytes": nb}
    assert ref["SFReduce"] == {"count": 1, "traced": 0, "bytes": nb}
    assert ref["SFBcastBegin"]["count"] == 1
    assert ref["SFBcastEnd"]["bytes"] == nb
    for b, got in streams.items():
        assert got == ref, f"backend {b} diverged on fixture {name}"


SFLOG_SHARDMAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r}); sys.path.insert(0, {tests!r})
    import jax, jax.numpy as jnp
    from sf_fixtures import FIXTURES
    from repro.core import SFComm, sflog
    sflog.set_mode("on")

    def stream(sf, backend):
        sflog.reset()
        comm = SFComm(sf, backend=backend)
        roots = jnp.reshape(
            jnp.arange(2.0 * sf.nroots_total, dtype=jnp.float32),
            (sf.nroots_total, 2))
        leaves = jnp.zeros((sf.nleafspace_total, 2), jnp.float32)
        comm.bcast(roots, leaves, "replace")
        comm.reduce(jnp.ones_like(leaves), jnp.zeros_like(roots), "sum")
        pend = comm.bcast_begin(roots, "replace")
        jax.block_until_ready(pend.end(leaves))
        return sflog.events_snapshot()

    for name in sorted(FIXTURES):
        sf = FIXTURES[name]()
        ref = stream(sf, "global")
        got = stream(sf, "shardmap")
        assert got == ref, (name, ref, got)
        print(name, "OK")
    print("SFLOG-SHARDMAP-CONFORMANCE-OK")
""")


@pytest.mark.slow
def test_shardmap_event_stream_conformance_subprocess():
    """The shardmap backend (8 fake devices, own process) emits the same
    event stream as the global reference on every shared fixture."""
    import os
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    tests = os.path.abspath(os.path.dirname(__file__))
    script = SFLOG_SHARDMAP_SCRIPT.format(src=src, tests=tests)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "SFLOG-SHARDMAP-CONFORMANCE-OK" in r.stdout


# --------------------------------------------------------------------------
# disabled overhead
# --------------------------------------------------------------------------
def test_disabled_overhead_under_two_percent_of_one_exchange():
    """With logging off each facade hook is one integer test; a generous
    12-hooks-per-exchange budget must cost <2% of the cheapest eager
    exchange on the smallest graph in the suite."""
    old = sflog.set_mode("off")
    try:
        sf = fig2_sf()
        comm = SFComm(sf, backend="global")
        roots = jnp.arange(float(sf.nroots_total), dtype=jnp.float32)
        leaves = jnp.zeros(sf.nleafspace_total, jnp.float32)
        jax.block_until_ready(comm.bcast(roots, leaves, "replace"))
        t_ex = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(30):
                out = comm.bcast(roots, leaves, "replace")
            jax.block_until_ready(out)
            t_ex = min(t_ex, (time.perf_counter() - t0) / 30)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            sflog.enabled()
        t_hook = (time.perf_counter() - t0) / n
        assert 12 * t_hook < 0.02 * t_ex, \
            f"hook {t_hook * 1e9:.0f}ns vs exchange {t_ex * 1e6:.1f}us"
        # a span (with meta) per exchange, the profiler not capturing
        t_span = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n // 4):
                with sflog.span("t_sflog.overhead", rid=1):
                    pass
            t_span = min(t_span, (time.perf_counter() - t0) / (n // 4))
        assert t_span < 0.02 * t_ex, \
            f"span {t_span * 1e9:.0f}ns vs exchange {t_ex * 1e6:.1f}us"
    finally:
        sflog.set_mode(old)


# --------------------------------------------------------------------------
# spans in the profiler's trace, scopes in the lowered programs
# --------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["off", "on"])
def test_span_writes_host_event_with_meta_into_profiler_trace(mode,
                                                              tmp_path):
    """A span is a host event of a captured CPU trace, its meta the event's
    stats, whether or not event logging is on."""
    from jax.profiler import ProfileData
    old = sflog.set_mode(mode)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with sflog.span("t_sflog.span", rid=7, bucket=256):
                jax.block_until_ready(jnp.ones(4) + 1)
        finally:
            jax.profiler.stop_trace()
    finally:
        sflog.set_mode(old)
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    hits = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "t_sflog.span"]
    assert hits == [{"rid": 7, "bucket": 256}]


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_decode_and_prefill_programs_carry_model_scopes():
    """The engine's decode program names attention and the MoE stages; the
    prefill program names attention and its own scope."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving.engine import ServeEngine
    cfg = get_config("phi3.5-moe-42b-a6.6b").smoke_config().scaled(
        dtype="float32", moe_dispatch="sf")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, batch=2, s_max=32)
    tok = jnp.zeros(2, jnp.int32)
    txt = eng._decode_fn().lower(params, tok, eng.cache,
                                 jnp.zeros(2, jnp.int32)).as_text(
                                     debug_info=True)
    for name in ("serve.decode", "model.attn", "moe.route", "moe.dispatch",
                 "moe.experts", "moe.combine", "sf.pack"):
        assert name in txt, name
    txt = eng._prefill_fn(8).lower(params, jnp.zeros((1, 8), jnp.int32),
                                   jnp.zeros(1, jnp.int32)).as_text(
                                       debug_info=True)
    for name in ("serve.prefill", "model.attn", "moe.dispatch"):
        assert name in txt, name


def test_cg_step_program_carries_operator_scopes(tridiag, rng):
    """One CG iteration names the local and off-diagonal SpMV, the ghost
    exchange and the vector ops.  Every ghost-buffer row is a leaf, so the
    exchange's unpack program is the identity form: it is there, but no
    device op runs under ``sf.unpack``."""
    from repro.solvers.cg import step_program
    v = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    txt = step_program(tridiag.spmv).lower(v, v, v, jnp.float32(1.0)) \
        .as_text(debug_info=True)
    for name in ("jit(cg_step)", "mat.diag", "mat.offdiag", "cg.vec",
                 "sf.pack", "sf_unpack_rows"):
        assert name in txt, name


def test_sf_bcast_and_reduce_carry_sf_scopes():
    """On the kernel backend a bcast packs and unpacks; a reduce onto roots
    with several leaves packs, combines and unpacks."""
    sf = fig2_sf()
    comm = SFComm(sf, backend="pallas")
    roots = jnp.arange(float(sf.nroots_total), dtype=jnp.float32)
    leaves = jnp.zeros(sf.nleafspace_total, jnp.float32)

    def bcast_reduce(roots, leaves):
        out = comm.bcast(roots, leaves, "replace")
        return comm.reduce(out, jnp.zeros_like(roots), "sum")

    txt = _hlo(bcast_reduce, roots, leaves)
    for name in ("sf.pack", "sf.combine", "sf.unpack", "jit(sf_pack_rows)",
                 "jit(sf_segment_reduce)", "jit(sf_unpack_rows)"):
        assert name in txt, name
    txt = _hlo(lambda r, l: comm.bcast(r, l, "replace"), roots, leaves)
    assert "sf.pack" in txt and "sf.unpack" in txt
    assert "sf.combine" not in txt
