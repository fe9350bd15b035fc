"""The harness: the chip check, the metric readers' window arithmetic, the
traffic generator, and the cells' files as BENCHMARK.json names them."""

import glob
import json
import os
import types

import numpy as np
import pytest

from bench import harness, loadgen
from bench.work import Work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- chip check
def test_refuses_the_cpu():
    with pytest.raises(harness.NoChip, match="needs a TPU"):
        harness.check_device(1, harness.load_peaks())


def _fake_devices(monkeypatch, kind, n=1):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_refuses_a_kind_the_peaks_table_lacks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99")
    with pytest.raises(harness.NoChip, match="not in"):
        harness.check_device(1, harness.load_peaks())


def test_refuses_too_few_chips(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.check_device(4, harness.load_peaks())


def test_accepts_a_v5e(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", n=4)
    assert harness.check_device(4, harness.load_peaks()) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_peaks_of_a_v5e():
    p = harness.load_peaks()
    assert "source" in p
    v5e = p["kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30


# ------------------------------------------------------ window arithmetic
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(samples, window_s=2.0, trace=None):
    return {"samples": samples, "window_s": window_s, "setup_s": 9.5,
            "peaks": PEAKS, "trace": trace}


def test_rates_are_over_the_whole_window():
    read = harness.metric_reader
    assert read("halo_exchange_us")(_ctx({"pairs": 400})) == 5000.0
    assert read("cg_iter_ms")(_ctx({"cg_iters": 100})) == 20.0
    serve = {"tokens": 3000, "ttft_s": [0.1], "serve_units": [Work(1, 1)]}
    assert read("serve_tokens_per_s")(_ctx(serve)) == 1500.0
    assert read("setup_s")(_ctx({})) == 9.5
    # a reader whose cell it is not finds nothing
    assert read("halo_exchange_us")(_ctx(serve)) is None
    assert read("serve_tokens_per_s")(_ctx({"pairs": 4})) is None


def test_ttft_p95_is_over_all_requests():
    ttft = list(np.linspace(0.001, 0.200, 200))
    got = harness.metric_reader("serve_ttft_p95_ms")(
        _ctx({"ttft_s": ttft, "tokens": 1, "serve_units": []}))
    assert got == pytest.approx(float(np.percentile(ttft, 95)) * 1e3)
    assert harness.metric_reader("serve_ttft_p95_ms")(
        _ctx({"ttft_s": []})) is None


def test_roofline_shares_and_idle():
    read = harness.metric_reader
    pair = Work(0.0, 819e6)                    # 1 ms at 819 GB/s
    assert read("halo_exchange_mfu")(
        _ctx({"pairs": 1000, "pair_work": pair})) == pytest.approx(50.0)
    it = Work(197e9, 1.0)                      # 1 ms at 197 TFLOP/s
    assert read("cg_iter_mfu")(
        _ctx({"cg_iters": 500, "iter_work": it})) == pytest.approx(25.0)
    units = [Work(0.0, 819e6)] * 3
    assert read("serve_mfu")(
        _ctx({"serve_units": units}, window_s=0.03)) == pytest.approx(10.0)
    tr = {"busy_s": 1.5, "window_s": 2.0}
    assert read("halo_device_idle_pct")(
        _ctx({"pairs": 1}, trace=tr)) == pytest.approx(25.0)
    assert read("halo_device_idle_pct")(_ctx({"pairs": 1})) is None
    assert read("cg_device_idle_pct")(_ctx({"pairs": 1}, trace=tr)) is None
    # no peaks for the device: no share at all, never a 0
    ctx = _ctx({"pairs": 1000, "pair_work": pair})
    ctx["peaks"] = None
    assert read("halo_exchange_mfu")(ctx) is None


def test_result_line_is_strict_json():
    res = {"correct": False, "attempted": 1, "failed": 1,
           "metrics": {"x": {"value": 1.0, "unit": "s"}}, "device": {},
           "checks": {"gap": {"value": float("nan"), "limit": 1.0}}}
    line = json.loads(harness.result_line(res))
    assert line["checks"]["gap"]["value"] > 1.0
    assert list(line)[-1] == "checks"


def test_winner_key_is_short_and_plain():
    import jax.numpy as jnp
    key = ("pack", 1025, 16384, (4096,), jnp.dtype(jnp.bfloat16), False,
           "tpu", ("dynplan", 16384, 2048))
    assert harness.winner_key(key) == "pack 1025 16384 (4096,) bfloat16"
    assert harness.winner_key("xla") == "xla"


# ------------------------------------------------------------ traffic
def test_every_seed_gets_the_same_sizes():
    tr = loadgen.load("chat32-closed")
    a = loadgen.RequestStream(tr, 1, 32064)
    b = loadgen.RequestStream(tr, 2 ** 31 + 77, 32064)
    pa = [a.next() for _ in range(tr["pool"])]
    pb = [b.next() for _ in range(tr["pool"])]
    assert sorted(len(t) for t, _ in pa) == sorted(len(t) for t, _ in pb)
    assert sorted(n for _, n in pa) == sorted(n for _, n in pb)
    assert [len(t) for t, _ in pa] != [len(t) for t, _ in pb]
    lens = [len(t) for t, _ in pa]
    assert min(lens) >= tr["prompt"]["min"] and max(lens) <= tr["prompt"]["max"]
    assert np.median(lens) == pytest.approx(tr["prompt"]["median"], rel=0.02)
    outs = [n for _, n in pa]
    assert np.median(outs) == pytest.approx(tr["output"]["median"], rel=0.02)


def _request_mixes():
    for path in sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json"))):
        tr = json.load(open(path))
        if "prompt" in tr:
            yield os.path.basename(path)[:-5], tr


@pytest.mark.parametrize("name,tr", list(_request_mixes()))
def test_request_mix_names_its_source_and_assumptions(name, tr):
    assert tr["source"] and tr["why"]
    assert tr["assumed"] and all(isinstance(a, str) for a in tr["assumed"])


@pytest.mark.parametrize("name,tr", list(_request_mixes()))
def test_warm_up_covers_exactly_the_buckets_the_mix_uses(name, tr):
    """The engine pads a prompt to the next power of two, at most s_max."""
    bucket = lambda n: min(1 << (int(n) - 1).bit_length(), tr["s_max"])
    pool = loadgen.quantile_pool(tr["prompt"], tr["pool"])
    assert {bucket(n) for n in pool} == {bucket(n) for n in tr["warm_prompts"]}
    # every request fits a slot's cache, so none is cut short
    assert tr["prompt"]["max"] + tr["output"]["max"] <= tr["s_max"] - 1
    assert len(tr["warm_prompts"]) <= tr["arrival"]["clients"] <= tr["slots"]


def test_lognormal_pool_sits_on_its_quantiles():
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.5,
            "min": 1, "max": 10 ** 6}
    pool = loadgen.quantile_pool(spec, 1001)
    assert np.all(np.diff(pool) >= 0)
    assert pool[500] == 100
    with pytest.raises(ValueError, match="unknown distribution"):
        loadgen.quantile_pool({"dist": "exponential", "mean": 1.0}, 8)


# ------------------------------------------------- the cells' own files
def _spec():
    return harness.load_spec()


def test_every_name_has_its_files():
    spec = _spec()
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(os.path.dirname(BENCH), c["file"]))
    for w in spec["workloads"]:
        cell, config, traffic = harness.resolve(spec, w["name"])
        assert os.path.isfile(os.path.join(BENCH, "systems",
                                           f"{config['system']}.py"))
        assert set(config["reduced"]) == set(
            next(c for c in spec["configs"] if c["name"] == w["config"])
            ["reduced"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_every_cell_reports_what_the_contract_asks():
    spec = _spec()
    e2e = {m["name"] for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        names = [m["name"] for m in harness.metrics_for(spec, w["name"], False)]
        assert "setup_s" in names and len(names) >= 2
        layer = harness.metrics_for(spec, w["name"], True)
        assert layer
        for m in layer:
            assert m["moves"] in e2e and m["moves"] in names
    for m in spec["per_layer"]:
        assert m["moves"] in e2e


def test_references_import_nothing_of_the_program():
    for path in glob.glob(os.path.join(BENCH, "systems", "*_ref.py")):
        src = open(path).read()
        assert "repro" not in src.replace("reproduc", ""), path
