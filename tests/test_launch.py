"""Launcher/dry-run machinery: small-mesh cell lowering in a subprocess
(8 virtual devices), HLO cost analyzer invariants, roofline math."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SMALL_CELL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {src!r})
    import jax
    from repro.launch.cells import build_cell, CellOptions
    from repro.launch.mesh import make_small_mesh
    from repro.launch.hlo_cost import analyze_hlo

    mesh = make_small_mesh((4, 2), ("data", "model"))
    # reduced cfg via overrides: tiny depth/width but same machinery
    overrides = dict(n_layers=4, d_model=128, n_heads=8, n_kv_heads=4,
                     head_dim=16, d_ff=256, vocab=512)
    import repro.launch.cells as cells
    import repro.configs as cfgs
    cfgs.SHAPES["tiny_train"] = dict(seq_len=64, global_batch=8, kind="train")
    cfgs.SHAPES["tiny_decode"] = dict(seq_len=64, global_batch=8,
                                      kind="decode")
    with jax.sharding.set_mesh(mesh):
        for shape in ("tiny_train", "tiny_decode"):
            cell = build_cell("qwen3-4b", shape, mesh,
                              opts=CellOptions(microbatches=2)
                              if shape == "tiny_train" else CellOptions(),
                              cfg_overrides=overrides)
            compiled = cell["fn"].lower(*cell["args"]).compile()
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes >= 0
            cost = analyze_hlo(compiled.as_text())
            assert cost.flops > 0, shape
            if shape == "tiny_train":
                # layer scan must be loop-weighted (trip 4 visible)
                assert 4 in cost.while_trip_counts or \
                    2 in cost.while_trip_counts, cost.while_trip_counts
                assert cost.collective_bytes > 0
            print("CELL-OK", shape, int(cost.flops))
""").format(src=SRC)


@pytest.mark.slow
def test_small_mesh_cells_lower_and_analyze():
    r = subprocess.run([sys.executable, "-c", SMALL_CELL],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("CELL-OK") == 2, r.stdout


def test_hlo_analyzer_loop_weighting():
    """Scan flops must be multiplied by the trip count (the core fix over
    cost_analysis, which counts loop bodies once)."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, SRC)
    from repro.launch.hlo_cost import analyze_hlo
    D, L, M = 128, 5, 32

    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        return jax.lax.scan(body, x, ws)[0]

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((M, D), jnp.float32),
                         jax.ShapeDtypeStruct((L, D, D), jnp.float32)
                         ).compile()
    cost = analyze_hlo(c.as_text())
    analytic = L * 2 * M * D * D
    assert 0.9 <= cost.flops / analytic <= 1.4
    assert L in cost.while_trip_counts
    # cross-check: cost_analysis undercounts by ~L
    ca = c.cost_analysis()
    assert ca["flops"] < cost.flops / (L - 1)


def test_roofline_row_math():
    from repro.launch.roofline import roofline_row
    rec = {
        "cell": "x", "memory": {"peak_per_device": 2 ** 30},
        "meta": {"mesh": {"data": 16, "model": 16}, "kind": "train",
                 "global_batch": 256, "seq_len": 4096,
                 "active_params": 1e9, "params": 1e9},
        "cost_analysis": {"flops": 1e12},
        "hlo_cost": {"flops": 1e12, "bytes_accessed": 1e11,
                     "collective_bytes": 1e9, "collective_counts": {}},
    }
    row = roofline_row(rec)
    assert row["dominant"] == "memory"
    assert abs(row["compute_s"] - 1e12 / 197e12) < 1e-9
    assert row["roofline_frac"] > 0


def test_cell_options_fit_decisions():
    from repro.launch.cells import cell_options
    o = cell_options("kimi-k2-1t-a32b", "train_4k")
    assert o.moments_dtype == "int8" and o.grad_dtype == "bfloat16"
    assert cell_options("qwen3-4b", "decode_32k").microbatches == 1
