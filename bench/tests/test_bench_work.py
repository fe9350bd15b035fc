"""The work counts, pinned to values worked out by hand at the cells' real
shapes, and independent of the code that does the work."""

import json
import os

import numpy as np
import pytest

from bench import work
from bench.systems.moe_lm import lm_shape

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_stencil_nonzeros_at_128_cubed():
    # 128^3 cells + 2 * (3 axes * 127 * 128^2 face-adjacent pairs)
    assert work.stencil_nnz((128, 128, 128)) == 14_581_760


def test_star_leaves_at_128_cubed_on_2x2x2():
    splits = [np.array([0, 64, 128])] * 3
    # 2,197,000 local positions less 1,544 corner holes of the star stencil
    assert work.star_connected_leaves((128,) * 3, splits) == 2_195_456


def test_halo_pair_payload_is_137_mb():
    w = work.halo_pair(2_097_152, 2_195_456, dof=4)
    # g2l reads globals, writes leaves; l2g reads leaves, writes globals;
    # 16 bytes per 4-dof f32 row
    assert w.bytes == 16 * 2 * (2_097_152 + 2_195_456) == 137_363_456
    assert w.flops == 4 * 98_304


def test_cg_iteration_at_128_cubed():
    w = work.cg_iteration(14_581_760, 2_097_152)
    # 8 bytes per nonzero + 11 f32 vector passes
    assert w.bytes == 116_654_080 + 92_274_688
    # 2 per nonzero + 2 dots and 3 axpys of 2 flops per entry
    assert w.flops == 29_163_520 + 20_971_520


def test_decode_step_reads_5_46_gb_of_weights():
    s = lm_shape(_config("phi35-moe-2l"))
    per_layer = (41_943_040 + 16 * 3 * 4096 * 6400) * 2 + 4096 * 16 * 4 \
        + 2 * 4096 * 2
    assert work.lm_weight_bytes(s) == 2 * per_layer + 4096 * 2 \
        + 4096 * 32064 * 2 == 5_464_170_496
    # 32 sequences attending to 1000 positions each
    w = work.lm_decode_step(s, [1000] * 32)
    kv_row = 2 * 8 * 128 * 2
    assert w.bytes == 5_464_170_496 + 2 * kv_row * 32 * 1000 \
        + 32 * 4096 * 2 + 32 * 32064 * 4
    # q/o are 4096x4096, k/v 4096x1024: 2*(16.8M + 4.2M) params per layer
    lin = 2 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 16
               + 2 * 3 * 4096 * 6400)
    assert w.flops == 2 * (32 * lin + 32 * 4 * 32 * 128 * 1000) \
        + 32 * 2 * 4096 * 32064
    assert work.lm_decode_step(s, []).bytes == 0


def test_prefill_counts_real_tokens_only():
    s = lm_shape(_config("phi35-moe-2l"))
    a, b = work.lm_prefill(s, 300), work.lm_prefill(s, 301)
    assert b.flops > a.flops and b.bytes - a.bytes == \
        4096 * 2 + 2 * 2 * 8 * 128 * 2


def test_roofline_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(work.Work(100.0, 50.0), peaks) == (5.0, "bytes")
    assert work.roofline_s(work.Work(1000.0, 50.0), peaks) == (10.0, "flops")


@pytest.mark.parametrize("grid,procs", [((12, 12, 12), (2, 2, 2)),
                                        ((9, 7, 5), (3, 2, 1))])
def test_leaf_count_matches_the_geometry(grid, procs):
    from bench.systems.dmda_ref import Geometry, _splits
    geo = Geometry(grid, procs)
    splits = [_splits(e, p) for e, p in zip(grid, procs)]
    assert geo.n_connected == work.star_connected_leaves(grid, splits)
    assert int(geo.multiplicity.sum()) == geo.n_connected
