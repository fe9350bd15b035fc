"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Every kernel is compiled at the widths the system runs
(``interpret=False``) and must lower to a Mosaic ``tpu_custom_call``, so a
kernel the chip's compiler refuses fails here instead of on the chip.  The
topology is described inside a module fixture — never at import — and the
tests skip from there when it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K
from repro.kernels.sf_pack import (bcast_fused, pack, pack_block_ok,
                                   pack_blocked, pack_strided)
from repro.kernels.sf_unpack import (seg_block_ok, segment_reduce_blocked,
                                     segment_reduce_gather,
                                     segment_reduce_sorted)

# the MoE hidden rows of phi3.5-moe (d_model 4096, bf16) and the halo rows
MOE = dict(N=8192, U=(4096,), dt=jnp.bfloat16)
HALO_SCALARS = dict(N=2 ** 21, U=(1,), dt=jnp.float32)   # 128^3 CG vector
HALO_4DOF = dict(N=2 ** 21, U=(4,), dt=jnp.float32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_tpu(topo):
    """Compile ``fn`` for one described chip; returns the HLO text.  The
    persistent cache is off around these compiles (a TPU executable cannot
    be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)


def _assert_kernel(hlo):
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("case", [MOE, HALO_SCALARS], ids=["moe", "scalars"])
def test_pack_compiles(compile_tpu, case):
    M = 8192 if case is MOE else 98304
    _assert_kernel(compile_tpu(
        lambda d, i: pack(d, i, interpret=False),
        ((case["N"],) + case["U"], case["dt"]), ((M,), jnp.int32)))


@pytest.mark.parametrize("case", [MOE, HALO_SCALARS, HALO_4DOF],
                         ids=["moe", "scalars", "4dof"])
def test_pack_blocks_compile(compile_tpu, case):
    """Every ``block:B`` the candidate set keeps on a TPU."""
    M = 2048 if case is MOE else 98304
    blocks = [int(n.split(":")[1]) for n in
              K._pack_candidates(M, case["U"], case["dt"], False)
              if n.startswith("block:")]
    assert blocks and all(pack_block_ok(B, case["U"], case["dt"])
                          for B in blocks)
    for B in blocks:
        _assert_kernel(compile_tpu(
            lambda d, i, B=B: pack_blocked(d, i, block_rows=B,
                                           interpret=False),
            ((case["N"],) + case["U"], case["dt"]), ((M,), jnp.int32)))


@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_reduce_compiles(compile_tpu, op):
    """``segment_reduce_sorted``, every kept ``segment_reduce_blocked``
    block and the ``gather`` lowering (XLA, one row gather, no kernel) on a
    DMDA local_to_global buffer (4-dof f32 rows, Lmax 4; one SMEM chunk of
    segments)."""
    M, S, L, U = 2 ** 17, 2 ** 15, 4, (4,)
    shapes = (((M,) + U, jnp.float32), ((S,), jnp.int32), ((S,), jnp.int32))
    _assert_kernel(compile_tpu(
        lambda b, f, l: segment_reduce_sorted(
            b, f, l, num_segments=S, Lmax=L, op=op, interpret=False),
        *shapes))
    blocks = [int(n.split(":")[1]) for n in
              K._seg_candidates(S, L, op, U, jnp.float32, False, True)
              if n.startswith("block:")]
    assert blocks and all(seg_block_ok(SB, L, U, jnp.float32)
                          for SB in blocks)
    for SB in blocks:
        _assert_kernel(compile_tpu(
            lambda b, f, l, SB=SB: segment_reduce_blocked(
                b, f, l, num_segments=S, Lmax=L, segs_per_block=SB, op=op,
                interpret=False),
            *shapes))
    assert "gather" in K._seg_candidates(S, L, op, U, jnp.float32, False,
                                         True)
    hlo = compile_tpu(lambda b, f, l, i: segment_reduce_gather(
        b, f, l, i, Lmax=L, op=op), *shapes, ((M,), jnp.int32))
    assert hlo.count(" gather(") == 1


@pytest.mark.parametrize("case", [HALO_4DOF, MOE], ids=["4dof", "moe"])
def test_bcast_fused_compiles(compile_tpu, case):
    E = 4096
    _assert_kernel(compile_tpu(
        lambda r, l, a, b: bcast_fused(r, l, a, b, interpret=False),
        ((case["N"],) + case["U"], case["dt"]),
        ((case["N"],) + case["U"], case["dt"]),
        ((E,), jnp.int32), ((E,), jnp.int32)))


def test_pack_strided_compiles(compile_tpu):
    """The owned 64^3 box inside a ghosted 66^3 local block (unaligned
    panel starts), 4-dof f32 rows."""
    n = 66
    dims, strides = (64, 64, 64), (1, n, n * n)
    assert K.strided_ok(dims, jnp.float32)
    _assert_kernel(compile_tpu(
        lambda d: pack_strided(d, start=1 + n + n * n, dims=dims,
                               strides=strides, interpret=False),
        ((n ** 3, 4), jnp.float32)))


@pytest.mark.parametrize("H,S,Dq,Dv", [(64, 16384, 192, 128),
                                       (32, 4096, 128, 128)],
                         ids=["kimi-mla-prefill", "gqa"])
def test_flash_attention_compiles(compile_tpu, H, S, Dq, Dv):
    """The causal flash kernel at the latent-attention prefill's widths
    (Kimi-K2: 64 heads, q/k 192 = nope 128 + rope 64, v 128, a 16k bucket)
    and at a plain head dim."""
    from repro.kernels.flash_attention import flash_attention_heads
    _assert_kernel(compile_tpu(
        lambda q, k, v: flash_attention_heads(q, k, v, scale=0.13,
                                              interpret=False),
        ((H, S, Dq), jnp.bfloat16), ((H, S, Dq), jnp.bfloat16),
        ((H, S, Dv), jnp.bfloat16)))
