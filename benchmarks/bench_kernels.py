"""Pallas kernel timings vs jnp reference (not a paper table; regression
tracking for the kernel layer).

The hot-path rows time the *tuned* entry points (``pack_rows``,
``segment_reduce_rows``) — the lowering the SF backends actually execute —
in compiled mode where the platform supports it (TPU Mosaic) and interpret
mode elsewhere; every timing records which mode it ran in and, for tuned
rows, which candidate lowering the autotuner picked.  The untuned pack
kernel at its smallest block (one output tile per grid step) is timed too
(few iterations in interpret mode), so the trajectory keeps both curves.
Results land in ``BENCH_kernels.json`` with the environment stamp from :mod:`benchmarks.artifacts`; the CI perf
guard (``benchmarks/perf_guard.py``) compares fresh ``pack_rows`` timings
against the committed artifact."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels import tuning

from benchmarks.artifacts import artifact_path, write_artifact

DEFAULT_JSON = artifact_path("BENCH_kernels.json")


def _t(fn, *a, iters=10):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(json_path=DEFAULT_JSON):
    rng = np.random.default_rng(0)
    interp = K.default_interpret()
    rows = []
    details = {}

    def add(name, us, note="", impl=None):
        rows.append((name, us, note))
        d = {"us": us, "interpret": interp}
        if impl is not None:
            d["impl"] = impl
        details[name] = d

    def _impl(kind, tag):
        """The lowering the autotuner picked for the tagged bench problem."""
        for fk, name in tuning.winners().items():
            if fk[0] == kind and fk[-1] == tag:
                return name
        return None

    data = jnp.asarray(rng.standard_normal((4096, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 4096, 128).astype(np.int32))
    # the tuned hot path — what PallasBackend/DistSF actually run
    key = ("bench", "pack128")
    us = _t(lambda d, i: K.pack_rows(d, i, key=key), data, idx)
    add("pack_kernel_128x128", us, "tuned", impl=_impl("pack", key))
    add("pack_ref_128x128", _t(lambda d, i: R.pack_ref(d, i), data, idx))
    # the untuned kernel at its smallest block, one output tile per grid
    # step (few iters in interpret mode)
    add("pack_rowdma_128x128", _t(K.sf_pack, data, idx,
                                  iters=1 if interp else 10),
        "one-tile-per-step")
    # §5.2 ¶3 parametric strided pack: same 128 rows, no index array at all
    add("pack_strided_kernel_4x4x8",
        _t(lambda d: K.sf_pack_strided(d, start=2, dims=(4, 4, 8),
                                       strides=(1, 8, 64)), data),
        "no-index-array")
    # sorted segment reduction (the CUDA-atomics replacement of §5.3),
    # through the tuned entry point
    seg_first = np.arange(0, 128, 4, dtype=np.int64)
    seg_len = np.full(32, 4, dtype=np.int64)
    seg_ids = np.repeat(np.arange(32), 4)
    buf = data[:128]
    skey = ("bench", "segred128")
    us = _t(lambda b: K.segment_reduce_rows(
        b, seg_first, seg_len, num_segments=32, Lmax=4, op="sum",
        seg_of_slot=seg_ids, key=skey), buf)
    add("unpack_segment_kernel_128rows", us, "tuned",
        impl=_impl("segred", skey))
    # backend-level hot path: SFComm bcast through the pallas kernels vs jnp
    from repro.core import SFComm
    from benchmarks.bench_pingpong import _pingpong_sf
    n = 1024
    sf = _pingpong_sf(n)
    root = jnp.arange(n, dtype=jnp.float32)
    leaf = jnp.zeros(sf.nleafspace_total, jnp.float32)
    for bk in ("global", "pallas"):
        ops = SFComm(sf, backend=bk)
        fn = jax.jit(lambda r, l, ops=ops: ops.bcast(r, l, "replace"))
        add(f"sfcomm_bcast_{bk}_{n}", _t(fn, root, leaf))
    q = jnp.asarray(rng.standard_normal((256, 4, 64)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((256, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((256, 2, 64)).astype(np.float32))
    add("flash_kernel_256", _t(K.flash_attention, q, k, v))
    add("flash_ref_256",
        _t(lambda a, b, c: R.flash_attention_ref(a, b, c), q, k, v))
    if json_path:   # pass json_path=None to skip the trajectory artifact
        report = {"bench": "kernels", "unit": "us_per_call",
                  "interpret": interp,
                  "timings": {name: us for name, us, _ in rows},
                  "details": details,
                  "derived": {name: note for name, _, note in rows if note}}
        write_artifact(json_path, report)
    return rows
