"""Paper Fig 5/9: blocked vs pipelined communication lowering.

Compares the DistSF general lowering with ``sync_mode`` barriers (the
blocking-MPI behaviour of Fig 5(R)) against the default async split-phase
lowering where XLA is free to overlap the collective with the independent
compute placed between begin and end (the NVSHMEM end-state).  On an
accelerator it runs on this process's own devices (at least 2); without
one it runs in a child process with 8 emulated host devices, so the main
process stays single-device.

Sweeps the per-rank halo size: small messages are latency-bound (overlap
hides nearly everything), large messages become bandwidth-bound.  The
figure-of-merit per size is

    overlap_efficiency = t_sync / t_split

i.e. how much the split-phase formulation buys over blocking barriers at
that message size (>1 means overlap is winning).  Both timings are recorded
as fenced :mod:`repro.core.sflog` events (``REPRO_SF_LOG=fence`` semantics:
``block_until_ready`` inside the event window) and the ratio is computed by
:func:`repro.core.sflog.overlap_efficiency` from the registry aggregates —
the same event stream ``log_view`` prints, not a separate hand-rolled
timer.  On emulated host devices there is no independent progress engine,
so efficiencies hover at or below 1.0 — the artifact records the *shape* of
the curve so real-accelerator runs have a comparison point.  The sweep
lands in ``BENCH_async.json`` alongside the usual CSV rows, together with
the sweeping process's ``sflog.dump_json()`` event summary.
"""

import json
import os
import subprocess
import sys

# per-rank halo widths (f32 elements); 1<<12 is the historical fixed point
SIZES = (1 << 8, 1 << 10, 1 << 12, 1 << 14)
W = 256          # fixed independent-compute width (<= every root pad in the
                 # sweep, so the overlap slice is full-size)
HOST_RANKS = 8   # emulated host devices when no accelerator is present


def sweep(devices, sizes=SIZES):
    """Time split-phase vs blocking halo exchanges over ``devices`` (one
    rank each).  Yields CSV lines, then one ``SFLOG,`` line."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec
    from repro.core import DistSF, StarForest, sflog
    from repro.core.distributed import _smap

    sflog.set_mode("fence")   # wall time means completion, not dispatch
    R = len(devices)
    mesh = jax.make_mesh((R,), ("sf",), (AxisType.Auto,), devices=devices)

    def make_sf(n):
        sf = StarForest(R)
        for q in range(R):   # ring halo: leaves pull from the left neighbor
            sf.set_graph(q, n, None,
                         np.stack([np.full(n, (q - 1) % R), np.arange(n)], 1),
                         nleafspace=n)
        return sf.setup()

    def build(sf, sync):
        d = DistSF(sf, axis_name="sf", lowering="general", sync_mode=sync)

        def step(roots, leaves, w):
            def inner(r, l, w):
                pend = d.bcast_begin(r[0], "replace")
                acc = r[0][:W]
                for _ in range(4):           # independent compute to overlap
                    acc = jnp.tanh(acc @ w)
                l2 = d.bcast_end(pend, l[0])
                return l2.at[:W].add(acc)[None]
            return _smap(inner, mesh,
                         (PartitionSpec("sf"),) * 2 + (PartitionSpec(),),
                         PartitionSpec("sf"))(roots, leaves, w)
        return jax.jit(step)

    def measure(fn, args, ev, iters=60):
        # compile + warm outside the event window, then record every call
        # as one fenced sflog event occurrence; the registry's mean per
        # call is the timing (overlap_efficiency reads the same aggregate)
        jax.block_until_ready(fn(*args))
        for _ in range(iters):
            t0 = sflog.op_begin()
            out = fn(*args)
            sflog.op_end(ev, t0, out)
        rec = sflog.event(ev)
        return rec.time / rec.count * 1e6

    for n in sizes:
        sf = make_sf(n)
        dd = DistSF(sf, lowering="general")
        rng = np.random.default_rng(0)
        roots = jnp.asarray(rng.standard_normal((R, dd.plan.root_pad))
                            .astype(np.float32))
        leaves = jnp.zeros((R, dd.plan.leaf_pad), jnp.float32)
        # fixed (W, W) operand: the overlap compute costs the same at every
        # message size, so only the communication term varies
        w = jnp.asarray(rng.standard_normal((W, W)).astype(np.float32) / 100)
        res = {}
        for name, sync in [("split", False), ("sync", True)]:
            res[name] = measure(build(sf, sync), (roots, leaves, w),
                                f"AsyncHalo{n}" + name.capitalize())
        eff = sflog.overlap_efficiency(f"AsyncHalo{n}Sync",
                                       f"AsyncHalo{n}Split")
        yield (f"CSV,halo_n{n}_split,{res['split']:.1f},"
               f"sync_us={res['sync']:.1f};overlap_eff={eff:.2f}")
    yield "SFLOG," + json.dumps(sflog.dump_json())


def _host_child() -> None:
    """Entry point of the emulated-device child (no accelerator present)."""
    import jax
    for line in sweep(jax.devices()[:HOST_RANKS]):
        print(line, flush=True)


def _sweep_lines():
    """On an accelerator: sweep this process's own devices (a chip belongs
    to one process, so no child may start).  Without one: sweep emulated
    host devices in a child, keeping this process single-device."""
    import jax
    if jax.default_backend() != "cpu":
        devices = jax.devices()
        if len(devices) < 2:
            raise RuntimeError(
                f"bench_async needs >= 2 devices for a halo ring; this "
                f"process has {len(devices)} ({devices[0].device_kind})")
        return list(sweep(devices)), "", len(devices)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_"
               f"count={HOST_RANKS}",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    r = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_async import _host_child; _host_child()"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    return r.stdout.splitlines(), r.stderr, HOST_RANKS


def run():
    from benchmarks.artifacts import artifact_path, write_artifact

    lines, err, ranks = _sweep_lines()
    rows, sweep_out, sflog_dump = [], {}, None
    for line in lines:
        if line.startswith("SFLOG,"):
            sflog_dump = json.loads(line.split(",", 1)[1])
            continue
        if not line.startswith("CSV,"):
            continue
        _, name, us, der = line.split(",", 3)
        rows.append((name, float(us), der))
        # name = halo_n<size>_split; der = sync_us=<..>;overlap_eff=<..>
        n = int(name.split("_")[1][1:])
        kv = dict(p.split("=") for p in der.split(";"))
        sweep_out[str(n)] = {
            "split_us": float(us),
            "sync_us": float(kv["sync_us"]),
            "overlap_efficiency": float(kv["overlap_eff"]),
        }
    if not rows:
        rows.append(("halo_overlap_FAILED", 0.0, err[-200:]))
        return rows
    out = {"ranks": ranks, "halo_sweep": sweep_out}
    if sflog_dump is not None:
        out["sflog"] = sflog_dump
    write_artifact(artifact_path("BENCH_async.json"), out)
    return rows
