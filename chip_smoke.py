#!/usr/bin/env python3
"""Chip smoke test: the star-forest stack's main paths, once, on a TPU.

    python chip_smoke.py                # one chip: solver, kernels, serving
    python chip_smoke.py --four-chips   # four chips: shardmap CG vs global

Everything runs in this one process (a chip belongs to one process).  The
phases, each checked against a reference computed by other code:

* **solver** — the paper's workload: 7-point Poisson on a 128^3 ``DMDA``
  grid split over 8 SF ranks, ``ParCSR.from_dmda_stencil``, host-stepped
  ``cg`` to a relative residual of 1e-5 with the SpMV ghost SF on the
  ``pallas`` backend, again on ``global`` as the reference; then one
  ``global_to_local`` (replace, must match bit for bit) and one
  ``local_to_global`` (sum, must match to f32 rounding) halo exchange of a
  4-dof unit.
* **kernels** — every Pallas candidate the autotuner may pick, run on the
  real halo and MoE shapes and compared with the XLA lowering.
* **serving** — phi3.5-moe-42b-a6.6b at its published widths, depth cut to
  2 layers, random bf16 weights from a seed, in a ``ServeEngine`` with
  MoE dispatch through the SF ``DynPlan``; 8 requests of 200-1000 prompt
  tokens and 32 new tokens each; each request's first-token logits are
  compared with the same weights under dense dispatch.

``--four-chips`` runs only the ``shardmap`` backend path (DistSF inside
``shard_map``) on a 4-rank SF over a 4-chip mesh, compared with ``global``.

Any failed check or raised phase exits non-zero and prints no result.  The
last line of standard output is the JSON result object; timings printed
before it are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODEL = "phi3.5-moe-42b-a6.6b"


def log(*args) -> None:
    print(*args, flush=True)


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    log(f"  ok: {what}")


# --------------------------------------------------------------------------
# solver phase
# --------------------------------------------------------------------------
def _poisson(n: int, nranks: int):
    from repro.meshdist.dmda import DMDA
    return DMDA((n, n, n), nranks, stencil="star", width=1, periodic=False)


def _solve(A, b, tol: float, maxiter: int, warm: bool):
    """Host-stepped CG: cold (compiles + autotunes), then warm if asked.
    Returns (result, time to solution, compile time or None)."""
    import jax
    from repro.solvers.cg import cg
    t0 = time.perf_counter()
    res = cg(A, b, tol=tol, maxiter=maxiter)
    jax.block_until_ready(res.x)
    cold = time.perf_counter() - t0
    if not warm:
        return res, cold, None
    t0 = time.perf_counter()
    res = cg(A, b, tol=tol, maxiter=maxiter)
    jax.block_until_ready(res.x)
    t = time.perf_counter() - t0
    return res, t, max(cold - t, 0.0)


def cg_compare(n: int, nranks: int, backend: str, *, tol: float = 1e-5,
               maxiter: int = 3000, seed: int = 0, warm: bool = True) -> dict:
    """CG on the DMDA Poisson operator with the SpMV ghost SF on
    ``backend``, against the same solve on ``global``.  With ``warm`` each
    solve runs twice so compilation is timed apart from the solve."""
    import jax.numpy as jnp
    import numpy as np
    from repro.sparse.parmat import ParCSR

    da = _poisson(n, nranks)
    t0 = time.perf_counter()
    A = ParCSR.from_dmda_stencil(da, backend=backend)
    A_ref = ParCSR.from_dmda_stencil(da, backend="global")
    setup = time.perf_counter() - t0
    log(f"  DMDA {da.shape} on {nranks} ranks, proc grid {da.proc_grid}: "
        f"{da.nglobal} unknowns, {A.sf.nedges_total} ghost edges, "
        f"backend {A.comm.backend_name!r}; host setup {setup:.6f} s")
    b = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal(da.nglobal).astype(np.float32))
    bnorm = float(jnp.linalg.norm(b))
    out = {}
    for name, op in ((backend, A), ("global", A_ref)):
        res, t, compile_s = _solve(op, b, tol, maxiter, warm)
        true = float(jnp.linalg.norm(b - A_ref.spmv(res.x))) / bnorm
        devs = sorted(d.id for d in res.x.sharding.device_set)
        out[name] = {"iters": res.iters, "rel_residual": res.rnorm / bnorm,
                     "true_rel_residual": true, "time_to_solution_s": t,
                     "compile_s": compile_s, "x": res.x}
        when = (f"compile {compile_s:.6f} s" if warm
                else "compile included")
        log(f"  cg[{name}]: {res.iters} iterations, relative residual "
            f"{res.rnorm / bnorm:.6e} (true {true:.6e}); time to solution "
            f"{t:.6f} s, {when}; x on devices {devs}")
    got, ref = out[backend], out["global"]
    for name in (backend, "global"):
        check(out[name]["rel_residual"] <= tol,
              f"cg[{name}] reached relative residual <= {tol:g}")
        check(out[name]["true_rel_residual"] <= 10 * tol,
              f"cg[{name}] true residual {out[name]['true_rel_residual']:.3e}"
              f" <= {10 * tol:g}")
    check(abs(got["iters"] - ref["iters"]) <= 2,
          f"{backend} and global iteration counts agree within 2 "
          f"({got['iters']} vs {ref['iters']})")
    dx = float(jnp.linalg.norm(got["x"] - ref["x"])
               / jnp.linalg.norm(ref["x"]))
    log(f"  ||x_{backend} - x_global|| / ||x_global|| = {dx:.6e}")
    check(dx <= 1e-3, f"{backend} solution matches global")
    for r in out.values():
        del r["x"]
    out["solution_rel_diff"] = dx
    out["da"] = da
    return out


def halo_compare(da, *, dof: int = 4, seed: int = 1) -> dict:
    """One DMGlobalToLocal (replace) and one DMLocalToGlobal (sum) of a
    ``dof``-unit field on ``pallas`` against ``global``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    g = jnp.asarray(np.random.default_rng(seed)
                    .standard_normal((da.nglobal, dof)).astype(np.float32))
    t0 = time.perf_counter()
    lp = jax.block_until_ready(da.global_to_local(g, backend="pallas"))
    t1 = time.perf_counter()
    lg = da.global_to_local(g, backend="global")
    check(bool(jnp.array_equal(lp, lg)),
          f"global_to_local replace, {dof}-dof unit: pallas == global bit "
          f"for bit ({lp.shape[0]} local rows)")
    t2 = time.perf_counter()
    rp = np.asarray(da.local_to_global(lp, op="sum", backend="pallas"))
    t3 = time.perf_counter()
    rg = np.asarray(da.local_to_global(lg, op="sum", backend="global"))
    err = float(np.max(np.abs(rp - rg)) / max(np.max(np.abs(rg)), 1e-30))
    log(f"  local_to_global sum: max |pallas - global| / max |global| = "
        f"{err:.6e}")
    check(err <= 4 * float(np.finfo(np.float32).eps),
          "local_to_global sum: pallas == global to f32 rounding")
    log(f"  first-call times (autotune + compile included): global_to_local "
        f"{t1 - t0:.6f} s, local_to_global {t3 - t2:.6f} s")
    return {"g2l_first_s": t1 - t0, "l2g_first_s": t3 - t2,
            "l2g_rel_err": err, "local": lp}


# --------------------------------------------------------------------------
# kernel phase
# --------------------------------------------------------------------------
def kernel_candidates(da, local, *, moe_rows: int = 8192, moe_width: int = 4096,
                      moe_picks: int = 2048, seed: int = 2) -> int:
    """Run every kernel candidate the autotuner may pick on the halo and
    MoE shapes and compare each with the XLA lowering (exact for packs,
    f32 rounding for reductions).  Returns the number of kernel runs."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    from repro.kernels.tuning import resolve_interpret

    interp = resolve_interpret()
    rng = np.random.default_rng(seed)
    be = da.comm("pallas").backend
    nrun = 0

    def same(name, got, want, exact=True):
        nonlocal nrun
        nrun += 1
        got, want = np.asarray(got), np.asarray(want)
        if exact:
            ok = got.shape == want.shape and np.array_equal(got, want)
        else:
            scale = max(float(np.max(np.abs(want))), 1e-30)
            ok = got.shape == want.shape and float(np.max(np.abs(
                got - want))) <= 16 * float(np.finfo(np.float32).eps) * scale
        check(ok, name)

    def packs(tag, data, idx):
        idx = jnp.asarray(idx, jnp.int32)
        want = jnp.take(data, idx, axis=0)
        unit = tuple(data.shape[1:]) or (1,)
        for name, fn in kops._pack_candidates(
                int(idx.shape[0]), unit, data.dtype, interp).items():
            if name != "xla":
                same(f"pack {name} on {tag} {data.shape}{data.dtype}",
                     fn(data, idx), want)

    # halo pack: the global_to_local root gather, 4-dof f32 rows
    g = jnp.asarray(rng.standard_normal((da.nglobal, local.shape[1]))
                    .astype(np.float32))
    packs("halo", g, be.plan.gr)
    # halo scalars: the CG ghost gather, (N, 1) f32 rows
    packs("halo scalars", g[:, :1], be.plan.gr[: min(be.plan.nedges, 98304)])
    # MoE hidden rows (bf16 at d_model) gathered by expert slot
    x = jnp.asarray(rng.standard_normal((moe_rows, moe_width))
                    .astype(np.float32)).astype(jnp.bfloat16)
    packs("MoE rows", x, rng.integers(0, moe_rows, moe_picks))

    # segment reduce: the local_to_global sorted-slot buffer
    red = be.plan.red
    vals = jnp.take(local, jnp.asarray(be._gl_sorted, jnp.int32), axis=0)
    args = (vals, jnp.asarray(red.seg_first, jnp.int32),
            jnp.asarray(red.seg_len, jnp.int32),
            jnp.asarray(red.seg_of_slot, jnp.int32))
    for op in ("sum", "max"):
        cands = kops._seg_candidates(red.nseg, red.max_valid_seg_len, op,
                                     tuple(vals.shape[1:]), vals.dtype,
                                     interp, True)
        want = cands["xla"](*args)
        for name, fn in cands.items():
            if name != "xla":
                same(f"segment {op} {name} on halo {vals.shape}",
                     fn(*args), want, exact=op != "sum")

    # strided pack: the owned box of the last rank inside its ghosted
    # local array (ghosts on its low sides: the panels start unaligned)
    r = da.nranks - 1
    ls = da.local_shape(r)
    box = tuple(hi - lo for lo, hi in da.owned_box(r))
    off = [lo - glo for (lo, _), (glo, _) in zip(da.owned_box(r),
                                                 da.ghosted_box(r))]
    start = int(da.local_offsets[r]) + (off[0] * ls[1] + off[1]) * ls[2] \
        + off[2]
    dims, strides = (box[2], box[1], box[0]), (1, ls[2], ls[1] * ls[2])
    if interp or kops.strided_ok(dims, local.dtype):
        from repro.core.patterns import Strided3D
        idx = Strided3D(start, dims, strides).enumerate()
        same(f"pack_strided {dims} on a {ls} local block",
             kops.sf_pack_strided(local, start=start, dims=dims,
                                  strides=strides),
             jnp.take(local, jnp.asarray(idx), axis=0))

    # fused local bcast: leaf[gl] = root[gr] on halo rows
    E = min(4096, da.nglobal)
    gr = rng.integers(0, da.nglobal, E).astype(np.int32)
    gl = rng.permutation(local.shape[0])[:E].astype(np.int32)
    want = local.at[gl].set(jnp.take(g, gr, axis=0))
    for name, fn in kops._local_candidates(g.dtype, local.dtype,
                                           interp).items():
        if name != "xla":
            same(f"local bcast {name} on halo {local.shape}",
                 fn(g, local, jnp.asarray(gr), jnp.asarray(gl)), want)
    return nrun


# --------------------------------------------------------------------------
# serving phase
# --------------------------------------------------------------------------
def serving(cfg, *, batch: int = 8, s_max: int = 2048, n_requests: int = 8,
            prompt_len=(200, 1000), new_tokens: int = 32, seed: int = 0,
            tol: float = 0.05) -> dict:
    """ServeEngine with SF MoE dispatch; first-token logits of every
    request against dense dispatch on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.serving.engine import Request, ServeEngine

    t0 = time.perf_counter()
    params = jax.block_until_ready(T.init_params(jax.random.PRNGKey(seed),
                                                 cfg))
    nbytes = sum(int(p.nbytes) for p in jax.tree.leaves(params))
    log(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV x {cfg.hd}, "
        f"{cfg.moe_experts} experts top-{cfg.moe_topk}, expert d_ff "
        f"{cfg.moe_dff}, vocab {cfg.vocab}, dispatch {cfg.moe_dispatch!r}; "
        f"{nbytes / 1e9:.6f} GB of {cfg.dtype} weights in "
        f"{time.perf_counter() - t0:.6f} s")
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, n_requests)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab, int(n)).tolist(),
                    max_new=new_tokens) for i, n in enumerate(lens)]
    eng = ServeEngine(cfg, params, batch=batch, s_max=s_max)
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    m = eng.metrics()
    log(f"  prompts {sorted(int(n) for n in lens)} -> buckets "
        f"{m['prefill_buckets']}; {m['tokens_generated']} tokens in "
        f"{wall:.6f} s incl. compilation")
    log(f"  smoke timings: TTFT p50 {m['ttft_p50_s']:.6f} s, TPOT p50 "
        f"{m['tpot_p50_s']:.6f} s, {m['tokens_per_sec']:.6f} tokens/s")
    check(all(r.done and len(r.out) == new_tokens for r in reqs),
          f"all {n_requests} requests generated {new_tokens} tokens")

    dense_cfg = cfg.scaled(moe_dispatch="dense")
    dense = jax.jit(lambda p, t, l: T.prefill(p, dense_cfg, tokens=t,
                                              s_max=s_max, last_pos=l))
    worst = 0.0
    for r in reqs:
        n = r.prompt_len
        toks = np.zeros((1, eng._bucket(n)), np.int32)
        toks[0, :n] = r.tokens
        last = jnp.asarray([n - 1], jnp.int32)
        sf_logits, _ = eng._prefill_fn(toks.shape[1])(params, toks, last)
        ref_logits, _ = dense(params, toks, last)
        sf_l = np.asarray(sf_logits, np.float32)[0]
        ref_l = np.asarray(ref_logits, np.float32)[0]
        err = float(np.max(np.abs(sf_l - ref_l)) / np.max(np.abs(ref_l)))
        worst = max(worst, err)
        check(r.out[0] == int(np.argmax(sf_l)),
              f"request {r.rid}: engine's first token is the argmax of its "
              f"SF-dispatch prefill logits")
        check(err <= tol, f"request {r.rid} ({n} tokens): first-token "
              f"logits SF vs dense dispatch, max rel diff {err:.3e} <= {tol}")
    return {"metrics": m, "wall_s": wall, "logit_rel_diff_max": worst}


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def device_info() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run_one_chip() -> None:
    from repro.configs import get_config
    from repro.kernels import tuning

    log("== solver phase: DMDA Poisson CG, pallas vs global")
    out = cg_compare(128, 8, "pallas")
    log("== solver phase: halo exchange of a 4-dof unit")
    halo = halo_compare(out["da"], dof=4)
    log("== kernel phase: every Pallas candidate vs XLA at real shapes")
    n = kernel_candidates(out["da"], halo["local"])
    log(f"  {n} kernel candidate runs matched XLA")
    log("== serving phase")
    cfg = get_config(MODEL)
    log(f"  cut: n_layers {cfg.n_layers} -> 2 (widths as published)")
    serving(cfg.scaled(n_layers=2))
    log("== autotuner winners")
    for key, name in sorted(tuning.winners().items(), key=str):
        log(f"  {key} -> {name}")
    st = tuning.stats()
    log(f"  tuning stats {st}")
    check(st["candidate_errors"] == 0, "no autotune candidate raised")


def run_four_chips() -> None:
    log("== shardmap phase: DMDA Poisson CG on a 4-rank SF over 4 chips")
    out = cg_compare(128, 4, "shardmap", warm=False)
    log(f"  result: {json.dumps({k: v for k, v in out.items() if k != 'da'})}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shardmap CG on a 4-chip mesh")
    args = ap.parse_args(argv)
    try:
        import jax
        info = device_info()
        if info["platform"] != "tpu":
            print(f"chip_smoke: needs a TPU, JAX found {info}",
                  file=sys.stderr)
            return 2
        need = 4 if args.four_chips else 1
        if info["count"] < need:
            print(f"chip_smoke: needs {need} chips, have {info['count']}",
                  file=sys.stderr)
            return 2
        from repro.compile_cache import enable_compile_cache
        from repro.kernels.tuning import resolve_interpret
        log(f"devices: {info}; jax {jax.__version__}; compile cache "
            f"{enable_compile_cache()}")
        if resolve_interpret():
            raise CheckFailed("Pallas kernels would run in interpret mode "
                              "on the TPU (REPRO_SF_INTERPRET is set)")
        t0 = time.perf_counter()
        run_four_chips() if args.four_chips else run_one_chip()
        log(f"total {time.perf_counter() - t0:.6f} s")
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
