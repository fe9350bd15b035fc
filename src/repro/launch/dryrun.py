import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell on the production meshes, record memory/cost/collective analysis.

MUST be run as a module (``PYTHONPATH=src python -m repro.launch.dryrun``);
the XLA_FLAGS assignment above executes before any jax import — jax locks
the device count at first init.

For every cell this driver:
  1. builds the jitted step (launch/cells.py),
  2. ``.lower(*abstract_args)`` then ``.compile()``,
  3. prints ``compiled.memory_analysis()`` (proves the cell fits) and
     ``compiled.cost_analysis()``,
  4. runs the loop-weighted HLO analyzer (launch/hlo_cost.py) for the
     roofline terms (collective bytes are NOT in cost_analysis),
  5. appends a JSON record to ``reports/dryrun/<cell>.json``.

Restartable: cells with an existing report are skipped unless --force.
"""

import argparse
import json
import time
import traceback


def main() -> int:
    import jax
    from repro.configs import ALL_ARCHS, SHAPES, shape_supported
    from repro.launch.cells import build_cell
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-hlo-analysis", action="store_true")
    args = ap.parse_args()

    archs = ALL_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_devices = len(jax.devices())
    assert n_devices == 512, f"expected 512 virtual devices, got {n_devices}"

    failures = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_tag = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{mesh_tag}".replace("/", "_")
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path) and not args.force:
                    print(f"[skip-done] {tag}")
                    continue
                if not shape_supported(arch, shape):
                    rec = {"cell": tag, "status": "skipped",
                           "reason": "full-attention arch: long_500k needs "
                                     "sub-quadratic attention (DESIGN.md "
                                     "§4.1)"}
                    json.dump(rec, open(out_path, "w"), indent=1)
                    print(f"[skip-by-design] {tag}")
                    continue
                t0 = time.time()
                try:
                    with jax.sharding.set_mesh(mesh):
                        cell = build_cell(arch, shape, mesh)
                        lowered = cell["fn"].lower(*cell["args"])
                        t_lower = time.time() - t0
                        compiled = lowered.compile()
                    t_compile = time.time() - t0 - t_lower
                    ma = compiled.memory_analysis()
                    ca = compiled.cost_analysis()
                    rec = {
                        "cell": tag, "status": "ok", "meta": cell["meta"],
                        "lower_s": round(t_lower, 1),
                        "compile_s": round(t_compile, 1),
                        "memory": {
                            "argument_bytes": ma.argument_size_in_bytes,
                            "output_bytes": ma.output_size_in_bytes,
                            "temp_bytes": ma.temp_size_in_bytes,
                            "alias_bytes": ma.alias_size_in_bytes,
                            "peak_per_device": ma.argument_size_in_bytes
                            + ma.output_size_in_bytes + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes,
                        },
                        "cost_analysis": {
                            k: v for k, v in ca.items()
                            if isinstance(v, (int, float)) and
                            k in ("flops", "bytes accessed",
                                  "transcendentals")},
                    }
                    if not args.no_hlo_analysis:
                        hc = analyze_hlo(compiled.as_text())
                        rec["hlo_cost"] = {
                            "flops": hc.flops,
                            "bytes_accessed": hc.bytes_accessed,
                            "collective_bytes": hc.collective_bytes,
                            "collective_counts": hc.collective_counts,
                            "collective_bytes_by_kind":
                                hc.collective_bytes_by_kind,
                            "while_trip_counts": hc.while_trip_counts,
                            "unresolved_whiles": hc.unresolved_whiles,
                        }
                    json.dump(rec, open(out_path, "w"), indent=1)
                    peak_gb = rec["memory"]["peak_per_device"] / 2 ** 30
                    print(f"[ok] {tag} compile={t_compile:.0f}s "
                          f"peak/dev={peak_gb:.2f}GiB "
                          f"fits16G={'YES' if peak_gb <= 16 else 'NO'}")
                except Exception as e:  # noqa: BLE001
                    failures.append(tag)
                    rec = {"cell": tag, "status": "fail",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-4000:]}
                    json.dump(rec, open(out_path + ".fail", "w"), indent=1)
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
    print(f"\ndone; failures: {len(failures)}")
    for f in failures:
        print("  FAIL", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
