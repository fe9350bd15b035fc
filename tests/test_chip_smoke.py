"""``chip_smoke.py`` on the CPU: its phases at tiny sizes in interpret mode
(the 4-device shardmap path on emulated host devices in a subprocess), and
its refusal to report a result without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as C  # noqa: E402


@pytest.fixture(scope="module")
def solved():
    return C.cg_compare(8, 8, "pallas")


def test_cg_phase_pallas_matches_global(solved):
    p, g = solved["pallas"], solved["global"]
    assert p["iters"] == g["iters"] > 0
    assert p["rel_residual"] <= 1e-5 and g["rel_residual"] <= 1e-5
    assert solved["solution_rel_diff"] <= 1e-3


def test_halo_and_kernel_phases(solved):
    halo = C.halo_compare(solved["da"], dof=4)
    assert halo["l2g_rel_err"] <= 1e-6
    n = C.kernel_candidates(solved["da"], halo["local"], moe_rows=48,
                            moe_width=256, moe_picks=40)
    assert n >= 8          # pack, segment, strided and fused candidates


def test_serving_phase_matches_dense_dispatch():
    from repro.configs import get_config
    cfg = get_config(C.MODEL).smoke_config().scaled(n_layers=2)
    out = C.serving(cfg, batch=2, s_max=64, n_requests=2, prompt_len=(9, 30),
                    new_tokens=3)
    assert out["metrics"]["requests_finished"] == 2
    assert out["logit_rel_diff_max"] <= 0.05


def test_check_raises_on_failure():
    with pytest.raises(C.CheckFailed, match="broken"):
        C.check(False, "broken")


def test_four_device_shardmap_phase_subprocess():
    """The --four-chips path (DistSF in shard_map over a 4-device mesh)
    on emulated host devices."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys, json
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as C
        out = C.cg_compare(8, 4, "shardmap")
        print("RESULT", json.dumps({{k: v for k, v in out.items()
                                     if k != "da"}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["shardmap"]["iters"] == out["global"]["iters"]
    assert out["solution_rel_diff"] <= 1e-3


def test_main_without_tpu_fails_and_reports_nothing(capsys):
    assert C.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Outside the repository (the script and nothing else) it fails."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
