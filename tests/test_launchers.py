"""CLI launchers run end-to-end (subprocess smoke) — slow, --runslow."""
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(args, extra_env=None):
    import os

    # pin the jax platform: without it each subprocess burns minutes
    # probing for accelerator plugins before falling back to CPU
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m"] + args, cwd=ROOT, capture_output=True,
        text=True, timeout=500, env=env)


def test_train_cli(tmp_path):
    r = _run(["repro.launch.train", "--arch", "olmo-1b", "--smoke",
              "--steps", "6", "--batch", "2", "--seq", "16",
              "--ckpt", str(tmp_path)])
    assert "final step 6" in r.stdout, r.stderr[-1500:]


def test_serve_cli():
    r = _run(["repro.launch.serve", "--arch", "qwen2.5-3b", "--smoke",
              "--quant", "w4a8", "--requests", "2", "--batch", "2",
              "--max-new", "4"])
    assert "tok/s" in r.stdout, r.stderr[-1500:]


def test_serve_cli_mesh():
    """--mesh dp,tp serves on a forced-host-device cluster and prints
    each device's mean slot occupancy over the serve's steps."""
    r = _run(["repro.launch.serve", "--arch", "qwen2.5-3b", "--smoke",
              "--quant", "w4a8", "--requests", "6", "--batch", "4",
              "--max-new", "4", "--mesh", "4,2"],
             extra_env={"XLA_FLAGS":
                        "--xla_force_host_platform_device_count=8"})
    assert "mesh: data=4 model=2" in r.stdout, r.stderr[-1500:]
    # one slot per device; the seed-0 prompts (2-7 tokens) and 4 new
    # tokens each keep device 2 busy in all 17 steps of the serve
    assert "per-device occupancy: mean 71% over 17 step(s)" in r.stdout
    assert "[d0=59% d1=76% d2=100% d3=47%]" in r.stdout


def test_fig9_cluster_bench_cli(tmp_path):
    """The fig. 9 benchmark runs the sharded path end-to-end over
    --devices 1,2,4,8 and emits BENCH_cluster.json with a speedup
    column (ISSUE-4 acceptance criterion; the script forces 8 host
    devices itself when XLA_FLAGS is unset)."""
    import json

    out = tmp_path / "BENCH_cluster.json"
    r = _run(["benchmarks.fig9_cluster_scaling", "--devices", "1,2,4,8",
              "--json", str(out)])
    assert out.exists(), r.stderr[-1500:]
    sys.path.insert(0, str(ROOT))
    from benchmarks import schema
    schema.validate_file(out)           # the checked-in artifact schema
    d = json.loads(out.read_text())
    assert d["path"] == "repro.kernels.api.qdot_sharded"
    rows = d["rows"]
    assert {row["devices"] for row in rows} == {1, 2, 4, 8}
    assert {row["bits"] for row in rows} == {8, 4, 2}
    for row in rows:
        assert "speedup" in row and "efficiency" in row
        if row["devices"] == 1:
            assert row["speedup"] == 1.0


def test_deploy_then_serve_plan_cli(tmp_path):
    """calibrate -> plan -> pack via the deploy CLI, then serve the plan:
    mixed artifact must be strictly smaller than the uniform-w8 one."""
    plan = tmp_path / "plan.json"
    r = _run(["repro.launch.deploy", "--arch", "qwen2.5-3b", "--smoke",
              "--budget", "auto", "--out", str(plan)])
    assert "deploy done" in r.stdout, r.stderr[-1500:]
    import json
    import re
    d = json.loads(plan.read_text())
    assert len({rule["w_bits"] for rule in d["rules"]}) >= 2
    m = re.search(r"uniform-w8 ([\d,]+)\s+mixed ([\d,]+)", r.stdout)
    assert m, r.stdout
    w8, mixed = (int(g.replace(",", "")) for g in m.groups())
    assert mixed < w8
    r2 = _run(["repro.launch.serve", "--arch", "qwen2.5-3b", "--smoke",
               "--plan", str(plan), "--requests", "3", "--batch", "2",
               "--max-new", "4"])
    assert "tok/s" in r2.stdout, r2.stderr[-1500:]
    m2 = re.search(r"\((\d[\d,]*) bytes\)", r2.stdout)
    assert m2 and int(m2.group(1).replace(",", "")) == mixed
