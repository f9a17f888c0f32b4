"""``chip_smoke.py`` off the chip: its bodies at the reduced config on the
CPU, its refusal of any platform but TPU, and the compile-cache rule of the
entry points."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env=None, cwd=ROOT, timeout=300):
    env = dict(os.environ if env is None else env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


def test_smoke_body_reduced(chip_smoke):
    """The one-chip body end to end at the reduced config: logit and kernel
    checks pass, every request of the control loop finishes exactly once."""
    out = chip_smoke.smoke("granite-3-8b-reduced", max_seq=64, ticks=12)
    assert out["requests"] > 10 and out["tokens"] >= out["requests"]
    assert out["balanced"] and out["double_served"] == 0
    assert out["serve_kernel_traces"] > 0
    assert out["logit_rel_err"] <= chip_smoke.LOGIT_RTOL
    assert out["kernel_err"] <= chip_smoke.KERNEL_TOL


def test_sharded_body_reduced():
    """The four-chip body on four virtual CPU devices (the device count is
    fixed at backend start, hence the child process)."""
    code = ("import json, chip_smoke; print(json.dumps(chip_smoke."
            "sharded_smoke('granite-3-8b-reduced', n_devices=4, "
            "max_seq=64)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = _run(["-c", code], env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["requests"] == 16
    assert out["differ"] == 0          # one CPU: sharding is bit-exact


def test_refuses_cpu():
    res = _run([SCRIPT], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    res = _run([str(lone)], env=dict(os.environ, JAX_PLATFORMS="cpu"),
               cwd=tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins where set; otherwise the fixed
    ``<checkout>/.jax_cache``. Run in a child: tests keep the cache off."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache as e; d = e(); "
            "print(d, jax.config.jax_compilation_cache_dir)")
    res = _run(["-c", code], env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    used, seen = res.stdout.split()
    want = str(tmp_path / env_dir) if env_dir else \
        os.path.join(ROOT, ".jax_cache")
    assert used == seen == want
