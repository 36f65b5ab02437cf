"""The entry points' process discipline: one process per chip, a compile
cache that can be placed from outside, no failure swallowed into exit 0.

Everything that must not leak into the test process (a jax config change,
``import bench``) runs in a child pinned to the CPU."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env=None):
    """A child of the checkout on ONE plain CPU device."""
    e = os.environ.copy()
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("XLA_FLAGS", None)
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=e,
                          capture_output=True, text=True, timeout=240)


def _child(code, env=None):
    return _run(["-c", code], env)


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-set", "env-unset"])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no path; unset,
    the cache sits at ONE fixed path inside the checkout."""
    code = ("import jax\n"
            "from brpc_tpu.butil import compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "d = compile_cache.enable()\n"
            "print(repr((before, d, "
            "jax.config.jax_compilation_cache_dir)))\n")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    proc = _child(code, env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    before, used, after = eval(proc.stdout.strip().splitlines()[-1])
    if from_env:
        assert before == used == after == str(tmp_path)
    else:
        assert before is None
        assert used == after == os.path.join(REPO, ".jax_cache")


def test_importing_the_library_sets_no_cache():
    proc = _child("import jax, brpc_tpu, brpc_tpu.ici, brpc_tpu.rpc\n"
                  "assert jax.config.jax_compilation_cache_dir is None\n")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_bench_parent_stays_off_jax_and_names_a_failed_tier():
    """bench.main() orchestrates children and never imports jax; a tier
    that fails is named, the headline is "not measured" (no stand-in from
    another tier) and the exit code is non-zero."""
    code = (
        "import json, sys\n"
        "import bench\n"
        "def tier(name, failed):\n"
        "    if name == 'echo':\n"
        "        failed.append(name)\n"
        "        return {}\n"
        "    if name == 'native':\n"
        "        return {'rpc_p50_us': 9.0, 'device': 'host'}\n"
        "    return {}\n"
        "bench._run_tier = tier\n"
        "rc = bench.main()\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "sys.exit(rc)\n")
    proc = _child(code)
    assert proc.returncode == 1, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["failed_tiers"] == ["echo"]
    assert out["value"] is None and "not measured" in out["metric"]
    assert out["extra"]["native_tcp_echo_p50_us"] == 9.0
    assert "FAILED tiers: echo" in proc.stderr


def test_bench_mesh_tier_on_one_device_is_not_measured():
    """No re-run on a virtual CPU mesh: a tier that needs two devices
    says "not measured" on a one-device host, and names that device."""
    proc = _run(["bench.py", "--sub", "relocation"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "not_measured" in out
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_bench_tier_that_produces_nothing_fails_its_child():
    code = ("import sys, bench\n"
            "bench._TIERS['qps'] = (lambda: {}, {})\n"
            "bench._run_sub('qps')\n")
    proc = _child(code)
    assert proc.returncode != 0
    assert "produced no result" in proc.stderr


def test_chip_smoke_refuses_to_pass_off_the_chip():
    """Anywhere but on a TPU: non-zero exit and NO result line."""
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
