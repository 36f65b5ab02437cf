"""The entry points' process discipline: one process per chip, a compile
cache that can be placed from outside, no failure swallowed into exit 0.

Everything that must not leak into the test process (a jax config
change) runs in a child pinned to the CPU."""
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


def test_chip_smoke_refuses_to_pass_off_the_chip():
    """Anywhere but on a TPU: non-zero exit and NO result line."""
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
